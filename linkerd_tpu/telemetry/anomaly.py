"""``io.l5d.jaxAnomaly`` — the inline ML-inference telemeter (north star).

BASELINE.json: a telemeter that taps the router stack, extracts per-request
feature vectors, micro-batches them to a JAX/TPU anomaly scorer
(autoencoder + classifier), and feeds scores back into failure-accrual /
response-classification policy plus the admin metrics surface.

Data path (all off the request critical path — the recorder filter does
O(1) Python work per request; everything else is batched):

    request -> FeatureRecorder filter -> ring buffer (deque)
            -> micro-batcher task (drain + featurize -> float32[B, D])
            -> scorer (in-process jit OR gRPC sidecar)
            -> ScoreBoard (per-dst EWMA scores, Var + metrics gauges)
            -> AnomalyFailureAccrualPolicy / admin handlers

Reference parity: implements the Telemeter SPI (telemetry/core/.../
Telemeter.scala:11) the way exporter telemeters do, but taps the stack the
way the reference's stats filters do (PerDstPathStatsFilter.scala).
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from linkerd_tpu.config import register
from linkerd_tpu.control.loop import ControlConfig
from linkerd_tpu.core import Var
from linkerd_tpu.distill import DistillConfig
from linkerd_tpu.lifecycle import LifecycleConfig
from linkerd_tpu.models.features import FEATURE_DIM, FeatureVector, featurize_batch
from linkerd_tpu.protocol.http.message import Request, Response
from linkerd_tpu.router.service import Filter, Service
from linkerd_tpu.telemetry import phases
from linkerd_tpu.telemetry.metrics import MetricsTree
from linkerd_tpu.telemetry.telemeter import Telemeter

log = logging.getLogger(__name__)


class ScoreBoard:
    """Per-dst anomaly scores: EWMA-smoothed, observable, with a
    staleness TTL.

    The Var publishes {dst_path: score}; failure-accrual policies and the
    admin handler read it. Scores decay toward 0 when traffic stops, and
    — independently — go STALE when the scorer stops updating them (a
    degraded scorer path must not pin accrual policies to an old anomaly
    verdict): within ``ttl_s`` of the last update a score reads at full
    strength, then decays linearly to neutral (0) over one further
    ``ttl_s`` window. ``degraded`` is set by the telemeter while the
    scorer breaker is open; anomaly-aware policies treat it as
    "no signal" and fall back to their reference behavior.
    """

    def __init__(self, alpha: float = 0.3, ttl_s: Optional[float] = 30.0):
        self.alpha = alpha
        self.ttl_s = ttl_s
        self.scores: Var[dict] = Var({})
        self.degraded = False
        self._updated: Dict[str, float] = {}
        # per-REPLICA scores keyed by endpoint hostport (the balancer
        # stamps req.ctx["endpoint"] at pick time): the control loop's
        # score-weighted balancer reads these; same EWMA + staleness
        # machinery as the per-dst board
        self._ep_scores: Dict[str, float] = {}
        self._ep_updated: Dict[str, float] = {}

    def update_batch(self, dsts: List[str], scores: np.ndarray,
                     endpoints: Optional[List[Optional[str]]] = None,
                     ) -> None:
        now = time.monotonic()
        cur = dict(self.scores.sample())
        per_dst: Dict[str, List[float]] = {}
        per_ep: Dict[str, List[float]] = {}
        for i, (dst, s) in enumerate(zip(dsts, scores)):
            per_dst.setdefault(dst, []).append(float(s))
            if endpoints is not None and i < len(endpoints) \
                    and endpoints[i]:
                per_ep.setdefault(endpoints[i], []).append(float(s))
        for dst, vals in per_dst.items():
            mean = sum(vals) / len(vals)
            prev = cur.get(dst, mean)
            cur[dst] = prev + self.alpha * (mean - prev)
            self._updated[dst] = now
        for ep, vals in per_ep.items():
            mean = sum(vals) / len(vals)
            prev = self._ep_scores.get(ep, mean)
            self._ep_scores[ep] = prev + self.alpha * (mean - prev)
            self._ep_updated[ep] = now
        # endpoint keys churn with the replica set (hostports change on
        # every deploy); fully-stale entries are dead replicas — prune,
        # or the maps grow without bound on a long-running linker
        if self.ttl_s is not None and per_ep:
            dead = [ep for ep, upd in self._ep_updated.items()
                    if now - upd > 2 * self.ttl_s]
            for ep in dead:
                self._ep_scores.pop(ep, None)
                self._ep_updated.pop(ep, None)
        self.scores.update(cur)

    def _decay(self, updated: Optional[float], now: float) -> float:
        if self.ttl_s is None:
            return 1.0
        if updated is None:
            return 1.0  # pre-TTL boards (tests seed Var directly)
        age = now - updated
        if age <= self.ttl_s:
            return 1.0
        return max(0.0, 1.0 - (age - self.ttl_s) / self.ttl_s)

    def _staleness_factor(self, dst: str, now: float) -> float:
        return self._decay(self._updated.get(dst), now)

    def score_of(self, dst: str) -> float:
        raw = self.scores.sample().get(dst, 0.0)
        return raw * self._staleness_factor(dst, time.monotonic())

    def effective_scores(self) -> Dict[str, float]:
        """{dst: staleness-decayed score} — the policy-facing view."""
        now = time.monotonic()
        return {dst: s * self._staleness_factor(dst, now)
                for dst, s in self.scores.sample().items()}

    def endpoint_score_of(self, hostport: str) -> float:
        """Per-replica effective score: staleness-decayed, and neutral
        while the scorer path is degraded (a dead scorer must not pin
        a replica's down-weight)."""
        if self.degraded:
            return 0.0
        raw = self._ep_scores.get(hostport, 0.0)
        return raw * self._decay(self._ep_updated.get(hostport),
                                 time.monotonic())

    def effective_endpoint_scores(self) -> Dict[str, float]:
        if self.degraded:
            return {ep: 0.0 for ep in self._ep_scores}
        now = time.monotonic()
        return {ep: s * self._decay(self._ep_updated.get(ep), now)
                for ep, s in self._ep_scores.items()}

    def anomaly_level(self) -> float:
        """Mesh-wide anomaly level: max effective score, 0 while the
        scorer path is degraded (no signal beats a stale signal)."""
        if self.degraded:
            return 0.0
        return max(self.effective_scores().values(), default=0.0)


class FeatureRecorder(Filter[Request, Response]):
    """Tap the request path: record one FeatureVector per request into the
    ring. O(1) appends; the deque drops oldest under overload (scoring is
    best-effort, requests are never blocked). ``on_record`` (the
    telemeter's enqueue hook) counts the request toward the scored
    fraction and wakes the line-rate micro-batcher."""

    def __init__(self, ring: Deque,
                 on_record: Optional[Callable[[], None]] = None):
        from linkerd_tpu.models.features import DstTemporal
        self.ring = ring
        self._on_record = on_record
        self._inflight = 0
        self._rps_window: Deque[float] = collections.deque(maxlen=512)
        self._temporal = DstTemporal()

    async def apply(self, req: Request, service: Service) -> Response:
        t0 = time.monotonic()
        self._inflight += 1
        exc: Optional[BaseException] = None
        rsp: Optional[Response] = None
        try:
            rsp = await service(req)
            return rsp
        except BaseException as e:
            exc = e
            raise
        finally:
            self._inflight -= 1
            now = time.monotonic()
            self._rps_window.append(now)
            latency_ms = (now - t0) * 1e3
            dst = req.ctx.get("dst")
            dst_path = dst.path.show if dst is not None else "/unidentified"
            rc = req.ctx.get("response_class")
            status = rsp.status if rsp is not None else 0
            is_err = exc is not None or status >= 500
            drift, err_rate, rate_delta, mesh_err = self._temporal.observe(
                dst_path, latency_ms, is_err, now)
            fv = FeatureVector(
                latency_ms=latency_ms,
                status=status,
                retries=int(req.ctx.get("retries", 0)),
                # h2 messages carry streams, not bodies; size 0 there
                request_bytes=len(getattr(req, "body", b"") or b""),
                response_bytes=(len(getattr(rsp, "body", b"") or b"")
                                if rsp is not None else 0),
                concurrency=self._inflight + 1,
                queue_ms=0.0,
                exception=exc is not None,
                retryable=bool(getattr(rc, "is_retryable", False)),
                dst_path=dst_path,
                dst_rps=self._rps(now),
                lat_drift_ms=drift,
                dst_err_rate=err_rate,
                rate_delta=rate_delta,
                mesh_err_rate=mesh_err,
            )
            # label for fault-injection evaluation rides along when present:
            # from local ctx, or from the harness's response header
            label = req.ctx.get("fault_label")
            if label is None and rsp is not None:
                hdr = rsp.headers.get("l5d-fault-label")
                if hdr is not None:
                    try:
                        label = float(hdr)
                    except ValueError:
                        label = None  # untrusted header; never fail a request
            # the request's trace context + enqueue instant ride along so
            # the micro-batcher can emit scorer spans as children of the
            # originating request (ring wait = the span's queue
            # annotation); the balancer-picked endpoint rides too so the
            # board can score per replica (the control loop's weigher)
            self.ring.append((fv, label, req.ctx.get("trace"), now,
                              req.ctx.get("endpoint")))
            if self._on_record is not None:
                self._on_record()

    def _rps(self, now: float) -> float:
        w = self._rps_window
        if len(w) < 2:
            return 0.0
        span = now - w[0]
        return len(w) / span if span > 0 else 0.0


class Scorer:
    """Scoring + online-training backends. ``score`` takes float32[B, D]
    and returns float32[B] anomaly scores in [0, 1].

    Lifecycle hooks: ``snapshot``/``restore``/``swap`` capture and
    hot-swap the full training state (params, optimizer, normalization
    stats, step counter) without recreating the scorer. They may be sync
    (in-process: device transfers happen off the event loop via
    ``asyncio.to_thread``) or async (gRPC sidecar).

    ``last_timing``: per-call decomposition of the most recent score()
    ({queue_ms, transfer_ms, device_ms, hop_ms, bytes} in-process, read
    off the ring path's phase record; {rpc_ms} for the sidecar) — the
    source for scorer-span annotations. None until the first scored
    batch; backends without instrumentation leave it None."""

    last_timing: Optional[dict] = None

    async def score(self, x: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    async def fit(self, x: np.ndarray, labels: np.ndarray,
                  mask: np.ndarray) -> float:
        raise NotImplementedError

    def snapshot(self):
        raise NotImplementedError

    def restore(self, snap) -> None:
        raise NotImplementedError

    def swap(self, snap):
        """Restore ``snap`` and return the previous state's snapshot."""
        raise NotImplementedError

    def close(self) -> None:
        return


class InProcessScorer(Scorer):
    """Runs the JAX model in-process, dispatched at line rate.

    The score path has NO per-call thread hop and NO fresh full-batch
    ``device_put``: batches land in persistent double-buffered staging
    buffers (one pair per padded batch bucket), the jitted score step
    takes the device copy with ``donate_argnums`` (XLA reuses the
    buffer instead of allocating per batch), and dispatch rides JAX
    async dispatch — a single background drainer thread does the
    blocking readback, so host→device transfer of batch N overlaps
    device compute of batch N-1 and the event loop never blocks on the
    device (see telemetry/linerate.RingDispatcher).

    The model is a ``models.spec.ModelSpec`` (default ``mlp36``): what a
    row is, how the parameters are drawn, the score step, whether it
    trains, and whether it keeps state on the device between calls. Both
    models ride the same ring, drainer and phase clock.

    ``mlp36`` with more than one device runs the SAME serving path
    sharded: a dp x tp mesh from parallel/mesh.py, params placed per the
    Megatron column/row specs, micro-batches fed per-device via
    ``parallel.mesh.shard_batch`` (each device receives exactly its
    shard; no single host-side device_put of the full batch) — XLA
    inserts the ICI collectives. Single-chip keeps the fused-Pallas
    kernel (ops/scoring.best_scorer).

    The flow model (``latent_moe``) is single-device and frozen: more than
    one device raises, ``fit`` and ``snapshot``/``restore`` raise (its
    weights arrive whole: ``load``, never an online step; until then they
    are drawn from the seed and ``weights`` says so). It is keyed: its
    cache and flow lengths live on the device between calls, ``score``
    maps the call's stream keys to slots on the loop
    (``telemetry/flowstate.FlowTable``, span ``flow.map``), the step takes
    the state donated and hands back the new one at launch, and calls
    apply in the order ``score`` was called, two in flight. A call that
    fails before its launch leaves table and state as they were."""

    def __init__(self, seed: int = 0, learning_rate: float = 1e-3,
                 recon_weight: float = 0.7, fit_steps: int = 4,
                 devices=None, spec=None):
        import jax
        from linkerd_tpu.models.spec import mlp36
        from linkerd_tpu.telemetry.linerate import RingDispatcher

        self.spec = spec if spec is not None else mlp36(recon_weight)
        self.cfg = self.spec.cfg
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) > 1 and self.spec.single_device:
            raise ValueError(
                f"model {self.spec.name!r} is single-device: "
                f"{len(devices)} devices were given")
        self.mesh = None
        self._batch_multiple = 1
        self._devices = devices
        self.fit_steps = fit_steps
        # cumulative train steps; checkpointed so a restored model resumes
        # its lineage, not a fresh step count
        self._step = 0
        # fit() calls per compiled shape ("<padded rows>", "+mask" when
        # padding rows are masked out): with the dispatcher's per-bucket
        # score counts, every program this scorer made XLA compile
        self._fit_batches: Dict[str, int] = {}
        # "seed" until a ``restore`` or a ``load`` has brought weights in
        self.weights = "seed"
        if len(devices) > 1:
            import optax
            from linkerd_tpu.models.spec import reads_norm
            from linkerd_tpu.parallel.mesh import (
                init_sharded, make_mesh, make_score_step, make_train_step,
            )
            # parallel/mesh.py lays out mlp36 alone, by name: any other
            # spec is single_device and was refused above.
            # width-aware tp heuristic: at this model's scale the mesh
            # comes out pure-data (tp only engages for wide layers)
            self._opt = optax.adam(learning_rate)
            self.mesh = make_mesh(devices,
                                  model_width=max(self.cfg.enc_dims))
            self.params, self._opt_state = init_sharded(
                self.mesh, jax.random.key(seed), self._opt, self.cfg)
            # the one jitted score step DONATES its input batch: every
            # caller hands it a buffer it never re-reads (the dispatch
            # ring's staging copy, or a fresh per-call device_put on
            # the instrumented path)
            self._scorer = reads_norm(
                make_score_step(self.mesh, self.cfg, donate=True))
            self._train_step = make_train_step(self.mesh, self._opt, self.cfg)
            self._batch_multiple = self.mesh.shape["data"]
            self.score_path = "mesh"
            state = self.spec.init_state()
        else:
            # drawn where they will live (the flow model's 7 GB never
            # pass through another device), then committed there: an
            # explicit device choice (e.g. pin to the second chip) is
            # honored, and jit follows the params' placement
            with jax.default_device(devices[0]):
                params = self.spec.init(jax.random.key(seed))
                state = self.spec.init_state()
            self.params = jax.device_put(params, devices[0])
            # selected by where the params live, never probed: a kernel
            # Mosaic refuses on the chip raises out of the first score
            self._scorer = self.spec.make_step(devices[0].platform)
            self.score_path = self.spec.score_path(devices[0].platform)
            if self.spec.trains:
                import optax
                self._opt = optax.adam(learning_rate)
                # committed like the params: adam's fresh step count comes
                # back uncommitted, and the first train step would compile
                # once for it and again for its own committed outputs
                self._opt_state = jax.device_put(
                    self._opt.init(self.params), devices[0])
                self._train_step = self._mk_train_step()
        # What a score step reads beside the parameters, ON THE DEVICE, as
        # ONE attribute stored once. A keyed model's step takes it donated
        # and the new one replaces it at launch. mlp36's is the running
        # feature normalization ``(mu, var, initialised)`` (updated on
        # non-anomalous training rows): without it the autoencoder's
        # reconstruction error is dominated by raw feature scale and
        # tanh() saturates for normal AND anomalous traffic alike. A fit
        # reduces its batch's mean and variance on the device
        # (``_norm_step``) from the copy of the batch its train steps
        # read, and the jitted steps apply
        # models.anomaly.normalize_features there too — the z-score with
        # its 1e-2 soft variance floor (a near-constant training dim must
        # register novelty as a LARGE z-score, not a 1e3-sigma blowup;
        # hard clipping cost ~0.15 AUC on the k8s-restart benchmark). The
        # host never reads a batch; ``snapshot()`` fetches the triple;
        # ``score`` captures a fit's pair whole, never a new ``mu`` beside
        # an old ``var``.
        self._state = self._put_state(state)
        if self.spec.trains:
            self._norm_momentum = 0.2
            self._norm_step = self._mk_norm_step()
        # a keyed model's rows are laid out by the host's table, on the
        # loop, in call order (``_map_rows``)
        self._table = self.spec.make_table() if self.spec.keyed else None
        # persistent double-buffered staging ring (the line-rate
        # dispatch path; see class docstring)
        self._dispatcher = RingDispatcher(
            self.spec.row_width, self._bucket_target,
            dtype=self.spec.row_dtype,
            prepare=self._map_rows if self.spec.keyed else None)

    @property
    def last_timing(self) -> Optional[dict]:
        """The newest score call's phases on the ring path, as the scorer
        spans tag them (COMPONENTS.md, scorer-path spans). ``device_ms``
        is the drainer's wait for the device, transfer included."""
        rec = self._dispatcher.last
        if rec is None:
            return None
        return {"queue_ms": rec.ms(phases.SLOT_WAIT),
                "transfer_ms": rec.ms(phases.STAGE, phases.PUT,
                                      phases.READBACK),
                "device_ms": rec.ms(phases.DEVICE_WAIT),
                "hop_ms": rec.ms(phases.HOP),
                "bytes": (rec.counts.get("put.bytes", 0)
                          + rec.counts.get("readback.bytes", 0))}

    def device_state(self) -> dict:
        """What this scorer actually runs on, as JAX reports it, plus the
        score path it built and the batch shapes it has dispatched —
        the ``device`` block of /model.json. ``chip_smoke.py`` and the
        bench read the platform from here: a linker that came up on the
        CPU says so instead of serving under TPU names unnoticed.

        Keys: ``platform``, ``device_kind``, ``count`` (devices JAX sees),
        ``model`` (the spec's name), ``weights`` (``seed`` as drawn at
        construction, ``loaded`` once a ``restore`` or a ``load`` brought
        them), ``score_path``, ``mesh`` (axis sizes,
        or None), ``score_batches`` / ``fit_batches`` (calls per compiled
        shape), and what the spec's ``describe`` adds. The flow model adds
        ``flow``: ``slots``, ``positions``, ``experts_held``,
        ``layer_share``, ``attention`` and ``expert_product``
        (``fused_pallas`` | ``xla``: what the step was built with: the
        attention over a slot, the routed experts' grouped product),
        ``resident`` flows, ``layouts`` (calls
        per ``FxT`` layout the step was compiled for) and ``expert_tokens``
        (the newest call's tokens per expert layer and held expert)."""
        import jax

        d0 = self._devices[0]
        state = {
            "platform": d0.platform,
            "device_kind": d0.device_kind,
            "count": len(jax.devices()),
            "model": self.spec.name,
            "weights": self.weights,
            "score_path": self.score_path,
            "mesh": (dict(self.mesh.shape)
                     if self.mesh is not None else None),
            "score_batches": {str(b): n for b, n in
                              sorted(self._dispatcher.batches.items())},
            "fit_batches": dict(self._fit_batches),
        }
        if self.spec.describe is not None:
            state.update(self.spec.describe(
                self._table, self._dispatcher.last_extras or {}))
        return state

    def _put_rows(self, arr: np.ndarray):
        """Place a host array whose leading axis is the batch's rows: on
        the pinned device, or each data-axis shard on its own device.
        Returns before the bytes move."""
        if self.mesh is not None:
            from linkerd_tpu.parallel.mesh import shard_batch
            return shard_batch(self.mesh, arr)
        import jax
        return jax.device_put(arr, self._devices[0])  # l5d: ignore[jax-hotpath] — async placement: the score path's persistent staging buffer (donated to the step, never re-read) or a fit's batch, once a fit

    def _put_state(self, state):
        """The state as the jitted steps take it: committed to the pinned
        device (no copy of what was made there), replicated over the
        mesh (mlp36's triple is tiny)."""
        import jax

        if self.mesh is not None:
            from linkerd_tpu.parallel.mesh import replicated
            return jax.device_put(state, replicated(self.mesh))
        return jax.device_put(state, self._devices[0])

    @property
    def _norm_initialized(self) -> bool:
        """Whether a fit has set the statistics yet. Blocking (reads the
        device's flag): for snapshots and tests, not the serving path."""
        return bool(self._state[2])

    def _mk_norm_step(self):
        """The statistics program (``models.anomaly.running_norm``): a
        jitted program of its own, apart from the train step, run once a
        fit on the fit's resident batch."""
        import jax
        from linkerd_tpu.models.anomaly import running_norm

        momentum = self._norm_momentum

        # a def and not a partial: the program's name in a trace is
        # ``jit_norm_step`` (a partial's is ``jit__unknown``)
        def norm_step(norm, x, labels, mask, row_mask=None):
            return running_norm(norm, x, labels, mask, row_mask,
                                momentum=momentum)

        if self.mesh is not None:
            from linkerd_tpu.parallel.mesh import replicated
            # XLA inserts the reduction over the data axis
            return jax.jit(norm_step, out_shardings=replicated(self.mesh))
        return jax.jit(norm_step)

    def _mk_train_step(self):
        import jax
        import optax
        from linkerd_tpu.models.anomaly import loss_fn, normalize_features

        cfg = self.cfg
        opt = self._opt

        @jax.jit
        def step(params, opt_state, x, labels, mask, row_mask=None,
                 mu=None, var=None):
            if mu is not None:
                with jax.named_scope("normalize"):
                    x = normalize_features(x, mu, var)
            with jax.named_scope("loss_grad"):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, x, labels, mask, cfg, row_mask)
            with jax.named_scope("adam"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    def _bucket_target(self, n: int) -> int:
        """Padded batch size for ``n`` rows: next power of two, rounded
        up to a multiple of the data-axis size (sharded arrays must
        divide evenly over the mesh). Bucketing batch shapes bounds the
        number of distinct XLA compilations to ~log2(maxBatch) instead
        of one per batch size — and bounds the dispatch ring to one
        staging pair per bucket."""
        from linkerd_tpu.telemetry.sidecar import bucket_rows
        target = bucket_rows(n)
        m = self._batch_multiple
        if m > 1 and target % m:
            target += m - target % m
        return target

    def _must_train(self, what: str) -> None:
        if not self.spec.trains:
            raise RuntimeError(
                f"model {self.spec.name!r} is frozen: it has no optimizer "
                f"and no online fit, so no {what}()")

    def _pad_rows(self, arr: np.ndarray) -> np.ndarray:
        n = len(arr)
        target = self._bucket_target(n)
        if target == n:
            return arr
        widths = ((0, target - n),) + ((0, 0),) * (arr.ndim - 1)
        return np.pad(arr, widths)

    # -- lifecycle: snapshot / restore / swap -----------------------------
    def snapshot(self):
        """Capture the full training state to host memory: params,
        optimizer state, normalization stats, config, step counter. The
        returned ModelSnapshot restores to bit-identical scores on the
        same backend. Blocking (device->host transfer) — call off the
        event loop (the lifecycle manager uses asyncio.to_thread)."""
        import jax

        from linkerd_tpu.lifecycle.store import ModelSnapshot

        self._must_train("snapshot")
        params = jax.device_get(self.params)
        mu, var, initialized = jax.device_get(self._state)
        opt_leaves = [np.asarray(leaf) for leaf in
                      jax.tree_util.tree_leaves(
                          jax.device_get(self._opt_state))]
        return ModelSnapshot(
            params=params, opt_leaves=opt_leaves,
            mu=mu, var=var, norm_initialized=bool(initialized),
            step=self._step, cfg=self.cfg)

    def restore(self, snap) -> None:
        """Hot-swap a snapshot in: params re-placed per the current
        topology (the dp x tp mesh specs when sharded, the pinned device
        otherwise), optimizer state rebuilt leaf-for-leaf. The already
        compiled score/train steps keep working — shapes, dtypes, and
        shardings are unchanged, so no recompilation."""
        import jax

        from linkerd_tpu.lifecycle.store import _cfg_to_dict

        self._must_train("restore")
        if _cfg_to_dict(snap.cfg) != _cfg_to_dict(self.cfg):
            raise ValueError(
                f"snapshot config {snap.cfg_dict()} does not match "
                f"scorer config {_cfg_to_dict(self.cfg)}")
        if self.mesh is not None:
            from linkerd_tpu.parallel.mesh import place_snapshot
            self.params, self._opt_state = place_snapshot(
                self.mesh, self._opt, snap.params, snap.opt_leaves)
        else:
            params = jax.device_put(snap.params, self._devices[0])
            template = self._opt.init(params)
            t_leaves, treedef = jax.tree_util.tree_flatten(template)
            if len(snap.opt_leaves) != len(t_leaves):
                raise ValueError(
                    f"optimizer state mismatch: snapshot has "
                    f"{len(snap.opt_leaves)} leaves, optimizer expects "
                    f"{len(t_leaves)}")
            placed = []
            for leaf, t in zip(snap.opt_leaves, t_leaves):
                arr = np.asarray(leaf)
                if tuple(arr.shape) != tuple(t.shape):
                    raise ValueError(
                        f"optimizer leaf shape mismatch: snapshot "
                        f"{arr.shape} vs optimizer {tuple(t.shape)}")
                placed.append(jax.device_put(arr.astype(t.dtype),
                                             self._devices[0]))
            self.params = params
            self._opt_state = jax.tree_util.tree_unflatten(treedef, placed)
        self._state = self._put_state(
            (np.asarray(snap.mu, np.float32),
             np.asarray(snap.var, np.float32),
             np.bool_(snap.norm_initialized)))
        self._step = int(snap.step)
        self.weights = "loaded"

    def load(self, params) -> None:
        """A frozen model's weights arrive whole (the lifecycle's push
        lands here): the old parameters and the state, a function of
        them, are dropped BEFORE the new ones are placed (two copies of
        the flow model do not fit a chip), the table starts empty, and
        calls in flight finish on what they took. Blocking; between two
        calls on the loop."""
        import jax

        if self.spec.trains:
            raise RuntimeError(
                f"model {self.spec.name!r} trains: its weights come by "
                f"restore(), with their optimizer state")
        self.params = self._state = None
        with jax.default_device(self._devices[0]):
            state = self.spec.init_state()
        self.params = jax.device_put(params, self._devices[0])
        self._state = self._put_state(state)
        if self.spec.keyed:
            self._table = self.spec.make_table()
        self.weights = "loaded"

    def swap(self, snap):
        """Restore ``snap``; returns the displaced state so a failed
        promotion can be undone without a store round-trip."""
        old = self.snapshot()
        self.restore(snap)
        return old

    async def warmup(self, rows: int = 4) -> None:
        """Trigger compilation of the score and fit paths without letting
        the dummy rows contaminate normalization stats or parameters.
        Also exercises the snapshot->restore->score hot-swap path (host
        gather, re-placement, optimizer-state rebuild) so the first real
        swap doesn't stall the event loop."""
        if self.spec.keyed:
            # its state is its flows': a dummy call would leave a flow
            # behind; its programs compile on first use
            return
        rows = max(rows, self._batch_multiple, 1)
        x = np.zeros((rows, self.spec.row_width), self.spec.row_dtype)
        if not self.spec.trains:
            await self.score(x)
            return
        params, opt_state = self.params, self._opt_state
        norm = self._state
        step = self._step
        try:
            await self.score(x)
            await self.fit(x, np.zeros(rows, np.float32),
                           np.zeros(rows, np.float32))
            snap = await asyncio.to_thread(self.snapshot)
            await asyncio.to_thread(self.restore, snap)
            await self.score(x)
        finally:
            # startup-sequenced: warmup runs before the telemeter's drain
            # loop starts, so no concurrent fit/score exists to clobber
            self.params, self._opt_state = params, opt_state  # l5d: ignore[await-atomicity] — warmup is startup-sequenced; no concurrent mutator yet
            self._state = norm  # l5d: ignore[await-atomicity] — warmup is startup-sequenced; no concurrent mutator yet
            self._step = step  # l5d: ignore[await-atomicity] — warmup is startup-sequenced; no concurrent mutator yet

    def _prep(self, x: np.ndarray) -> np.ndarray:
        """Pad + cast to the f32 transfer dtype. Raw features ship as-is:
        normalization happens ON DEVICE inside the jitted step (under the
        device's own mu/var), fused into the first matmul's producer
        — so f32 precision is kept through the z-score (raw latencies in
        the thousands would lose mantissa bits if cast to bf16 before
        subtracting mu) and the sharded path normalizes each batch shard
        on its own device."""
        return self._pad_rows(np.asarray(x, np.float32))

    async def score(self, x: np.ndarray) -> np.ndarray:
        """Score [n, D] -> [n] through the donated staging ring. The
        event loop only pays one host memcpy into the staging slot plus
        JAX async dispatch; readback happens on the drainer thread.
        Hot-swap safety: ``params`` and the state are each read ONCE, at
        the launch — a concurrent ``restore``/``fit`` repoints the
        attributes but never mutates the (immutable) device arrays a call
        took, so an in-flight donated batch always completes against a
        consistent model. A step that hands back another state than it
        was given has advanced it (a keyed model's, donated): the new one
        replaces the old here, before any other call can map — so calls
        apply in order; a state handed back as it came is not stored, and
        so never written over one a fit or a restore has repointed
        since."""
        n = len(x)

        def put(staging: np.ndarray):
            # per-device shard feed on the mesh; the array is donated. A
            # call and not the bound method handed on: the jax-hotpath
            # lint follows calls, and this placement is on its path
            return self._put_rows(staging)

        def step(xd, plan=None):
            state = self._state     # one read: one fit's pair
            scores, new, counts = self._scorer(
                self.params, state, xd, n,
                None if plan is None else plan.layout)
            if new is not state:
                self._state = new
            return (scores, counts) if counts else scores

        return await self._dispatcher.dispatch(x, step, put)

    def _map_rows(self, x: np.ndarray, rec: phases.Call):
        """The dispatcher's ``prepare`` for a keyed model, run on the loop
        in call order once the slot is held: the host's table gives the
        call's flows their slots and positions, and moves forward at once,
        so the next call maps on top of this one. Returns the rows as the
        step takes them, the plan (whose layout the step is compiled for)
        and the ``undo`` that puts the table back if the call fails before
        its launch: such a call leaves table and state as they were."""
        saved = self._table.checkpoint()
        try:
            plan = self._table.map(x)
        except BaseException:
            self._table.rollback(saved)
            raise
        for name, v in plan.counts.items():
            rec.count(name, v)
        return plan.rows, plan, lambda: self._table.rollback(saved)

    async def fit(self, x: np.ndarray, labels: np.ndarray,
                  mask: np.ndarray) -> float:
        self._must_train("fit")
        rec = phases.Call(phases.FIT)
        try:
            return await self._fit(x, labels, mask, rec)
        finally:
            rec.close()

    async def _fit(self, x, labels, mask, rec: phases.Call) -> float:
        """The batch goes to the device ONCE: its statistics are reduced
        there and its ``fit_steps`` train steps read the same resident
        arrays. Placement, the statistics program's launch and the
        repointing of ``_norm`` all return before a byte moves and all
        happen before this coroutine first yields, so no call can begin
        between a fit's start and its statistics; the device runs its
        queue in order, so a score launched afterwards meets them."""
        n = len(x)
        rec.count("fit.calls")
        xn = self._prep(x)
        labels = self._pad_rows(np.asarray(labels, np.float32))
        mask = self._pad_rows(np.asarray(mask, np.float32))
        # row_mask excludes the padding rows from the statistics and from
        # BOTH loss terms so the sharded and single-chip paths train on
        # the same objective
        row_mask = (self._pad_rows(np.ones(n, np.float32))
                    if len(xn) != n else None)
        host = (xn, labels, mask, row_mask)
        shape = f"{len(xn)}+mask" if row_mask is not None else str(len(xn))
        self._fit_batches[shape] = self._fit_batches.get(shape, 0) + 1
        rec.mark(phases.PREP)
        batch = [None if a is None else self._put_rows(a) for a in host]
        rec.count("fit.shipped_bytes",
                  sum(a.nbytes for a in host if a is not None))
        norm = self._state = self._norm_step(self._state, *batch)
        rec.mark(phases.UPDATE_NORM)

        def run() -> float:
            rec.mark(phases.THREAD_HOP)
            loss = float("nan")
            for _ in range(self.fit_steps):
                self.params, self._opt_state, loss = self._train_step(
                    self.params, self._opt_state, *batch, norm[0], norm[1])
                rec.mark(phases.STEP)
            self._step += self.fit_steps
            loss = float(loss)
            rec.mark(phases.LOSS_WAIT)
            return loss

        loss = await asyncio.to_thread(run)
        rec.mark(phases.RETURN_HOP)
        return loss

    def close(self) -> None:
        self._dispatcher.close()


@register("telemeter", "io.l5d.jaxAnomaly")
@dataclass
class JaxAnomalyConfig:
    maxBatch: int = 1024
    ringCapacity: int = 65536
    scoreThreshold: float = 0.5
    trainEveryBatches: int = 8      # online-fit cadence (0 = never train)
    reconWeight: float = 0.7
    learningRate: float = 0.001
    # the scoring model (models/spec.py). "mlp36": the 36-column
    # autoencoder + classifier, every row scored on its own, trained
    # online. "latent_moe": a second, FROZEN tier beside it for flows:
    # engine rows that carry a stream key are scored by the flow model
    # (latent attention over a per-flow cache on the device, routed
    # experts) as the next event of their stream; rows without a key keep
    # the mlp36 path. The flow tier has no online fit: its weights arrive
    # whole, by ``InProcessScorer.load`` (where the lifecycle's push will
    # land). Until they have, the tier runs in SHADOW on weights drawn
    # from the seed: keyed rows are scored by both tiers, the flow tier's
    # scores are counted (flow_shadow_total) and not published.
    # Single-device: the first chip, whatever the host has.
    # "lfm2_moe", "laguna_moe": the same tier over another model's layers
    # (models/lfm2_moe.py: short convolutions among attention layers;
    # models/laguna_moe.py: window and full attention layers mixed, a
    # ring of the newest positions beside a cache of them all), by the
    # same step, table and dispatcher; "hy4_moe" (models/hy4_moe.py:
    # latent attention over an indexer's selection of positions, a
    # residual stream four wide) likewise.
    model: str = "mlp36"
    # line-rate micro-batcher: drain is size- and deadline-triggered —
    # a batch dispatches when maxBatch rows are pending OR the oldest
    # pending row has lingered maxLingerMs, whichever first — so 100%
    # of requests are scored with bounded added queue latency.
    maxLingerMs: float = 2.0
    scoreConcurrency: int = 2  # batches in flight (double-buffer depth)
    # gRPC sidecar address: "host:port" (one pinned replica),
    # "host:p1,host:p2" (static replica pool, load-balanced), or a
    # namer path "/#/io.l5d.fs/l5d-scorer" — announced scorer replicas
    # resolved through the linker's configured namers and load-balanced
    # like any other service (linkerd_tpu/fleet/scorer_pool.py)
    sidecarAddress: Optional[str] = None
    # sidecar tiering: "fallback" (default) serves every batch from the
    # in-process line-rate scorer and demotes the sidecar to a fallback
    # tier behind its breaker; "primary" keeps the sidecar as the one
    # scorer (the pre-line-rate wiring, used by the chaos harnesses)
    sidecarTier: str = "fallback"
    # in-data-plane scoring (the native tier): "primary" exports the
    # serving model as a versioned, CRC'd weight blob — published into
    # the fastpath engines' double-buffered weight slab at startup and
    # on every lifecycle promote/hot-swap — so engine rows arrive
    # PRE-SCORED (featurized and evaluated inside the epoll thread,
    # sub-ms added latency) and the JAX path only trains and serves
    # rows the engine could not score; "off" keeps every row on the
    # JAX tier. Python-path (non-fastPath) rows always score on JAX.
    nativeTier: str = "primary"
    # native blob weight encoding: f32 | int8 | int4 (int4 packs two
    # weights per byte — the smallest blobs/deltas; parity bound pinned
    # by test alongside the f32/int8 bounds)
    nativeQuant: str = "f32"
    # without a lifecycle: block there are no promote/rollback events
    # to chase, so the ONLINE-trained model is re-exported to the
    # engines on this cadence (seconds; 0 disables) — the native tier
    # must track training, not serve the startup init blob forever.
    # With a lifecycle, promotes republish and bound the staleness.
    nativeRefreshS: float = 30.0
    # scorer-path resilience (sidecar mode): per-call deadline, breaker
    # thresholds/probe backoffs, and the ScoreBoard staleness TTL (stale
    # scores decay to neutral so a dead scorer can't pin accrual policy)
    scoreTimeoutMs: int = 2000
    breakerFailures: int = 3
    breakerMinBackoffMs: int = 500
    breakerMaxBackoffMs: int = 30000
    scoreTtlSecs: float = 30.0
    # model lifecycle: checkpointing, shadow-eval promotion gating, drift
    # detection, restart restore (see linkerd_tpu/lifecycle/)
    lifecycle: Optional["LifecycleConfig"] = None
    # reactive control loop: score-weighted balancing, adaptive
    # admission, anomaly-triggered namerd dtab overrides (see
    # linkerd_tpu/control/)
    control: Optional["ControlConfig"] = None
    # continuous in-plane learning: drift-triggered distillation of
    # per-route specialist heads, shadow-gated and delta-published to
    # the engines' weight bank (see linkerd_tpu/distill/)
    distill: Optional["DistillConfig"] = None

    def mk(self, metrics: MetricsTree) -> "JaxAnomalyTelemeter":
        return JaxAnomalyTelemeter(self, metrics)


class JaxAnomalyTelemeter(Telemeter):
    def __init__(self, cfg: JaxAnomalyConfig, metrics: MetricsTree,
                 scorer: Optional[Scorer] = None,
                 flow_scorer: Optional[Scorer] = None):
        from linkerd_tpu.models.spec import SPECS
        if cfg.model not in SPECS:
            raise ValueError(f"model must be one of {sorted(SPECS)}")
        if cfg.sidecarTier not in ("primary", "fallback"):
            raise ValueError("sidecarTier must be 'primary' or 'fallback'")
        if cfg.nativeTier not in ("primary", "off"):
            raise ValueError("nativeTier must be 'primary' or 'off'")
        if cfg.nativeQuant not in ("f32", "int8", "int4"):
            raise ValueError(
                "nativeQuant must be 'f32', 'int8', or 'int4'")
        if cfg.distill is not None \
                and (cfg.distill.quant or "f32") not in ("f32", "int8",
                                                         "int4"):
            raise ValueError(
                "distill.quant must be 'f32', 'int8', or 'int4'")
        if cfg.nativeRefreshS < 0:
            raise ValueError("nativeRefreshS must be >= 0")
        if cfg.maxLingerMs < 0:
            raise ValueError("maxLingerMs must be >= 0")
        if cfg.scoreConcurrency < 1:
            raise ValueError("scoreConcurrency must be >= 1")
        from linkerd_tpu.telemetry.linerate import (
            NativeFeatureRing, NativeFeaturizer,
        )
        self.cfg = cfg
        self.metrics = metrics
        self.ring: Deque = collections.deque(maxlen=cfg.ringCapacity)
        # raw native-engine rows, drained C -> ring memory by the
        # FastPathController and consumed zero-copy by the batcher
        self.native_ring = NativeFeatureRing(cfg.ringCapacity)
        self._native_featurizer = NativeFeaturizer()
        self.board = ScoreBoard(ttl_s=cfg.scoreTtlSecs)
        self._scorer = scorer
        # a keyed model is a second tier BESIDE the row scorer, for the
        # rows that carry a stream key: its spec, resolved here once;
        # its scorer is built with the row scorer (``_ensure_scorer``)
        # unless one was handed in
        model = SPECS[cfg.model]()
        self._flow_spec = model if model.keyed else None
        self._flow_scorer = flow_scorer
        self._stop = asyncio.Event()
        self._wake = asyncio.Event()  # batcher wake: rows pending
        self._fit_lock = asyncio.Lock()
        self._node = metrics.scope("anomaly")
        self._scored = self._node.counter("scored_total")
        # events scored by the flow tier; scored_total includes them
        self._flow_scored = self._node.counter("flow_scored_total")
        # events the flow tier scored in shadow (weights from the seed):
        # not published; the row scorer scored those rows too
        self._flow_shadow = self._node.counter("flow_shadow_total")
        # rows scored IN the native engines (in-data-plane tier); the
        # scored_total counter includes them — native_scored_fraction
        # is the native-vs-JAX tier split
        self._native_scored = self._node.counter("native_scored_total")
        self._node.gauge("native_scored_fraction",
                         fn=self._native_fraction)
        # every request that ENTERS the scoring path (recorder append or
        # native-ring row): scored_total / requests_total is the scored
        # fraction — "100% scored" is measured, not asserted
        self._requests = self._node.counter("requests_total")
        self._node.gauge("scored_fraction", fn=self._scored_fraction)
        self._dropped = self._node.gauge("ring_depth", fn=lambda: len(self.ring))
        self._node.gauge("native_ring_depth",
                         fn=lambda: float(len(self.native_ring)))
        self._node.gauge("native_ring_dropped",
                         fn=lambda: float(self.native_ring.dropped))
        self._batches = self._node.counter("batches")
        self._train_loss = self._node.gauge("train_loss")
        # degraded mode: 1 while the scorer path is failing (breaker
        # open / calls erroring); the data plane keeps serving, scoring
        # pauses, anomaly-aware policies fall back to reference behavior
        self._degraded = self._node.gauge("degraded")
        self._degraded.set(0.0)
        self._score_failures = self._node.counter("score_failures")
        self._dropped_batches = self._node.counter("dropped_batches")
        # fleet model coordination: replicas restored per promote
        self._fleet_model_pushes = self._node.counter(
            "fleet_model_pushes")
        self._gauges: Dict[str, object] = {}
        self._batch_i = 0
        # native weight publication: the FastPath controllers register
        # their engines as sinks; the serving model is exported as a
        # CRC'd blob at startup and on every lifecycle promote/rollback
        # hot-swap, and the last blob is replayed to late registrations
        self._weight_sinks: List[Callable[[bytes], None]] = []
        # full sink -> delta-patch sink (engines that can apply
        # per-route L5DWTD01 patches register one alongside)
        self._delta_sinks: Dict[Callable, Callable[[bytes], None]] = {}
        self._last_blob: Optional[bytes] = None
        self._native_blob_meta: Optional[dict] = None
        self._native_publishes = 0
        self._last_native_pub = 0.0   # monotonic; periodic re-export
        self._native_refreshing = False
        # scorer replica pool (sidecarAddress as a list or namer path):
        # held separately from the wrapped self._scorer so run() can
        # start its membership watch and /model.json can report it
        self._scorer_pool = None
        self._sidecar_activity = None
        # span sink (the linker's BroadcastTracer): scorer-path spans —
        # per-request children of the originating trace plus one batch
        # span linking its constituents — flow to every tracer telemeter
        self._span_sink = None
        self._spans_recorded = self._node.counter("spans_recorded")
        # model lifecycle: checkpoint store + promotion gate + drift
        # monitor; None when the config block is absent (zero overhead)
        self._lifecycle = None
        if cfg.lifecycle is not None:
            if cfg.lifecycle.holdoutEveryBatches < 1:
                raise ValueError("lifecycle.holdoutEveryBatches must be >= 1")
            self._lifecycle = cfg.lifecycle.mk_manager(
                self._node.scope("drift"))
            model_node = self._node.scope("model")
            model_node.gauge("version", fn=lambda: float(
                self._lifecycle.serving_version or 0))
            model_node.gauge("step", fn=lambda: float(
                getattr(self._scorer, "_step", 0) or 0))
            model_node.gauge("promotions",
                             fn=lambda: float(self._lifecycle.promotions))
            model_node.gauge("rollbacks",
                             fn=lambda: float(self._lifecycle.rollbacks))
        # continuous in-plane learning: the drift-triggered distillation
        # pipeline producing per-route specialist heads; None when the
        # block is absent (zero overhead). Publishes ride the same
        # weight sinks as the global refresh, preferring delta patches.
        self.distill = None
        if cfg.distill is not None:
            self.distill = cfg.distill.mk(
                self._node.scope("distill"),
                store=(self._lifecycle.store
                       if self._lifecycle is not None else None),
                quant=cfg.nativeQuant)
            self.distill.set_publisher(self.publish_bank_update)
        # reactive control loop (score-weighted balancing / adaptive
        # admission / mesh reactor); None when the block is absent. The
        # Linker registers balancers + admission filters into it during
        # router assembly and its run() task rides alongside ours.
        self.control = None
        if cfg.control is not None:
            self.control = cfg.control.mk(
                self.board, metrics,
                drift=(self._lifecycle.drift
                       if self._lifecycle is not None else None),
                # cold-start guard: no actuation until the scorer has
                # seen (and trained on) warmupBatches batches
                ready_fn=lambda: (self._batches.value
                                  >= self.cfg.control.warmupBatches))

    @property
    def lifecycle(self):
        """The ModelLifecycleManager (None unless configured)."""
        return self._lifecycle

    def _scored_fraction(self) -> float:
        req = self._requests.value
        if req <= 0:
            return 1.0
        return min(1.0, self._scored.value / req)

    def _native_fraction(self) -> float:
        scored = self._scored.value
        if scored <= 0:
            return 0.0
        return min(1.0, self._native_scored.value / scored)

    # -- native tier: weight export + publication -------------------------
    def register_weight_sink(self, sink: Callable[[bytes], None],
                             delta_sink: Optional[Callable[[bytes], None]]
                             = None) -> None:
        """Install a native-engine publish callback (the FastPath
        controller registers ``engine.publish_weights`` here, plus
        ``engine.publish_delta`` when the engine can apply per-route
        patches). The last exported blob is replayed immediately, so
        registration order against the startup publish does not
        matter — a late engine starts from the full bank and is then
        eligible for deltas (its generation matches)."""
        self._weight_sinks.append(sink)
        if delta_sink is not None:
            self._delta_sinks[sink] = delta_sink
        if self._last_blob is not None:
            self._publish_blob_to(sink, self._last_blob)

    def unregister_weight_sink(self, sink: Callable[[bytes], None]) -> None:
        """Remove an engine's publish callback (the controller calls
        this from close(): a later promote must not call into a freed
        native engine)."""
        try:
            self._weight_sinks.remove(sink)
        except ValueError:
            pass
        self._delta_sinks.pop(sink, None)

    def publish_bank_update(self, full: Optional[bytes],
                            delta: Optional[bytes] = None) -> bool:
        """Ship a specialist-bank update to every registered engine:
        the delta patch where a sink can take it (generation-fenced in
        the engine; a rejection falls back to the full bank, which
        re-fences the engine for future deltas), the full blob
        otherwise. Returns True when at least one sink took the delta
        path. Called by the DistillationPipeline under its lock."""
        from linkerd_tpu.lifecycle.export import blob_meta
        used_delta = False
        if full is not None:
            self._last_blob = full
            self._native_blob_meta = blob_meta(full)
            self._native_publishes += 1
            self._last_native_pub = time.monotonic()
        for sink in list(self._weight_sinks):
            dsink = self._delta_sinks.get(sink)
            if delta is not None and dsink is not None:
                try:
                    dsink(delta)
                    used_delta = True
                    continue
                except Exception:  # noqa: BLE001 — a fence-rejected
                    # patch (engine restarted on an older generation)
                    # falls back to the full bank below
                    log.warning("native delta publish rejected; "
                                "falling back to full bank",
                                exc_info=True)
            if full is not None:
                self._publish_blob_to(sink, full)
        return used_delta

    def _publish_blob_to(self, sink, blob: bytes) -> None:
        try:
            sink(blob)
        except Exception:  # noqa: BLE001 — a rejecting engine must not
            # take down the telemeter; the JAX tier keeps scoring
            log.exception("native weight publish failed")

    async def refresh_native_weights(self, scorer: Optional[Scorer] = None,
                                     version: Optional[int] = None) -> bool:
        """Export the serving model as a native weight blob and publish
        it to every registered engine (double-buffered hot-swap in the
        slab — the data plane never pauses). Called at startup and after
        every lifecycle promote/rollback; also admin-invocable via the
        lifecycle cycle. Returns True when a blob went out."""
        if self.cfg.nativeTier != "primary":
            return False
        scorer = scorer or self._ensure_scorer()
        snap_fn = getattr(scorer, "snapshot", None)
        if snap_fn is None or asyncio.iscoroutinefunction(snap_fn):
            # no host-side snapshot surface (stub scorer, sidecar-primary
            # wiring): the native tier stays off, rows fall back to JAX
            return False
        from linkerd_tpu.lifecycle.export import export_weight_blob
        try:
            snap = await asyncio.to_thread(snap_fn)  # l5d: ignore[jax-hotpath] — weight export is a fire-and-forget task on the nativeRefreshS (>=30s) cadence, never a per-batch hop; the device readback must NOT run on the event loop
            if version is None:
                version = (self._lifecycle.serving_version
                           if self._lifecycle is not None else None)
            if version is None:
                version = int(getattr(scorer, "_step", 0) or 0)
            if self.distill is not None:
                # base model changed: export the FULL bank (new base +
                # every promoted head, generation bumped) so a promote
                # never wipes the specialists off the engines. Export
                # AND sink fan-out stay under the pipeline lock: a
                # retrain's delta landing between them would otherwise
                # be clobbered by this (older-generation) full blob.
                async with self.distill.lock:
                    # quant=None: the pipeline's own quant governs (its
                    # distill.quant override, else nativeQuant) — the
                    # recurring full-bank exports must match the delta
                    # publishes byte-encoding for byte-encoding
                    blob = await asyncio.to_thread(  # l5d: ignore[jax-hotpath] — same cadence-bounded export task as below, off-loop
                        self.distill.export_full, snap, int(version),
                        None)
                    self._finish_full_publish(blob, int(version))
                return True
            blob = await asyncio.to_thread(  # l5d: ignore[jax-hotpath] — same cadence-bounded export task: flattening a few-thousand-param snapshot off-loop, not a dispatch-path hop
                export_weight_blob, snap, int(version),
                self.cfg.nativeQuant)
        except Exception:  # noqa: BLE001 — export failures must never
            # stop scoring; the JAX tier serves everything meanwhile
            log.exception("native weight export failed")
            return False
        self._finish_full_publish(blob, int(version))
        return True

    def _finish_full_publish(self, blob: bytes, version: int) -> None:
        """Bookkeeping + sink fan-out for a full blob/bank export (sync
        so the distill path can hold its lock across it)."""
        from linkerd_tpu.lifecycle.export import blob_meta
        self._last_blob = blob
        self._native_blob_meta = blob_meta(blob)
        self._native_publishes += 1
        self._last_native_pub = time.monotonic()
        if (self._lifecycle is not None
                and version == self._lifecycle.serving_version):
            # the blob rides the checkpoint manifest: the serving
            # version's entry records exactly which CRC'd bits went to
            # the engines (lineage from training state to data plane)
            try:
                self._lifecycle.store.record_native_blob(
                    int(version), self._native_blob_meta)
            except Exception:  # noqa: BLE001 — lineage annotation must
                log.exception("native blob manifest record failed")
        for sink in list(self._weight_sinks):
            self._publish_blob_to(sink, blob)

    def _maybe_refresh_native_weights(self, scorer: Scorer) -> None:
        """Periodic re-export of the ONLINE-trained model to the
        engines when no lifecycle manages promotes — without this the
        native tier would serve the startup init blob forever while
        training improves only the JAX model. Fire-and-forget with a
        reentrancy guard; with a lifecycle configured, promote/rollback
        republishes bound the staleness instead (and keep the manifest
        lineage exact)."""
        if (self.cfg.nativeTier != "primary"
                or self._lifecycle is not None
                or not self.cfg.nativeRefreshS
                or not self._weight_sinks
                or self._native_refreshing
                or time.monotonic() - self._last_native_pub
                < self.cfg.nativeRefreshS):
            return
        self._native_refreshing = True

        async def go() -> None:
            try:
                await self.refresh_native_weights(scorer)
            finally:
                # rate-limit retries on export failure too
                self._last_native_pub = time.monotonic()
                self._native_refreshing = False

        from linkerd_tpu.core.tasks import monitor
        monitor(asyncio.create_task(go(), name="native-weight-refresh"),
                what="native-weight-refresh")

    def _maybe_distill(self, scorer: Scorer) -> None:
        """Kick one drift-triggered specialist retrain when a route is
        pending — fire-and-forget with the pipeline's own reentrancy
        guard (one retrain at a time; a second trigger waits for the
        next batch). Fine-tune + shadow-eval run off-loop inside the
        pipeline; the drain path only pays the trigger scan."""
        if self.distill is None or self.distill.busy:
            return
        snap_fn = getattr(scorer, "snapshot", None)
        if snap_fn is None or asyncio.iscoroutinefunction(snap_fn):
            return  # no host snapshot surface: nothing to distill from
        if self.distill.pending_route() is None:
            return
        base_version = (self._lifecycle.serving_version
                        if self._lifecycle is not None else None)

        async def go() -> None:
            try:
                await self.distill.run_once(scorer,
                                            base_version=base_version)
            except Exception:  # noqa: BLE001 — a failed retrain must
                # never stop scoring; the route keeps its serving head
                log.exception("distillation cycle failed")

        from linkerd_tpu.core.tasks import monitor
        monitor(asyncio.create_task(go(), name="distill-retrain"),
                what="distill-retrain")

    def native_tier_state(self) -> dict:
        """The /model.json + /control.json native-tier block: what blob
        the engines serve (version/CRC), how often it swapped, and the
        native-vs-JAX scored split."""
        scored = self._scored.value
        nat = self._native_scored.value
        return {
            "mode": self.cfg.nativeTier,
            "quant": self.cfg.nativeQuant,
            "blob": self._native_blob_meta,
            "publishes": self._native_publishes,
            "engines": len(self._weight_sinks),
            "native_scored_total": nat,
            "jax_scored_total": scored - nat,
            "native_scored_fraction": (round(nat / scored, 6)
                                       if scored else 0.0),
        }

    # -- stack tap --------------------------------------------------------
    def recorder(self) -> FeatureRecorder:
        return FeatureRecorder(self.ring, on_record=self._note_request)

    def _note_request(self) -> None:
        self._requests.incr()
        self._wake.set()

    # -- native fastpath feed ---------------------------------------------
    def set_native_route_resolver(self, fn: Callable[[int], str]) -> None:
        """Install the FastPathController's route_id -> dst-path mapping
        (consulted once per unique route, cached)."""
        self._native_featurizer.resolver = fn

    def native_committed(self, rows: int, dropped: int = 0) -> None:
        """The controller drained ``rows`` engine rows into
        ``native_ring`` and shed ``dropped`` more under backpressure:
        BOTH count toward requests_total (a shed row entered the
        scoring path and was not scored — the scored fraction must
        report < 1.0 under overload, not hide the shed), then wake the
        batcher."""
        if rows > 0 or dropped > 0:
            self._requests.incr(rows + dropped)
        if rows > 0:
            self._wake.set()

    def set_tracer(self, tracer) -> None:
        """Install the linker's span sink (called after telemeter
        assembly — the broadcast tracer is built FROM telemeters, so it
        cannot exist when this one is constructed). The scorer spans
        take their tags from the scorer's ``last_timing``: the ring
        path's own phase record, which every batch writes."""
        self._span_sink = tracer
        if self.control is not None and tracer is not None:
            self.control.set_tracer(tracer)

    # -- Telemeter --------------------------------------------------------
    def _mk_inprocess(self) -> "InProcessScorer":
        scorer = InProcessScorer(
            learning_rate=self.cfg.learningRate,
            recon_weight=self.cfg.reconWeight)
        d = scorer.device_state()
        log.info("in-process scorer on %s (%s, %d visible), score path %s",
                 d["platform"], d["device_kind"], d["count"],
                 d["score_path"])
        return scorer

    def set_sidecar_activity(self, activity) -> None:
        """Install the namer lookup Activity backing a path-form
        ``sidecarAddress`` (the Linker resolves the path against its
        configured namers at assembly); the replica pool tracks it."""
        self._sidecar_activity = activity
        if self._scorer_pool is not None:
            self._scorer_pool.attach_activity(activity)

    def _mk_sidecar_client(self):
        """One pinned GrpcScorerClient, or a ScorerReplicaPool for a
        static list / namer path address (fleet/scorer_pool.py)."""
        addr = self.cfg.sidecarAddress
        from linkerd_tpu.telemetry.sidecar import GrpcScorerClient
        if addr.startswith("/"):
            from linkerd_tpu.fleet.scorer_pool import ScorerReplicaPool
            self._scorer_pool = ScorerReplicaPool()
            if self._sidecar_activity is not None:
                self._scorer_pool.attach_activity(self._sidecar_activity)
            return self._scorer_pool
        if "," in addr:
            from linkerd_tpu.fleet.scorer_pool import ScorerReplicaPool
            self._scorer_pool = ScorerReplicaPool(addr.split(","))
            return self._scorer_pool
        return GrpcScorerClient(addr)

    def _ensure_scorer(self) -> Scorer:
        if self._flow_spec is not None and self._flow_scorer is None:
            import jax
            # single-device, whatever the host has: the first chip, which
            # it shares with the row scorer (or with its mesh)
            self._flow_scorer = InProcessScorer(
                spec=self._flow_spec, devices=jax.devices()[:1])
        if self._scorer is None:
            if self.cfg.sidecarAddress:
                from linkerd_tpu.telemetry.linerate import TieredScorer
                from linkerd_tpu.telemetry.resilience import (
                    CircuitBreaker, ResilientScorer,
                )
                # the breaker + per-call deadline wrap OUTSIDE the
                # client's own (compile-aware) gRPC deadlines: a hung
                # sidecar costs one bounded call, then fails fast
                resilient = ResilientScorer(
                    self._mk_sidecar_client(),
                    call_timeout_s=self.cfg.scoreTimeoutMs / 1e3,
                    breaker=CircuitBreaker(
                        failures=self.cfg.breakerFailures,
                        min_backoff_s=self.cfg.breakerMinBackoffMs / 1e3,
                        max_backoff_s=self.cfg.breakerMaxBackoffMs / 1e3))
                if self.cfg.sidecarTier == "primary":
                    self._scorer = resilient
                else:
                    # line-rate default: in-process primary, sidecar
                    # DEMOTED to the fallback tier behind the breaker.
                    # A linker configured for an in-process primary that
                    # cannot build one (no device, chip held by another
                    # process) fails here, at start — sidecarTier:
                    # primary is how to run without a local device
                    self._scorer = TieredScorer(self._mk_inprocess(),
                                                resilient)
            else:
                self._scorer = self._mk_inprocess()
        return self._scorer

    def _set_degraded(self, degraded: bool) -> None:
        self._degraded.set(1.0 if degraded else 0.0)
        self.board.degraded = degraded

    async def run(self) -> None:
        scorer = self._ensure_scorer()
        if self._scorer_pool is not None:
            # begin tracking announced scorer replicas (namer path mode;
            # a no-op for static replica lists)
            self._scorer_pool.start_watch()
        lc_cfg = self.cfg.lifecycle
        if self._lifecycle is not None and lc_cfg.restoreOnStart:
            # survive restarts: pull the last-good model before scoring
            try:
                restored = await self._lifecycle.bootstrap(scorer)
                if restored is not None:
                    log.info("anomaly model restored from checkpoint v%d",
                             restored)
            except Exception:  # noqa: BLE001 — a bad store must not
                log.exception("checkpoint bootstrap failed; "
                              "serving from fresh init")
        # initial native publish: the engines score in-data-plane from
        # the first request (fresh-init weights if nothing restored;
        # promotions republish as the model improves)
        await self.refresh_native_weights(scorer)
        control_task = None
        if self.control is not None:
            from linkerd_tpu.core.tasks import monitor
            control_task = asyncio.create_task(
                self.control.run(), name="control-loop")
            monitor(control_task, what="control-loop")
        try:
            await self._line_rate_loop(scorer)
        except asyncio.CancelledError:
            pass
        finally:
            if control_task is not None:
                control_task.cancel()
                await asyncio.gather(control_task, return_exceptions=True)
            if self.control is not None and self.control.fleet is not None:
                # the exchange's gossip/store HTTP clients die with the
                # drain loop (nothing else awaits the control loop's
                # teardown; the reactor's client keeps its historical
                # process-lifetime scope)
                await self.control.fleet.aclose()

    async def _maybe_lifecycle(self, last_cycle: float) -> float:
        lc_cfg = self.cfg.lifecycle
        if (self._lifecycle is not None and lc_cfg.checkpointEveryS > 0
                and time.monotonic() - last_cycle
                >= lc_cfg.checkpointEveryS):
            last_cycle = time.monotonic()
            await self.lifecycle_cycle()
        return last_cycle

    async def _line_rate_loop(self, scorer: Scorer) -> None:
        """Adaptive micro-batcher: dispatch when maxBatch rows are
        pending OR the oldest pending row has lingered
        ``maxLingerMs``. Up to ``scoreConcurrency`` batches stay in
        flight so the staging ring double-buffers — host→device of
        batch N overlaps device compute of batch N-1 — while the
        recorder path stays O(1) (it only sets the wake event)."""
        from linkerd_tpu.core.tasks import monitor
        linger = max(self.cfg.maxLingerMs, 0.0) / 1e3
        tick = max(linger / 4, 2e-4)
        sem = asyncio.Semaphore(self.cfg.scoreConcurrency)
        inflight: set = set()
        last_cycle = time.monotonic()
        try:
            while not self._stop.is_set():
                if not self._pending_rows():
                    self._wake.clear()
                    if not self._pending_rows():  # recheck: append raced
                        # asyncio.wait, NOT wait_for: 3.10's wait_for
                        # swallows a cancel() that lands on the same
                        # tick the wake future completes, which would
                        # leave this loop running forever after the
                        # owner cancelled it
                        waiter = asyncio.ensure_future(self._wake.wait())
                        try:
                            await asyncio.wait((waiter,), timeout=0.05)
                        finally:
                            waiter.cancel()
                        if not self._pending_rows():
                            last_cycle = await self._maybe_lifecycle(
                                last_cycle)
                            continue
                # linger: give the batch up to maxLingerMs to fill
                t0 = time.monotonic()
                while (self._pending_rows() < self.cfg.maxBatch
                       and time.monotonic() - t0 < linger
                       and not self._stop.is_set()):
                    await asyncio.sleep(tick)
                batch = self._take_batch()
                if batch is None:
                    continue
                await sem.acquire()
                task = asyncio.create_task(
                    self._score_and_publish(scorer, batch),
                    name="anomaly-score-batch")
                task.add_done_callback(lambda _t: sem.release())
                inflight.add(task)
                task.add_done_callback(inflight.discard)
                monitor(task, what="anomaly-score-batch")
                last_cycle = await self._maybe_lifecycle(last_cycle)
        finally:
            for t in list(inflight):
                t.cancel()
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)

    def _pending_rows(self) -> int:
        return len(self.ring) + len(self.native_ring)

    async def lifecycle_cycle(self) -> Optional[dict]:
        """One checkpoint/shadow-eval/promote-or-rollback pass (the
        namerd-style periodic maintenance task; also admin-invocable)."""
        if self._lifecycle is None:
            return None
        try:
            outcome = await self._lifecycle.run_cycle(self._ensure_scorer())
            log.info("model lifecycle cycle: %s",
                     outcome.get("action", "?"))
            if outcome.get("action") in ("promoted", "rolled_back"):
                # the serving model changed (hot-swap): the native tier
                # must follow, or the engines keep scoring the old one
                await self.refresh_native_weights(
                    version=self._lifecycle.serving_version)
                # fleet model coordination: fan the promoted model out
                # to every announced scorer replica (Snapshot/Restore
                # RPCs) so fleet fallback scorers serve the same
                # generation as the in-plane bank
                self._maybe_push_fleet_model()
            return outcome
        except Exception:  # noqa: BLE001 — lifecycle failures must never
            log.exception("model lifecycle cycle failed")  # stop scoring
            return None

    def _maybe_push_fleet_model(self) -> None:
        """Fire-and-forget fleet model push: the serving checkpoint to
        every scorer replica in the pool via the Snapshot/Restore
        sidecar RPCs. Skipped when no pool (pinned/in-process-only
        wiring) or no promoted checkpoint exists. A slow replica costs
        one bounded background task, never the lifecycle cycle."""
        if self._scorer_pool is None or self._lifecycle is None:
            return
        version = self._lifecycle.serving_version
        if version is None:
            return

        async def go() -> None:
            try:
                _, snap = await asyncio.to_thread(
                    self._lifecycle.store.load, version)
                n = await asyncio.wait_for(
                    self._scorer_pool.broadcast_restore(snap), 30.0)
                if n:
                    self._fleet_model_pushes.incr(n)
                    log.info("fleet model push: v%s restored on %d "
                             "scorer replica(s)", version, n)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — the fleet push is
                # best-effort; replicas converge on a later promote
                log.exception("fleet model push failed")

        from linkerd_tpu.core.tasks import monitor
        monitor(asyncio.create_task(go(), name="fleet-model-push"),
                what="fleet-model-push")

    async def drain_once(self, scorer: Optional[Scorer] = None) -> int:
        """Drain one micro-batch through the scorer; returns rows scored."""
        scorer = scorer or self._ensure_scorer()
        batch = self._take_batch()
        if batch is None:
            return 0
        return await self._score_and_publish(scorer, batch)

    def _take_batch(self) -> Optional[dict]:
        """Assemble one micro-batch: Python-path ring items plus a
        zero-copy block of native engine rows. Featurization happens
        HERE, synchronously — the native block is a view into ring
        memory that is only valid until the caller's next await.

        Engine rows that arrived PRE-SCORED (the in-data-plane native
        tier; scored flag set) are split out of the JAX dispatch: their
        features still feed training/drift/holdout, but the device
        never re-scores them. ``x`` holds only the rows that NEED a
        JAX score (Python-path + unscored native rows)."""
        from linkerd_tpu.telemetry.linerate import (
            NATIVE_COL_SCORE, NATIVE_COL_SCORED,
        )
        n_py = min(len(self.ring), self.cfg.maxBatch)
        # ring items are (fv, label[, trace, enqueued_at, endpoint]) —
        # external producers (benchmarks, fault harnesses) still append
        # 2-tuples
        items = [(it + (None, None, None, None))[:5]
                 for it in (self.ring.popleft() for _ in range(n_py))]
        nat_block = self.native_ring.consume(self.cfg.maxBatch - n_py)
        k = len(nat_block)
        if not items and k == 0:
            return None
        fvs = [it[0] for it in items]
        x_py = featurize_batch(fvs)
        nat_inv: Optional[np.ndarray] = None
        nat_dsts: List[str] = []
        nat_scored: Optional[dict] = None
        x_nat: Optional[np.ndarray] = None
        flow: Optional[dict] = None
        if k:
            # encode the WHOLE block in one pass — the featurizer's
            # per-route drift EWMA must advance exactly once per drain,
            # in arrival order (two subset passes would double-step the
            # baseline and compute the later subset's drift against an
            # already-advanced EWMA) — then split the ENCODED rows by
            # tier. Boolean fancy indexing copies, safe across awaits.
            x_enc, inv_all, dsts = \
                self._native_featurizer.encode_block(nat_block)
            is_scored = nat_block[:, NATIVE_COL_SCORED] > 0.5
            if self._flow_scorer is not None:
                flow, in_flow = self._flow_rows(nat_block, x_enc, inv_all,
                                                dsts)
                if flow is not None and flow["live"]:
                    # a row with a stream key is the flow tier's alone
                    x_enc, inv_all = x_enc[~in_flow], inv_all[~in_flow]
                    nat_block = nat_block[~in_flow]
                    is_scored = is_scored[~in_flow]
            if is_scored.any():
                all_sc = bool(is_scored.all())
                nat_scored = {
                    "x": x_enc if all_sc else x_enc[is_scored],
                    "scores": np.ascontiguousarray(
                        nat_block[is_scored, NATIVE_COL_SCORE],
                        np.float32),
                    "inv": inv_all if all_sc else inv_all[is_scored],
                    "dsts": dsts,
                }
            un = ~is_scored
            if un.any():
                all_un = bool(un.all())
                x_nat = x_enc if all_un else x_enc[un]
                nat_inv = inv_all if all_un else inv_all[un]
                nat_dsts = dsts
        k_un = 0 if x_nat is None else len(x_nat)
        labels = np.array(
            [0.0 if it[1] is None else float(it[1]) for it in items]
            + [0.0] * k_un, dtype=np.float32)
        mask = np.array(
            [0.0 if it[1] is None else 1.0 for it in items]
            + [0.0] * k_un, dtype=np.float32)
        if x_nat is not None:
            x = np.concatenate([x_py, x_nat]) if n_py else x_nat
        else:
            x = x_py
        return {"items": items, "fvs": fvs, "x": x, "labels": labels,
                "mask": mask, "n_py": n_py, "nat_inv": nat_inv,
                "nat_dsts": nat_dsts, "nat_scored": nat_scored,
                "flow": flow}

    def _flow_rows(self, nat_block: np.ndarray, x_enc: np.ndarray,
                   inv: np.ndarray, dsts: List[str]):
        """The engine rows that carry a stream key, as the flow scorer
        takes them: int32 ``(stream key, restart flag, event id)``, the
        flag set on a stream's first sample (frame count 1 or less), the
        id by ``models.features.event_ids``. ``live``: whether the flow
        scorer's weights were loaded; on weights drawn from the seed the
        tier runs in shadow (``_score_flows``) and the rows stay the row
        scorer's too. Returns ``(None, mask)`` where no row carries a
        key."""
        from linkerd_tpu.models.features import event_ids
        from linkerd_tpu.telemetry.linerate import (
            NATIVE_COL_SEQ, NATIVE_COL_STREAM,
        )
        key = nat_block[:, NATIVE_COL_STREAM].astype(np.int64)
        in_flow = key > 0
        if not in_flow.any():
            return None, in_flow
        vocab = self._flow_scorer.cfg.vocab_slice
        rows = np.stack([key[in_flow],
                         nat_block[in_flow, NATIVE_COL_SEQ] <= 1,
                         event_ids(x_enc[in_flow], vocab)], 1
                        ).astype(np.int32)
        live = getattr(self._flow_scorer, "weights", "loaded") == "loaded"
        return {"rows": rows, "inv": inv[in_flow], "dsts": dsts,
                "live": live}, in_flow

    async def _score_flows(self, flow: dict) -> int:
        """The flow tier's half of a batch: score the stream rows as their
        flows' next events and, where the tier is live, publish per-route
        means as the MLP's scores publish. In shadow (weights from the
        seed: nothing a route should be ejected on) the scores are counted
        under ``flow_shadow_total`` and go no further. A failing flow
        scorer drops this half only."""
        try:
            scores = await self._flow_scorer.score(flow["rows"])
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort, as the MLP tier
            self._score_failures.incr()
            log.warning("flow scorer failed (flow rows dropped): %r", e)
            return 0
        if not flow["live"]:
            self._flow_shadow.incr(len(scores))
            return 0
        self._publish_route_means(flow["dsts"], flow["inv"],
                                  np.asarray(scores))  # l5d: ignore[jax-hotpath] — a host array already: the drainer read it back
        self._flow_scored.incr(len(scores))
        self._scored.incr(len(scores))
        return len(scores)

    async def _score_and_publish(self, scorer: Scorer, b: dict) -> int:
        """Score one assembled batch and publish every downstream
        effect: degraded-mode accounting, scorer spans, lifecycle
        drift/holdout, per-dst board updates, training cadence.

        Rows the engines already scored in-data-plane (``nat_scored``)
        skip the JAX dispatch entirely: their scores publish straight
        to the board, their features still feed drift/holdout/training
        — the RingDispatcher stays the training and fallback tier."""
        x, items, n_py = b["x"], b["items"], b["n_py"]
        ns = b.get("nat_scored")
        k_ns = 0 if ns is None else len(ns["x"])
        n_jax = len(x)
        n_flow = (await self._score_flows(b["flow"])
                  if b.get("flow") is not None else 0)
        if not n_jax and not k_ns:
            return n_flow
        t_drain = time.monotonic()
        ts_us = int(time.time() * 1e6)
        scores: Optional[np.ndarray] = None
        jax_failed = False
        if n_jax:
            try:
                scores = await scorer.score(x)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — graceful degradation:
                # scoring is best-effort; a dead/hung scorer drops the
                # JAX half of the batch (requests were never blocked on
                # it) and flips degraded mode — engine-scored rows still
                # publish below: the native tier does not depend on the
                # device being healthy
                self._score_failures.incr()
                self._dropped_batches.incr()
                jax_failed = True
                if not self.board.degraded:
                    log.warning(
                        "anomaly scorer degraded (scoring paused, data "
                        "plane unaffected): %r", e)
                self._set_degraded(True)
                if k_ns == 0:
                    return n_flow
            else:
                scores = np.asarray(scores)  # l5d: ignore[jax-hotpath] — scorers return host arrays (the drainer already did readback); this is a no-op view
                if self.board.degraded:
                    log.info("anomaly scorer recovered; scoring resumed")
                self._set_degraded(False)
        n_scored = (n_jax if scores is not None else 0) + k_ns
        self._scored.incr(n_scored)
        if k_ns:
            self._native_scored.incr(k_ns)
        if not jax_failed:
            # a failed JAX dispatch was already counted dropped; the
            # native half still publishes below but the batch must not
            # ALSO count completed, nor export scorer spans for the
            # Python items whose scoring was just dropped
            self._batches.incr()
            if self._span_sink is not None:
                self._record_scorer_spans(
                    items, t_drain, ts_us,
                    int((time.monotonic() - t_drain) * 1e6), scorer)
        # every row with a score — JAX-scored and engine-scored alike —
        # feeds drift/holdout; labels/mask for the native rows are all
        # zeros (engine rows are never fault-labeled)
        x_all, labels_all, mask_all, scores_all = x, b["labels"], \
            b["mask"], scores
        if k_ns:
            if scores is not None and n_jax:
                x_all = np.concatenate([x, ns["x"]])
                scores_all = np.concatenate(
                    [scores, ns["scores"]])
                labels_all = np.concatenate(
                    [b["labels"], np.zeros(k_ns, np.float32)])
                mask_all = np.concatenate(
                    [b["mask"], np.zeros(k_ns, np.float32)])
            else:
                x_all, scores_all = ns["x"], ns["scores"]
                labels_all = np.zeros(k_ns, np.float32)
                mask_all = np.zeros(k_ns, np.float32)
        holdout = False
        if self._lifecycle is not None and scores_all is not None:
            # drift sees every batch (read-only); the replay window only
            # takes HOLDOUT batches, which are then excluded from
            # training below — a shadow-eval set the candidate trained on
            # (same rows AND same labels) could not catch a poisoned
            # training stream, because the poisoned candidate evaluates
            # best on its own poison
            self._lifecycle.drift.observe(x_all, scores_all)
            holdout = self._batch_i % self.cfg.lifecycle.holdoutEveryBatches == 0
            if holdout:
                self._lifecycle.replay.add_batch(x_all, labels_all,
                                                 mask_all)
        if scores is not None:
            self.board.update_batch([fv.dst_path for fv in b["fvs"]],
                                    scores[:n_py],
                                    endpoints=[it[4] for it in items])
            if b["nat_inv"] is not None and b["nat_dsts"]:
                # native rows: per-ROUTE means, vectorized (update_batch
                # averages per dst anyway, so feeding group means is
                # equivalent to feeding every row)
                self._publish_route_means(
                    b["nat_dsts"], b["nat_inv"], scores[n_py:])
        self._publish_native_batch(ns)
        if self.distill is not None and scores_all is not None \
                and len(scores_all):
            # per-route drift + replay feed: host-only bookkeeping,
            # mirroring exactly how x_all was assembled (python rows,
            # then JAX-scored native rows, then engine-scored rows)
            dsts_all: List[str] = []
            if scores is not None:
                dsts_all.extend(fv.dst_path for fv in b["fvs"])
                if b["nat_inv"] is not None and b["nat_dsts"]:
                    nd = b["nat_dsts"]
                    dsts_all.extend(nd[int(i)] for i in b["nat_inv"])
            if k_ns:
                nsd = ns["dsts"]
                dsts_all.extend(nsd[int(i)] for i in ns["inv"])
            if len(dsts_all) == len(scores_all):
                self.distill.observe_batch(dsts_all, x_all, scores_all,
                                           labels_all, mask_all)
        self._publish_gauges()
        self._batch_i += 1
        if (not holdout and self.cfg.trainEveryBatches
                and not jax_failed
                and self._batch_i % self.cfg.trainEveryBatches == 0):
            try:
                # serialized: concurrent line-rate batches must not
                # interleave their fit steps. Engine-scored rows train
                # too — the JAX model is the training tier for ALL
                # traffic, or it would drift away from the distribution
                # the native tier actually serves
                async with self._fit_lock:
                    loss = await scorer.fit(x_all, labels_all, mask_all)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — training is optional;
                # a fit failure (it still feeds the shared breaker) must
                # not take down scoring
                self._score_failures.incr()
                log.debug("online fit skipped (scorer failure): %r", e)
            else:
                self._train_loss.set(loss)
                self._maybe_refresh_native_weights(scorer)
        self._maybe_distill(scorer)
        return n_scored + n_flow

    def _publish_native_batch(self, ns: Optional[dict]) -> None:
        """Publish engine-scored rows to the board: per-route score
        means, no device work — the scores were computed in-data-plane
        and this hop is pure host arithmetic (a jax-hotpath root: a
        device seam creeping in here would put the old per-batch
        latency right back on the native tier's publish path)."""
        if ns is None or not ns["dsts"]:
            return
        self._publish_route_means(ns["dsts"], ns["inv"], ns["scores"])

    def _publish_route_means(self, dsts: List[str], inv: np.ndarray,
                             scores: np.ndarray) -> None:
        """Per-route score means onto the board. ``dsts`` is the FULL
        block's route list while ``inv`` may index only one tier's
        subset of its rows — routes with no rows here are skipped, not
        published as a spurious 0.0."""
        m = len(dsts)
        sums = np.bincount(inv, weights=scores, minlength=m)
        counts = np.bincount(inv, minlength=m)
        nz = counts > 0
        if not nz.any():
            return
        if nz.all():
            self.board.update_batch(dsts, sums / counts)
        else:
            self.board.update_batch(
                [d for d, keep in zip(dsts, nz) if keep],
                sums[nz] / counts[nz])

    # at most this many per-request scorer spans per drained batch: a
    # 1024-row batch must not turn into 1024 span records per 50ms
    MAX_SPANS_PER_BATCH = 128

    def _record_scorer_spans(self, items, t_drain: float, ts_us: int,
                             dur_us: int, scorer) -> None:
        """Scorer-path spans for one drained micro-batch: a batch span
        (own trace) that links its constituent request traces via
        annotations, plus one child span per SAMPLED originating request
        carrying the queue/device/transfer decomposition."""
        from linkerd_tpu.router.tracing import TraceId

        timing = getattr(scorer, "last_timing", None) or {}
        timing_tags = {f"scorer.{k}": (f"{v:.3f}" if isinstance(v, float)
                                       else str(v))
                       for k, v in timing.items()}
        traced = [(it[2], it[3]) for it in items
                  if it[2] is not None and it[2].sampled]
        batch = TraceId.mk_root(True)
        batch_tags = dict(timing_tags)
        batch_tags["scorer.batch_size"] = str(len(items))
        batch_tags["scorer.linked"] = str(len(traced))
        self._span_sink.record({
            "traceId": f"{batch.trace_id:032x}",
            "id": f"{batch.span_id:016x}",
            "parentId": None,
            "kind": "CONSUMER",
            "name": "scorer.batch",
            "timestamp": ts_us,
            "duration": dur_us,
            "localEndpoint": {"serviceName": "scorer"},
            # constituent request spans, linked (zipkin has no otel-style
            # span links; annotations are the v2-JSON-native equivalent)
            "annotations": [
                {"timestamp": ts_us,
                 "value": f"link:{t.trace_id:032x}:{t.span_id:016x}"}
                for t, _ in traced[:self.MAX_SPANS_PER_BATCH]],
            "tags": batch_tags,
        })
        self._spans_recorded.incr()
        for trace, enq in traced[:self.MAX_SPANS_PER_BATCH]:
            child = trace.child()
            tags = dict(timing_tags)
            tags["scorer.batch_span"] = f"{batch.span_id:016x}"
            if enq is not None:
                # ring wait: enqueue (request completion) -> drain start
                tags["scorer.queue_ms"] = f"{(t_drain - enq) * 1e3:.3f}"
            self._span_sink.record({
                "traceId": f"{child.trace_id:032x}",
                "id": f"{child.span_id:016x}",
                "parentId": f"{child.parent_id:016x}",
                "kind": "CONSUMER",
                "name": "scorer",
                "timestamp": ts_us,
                "duration": dur_us,
                "localEndpoint": {"serviceName": "scorer"},
                "tags": tags,
            })
            self._spans_recorded.incr()

    def _publish_gauges(self) -> None:
        for dst, score in self.board.scores.sample().items():
            key = dst.lstrip("/").replace("/", ".") or "root"
            g = self._gauges.get(key)
            if g is None:
                g = self._node.scope("dst").gauge(key)
                self._gauges[key] = g
            g.set(score)

    def admin_handlers(self):
        from linkerd_tpu.admin.server import json_response

        async def anomaly_json(req: Request) -> Response:
            return json_response({
                "scores": self.board.scores.sample(),
                "threshold": self.cfg.scoreThreshold,
                "ring_depth": len(self.ring),
            })

        async def model_json(req: Request) -> Response:
            return json_response(self.model_state())

        handlers = [("/anomaly.json", anomaly_json),
                    ("/model.json", model_json)]
        if self.control is not None:
            async def control_json(req: Request) -> Response:
                st = self.control.status()
                # the control loop actuates on scores; surface WHICH
                # tier produced them (and which model version/CRC the
                # engines are serving) next to the actuation state
                st["native_tier"] = self.native_tier_state()
                return json_response(st)

            handlers.append(("/control.json", control_json))
            if self.control.fleet is not None:
                # /fleet.json + the gossip push/pull endpoint ride the
                # admin server alongside the rest of the control surface
                from linkerd_tpu.fleet.gossip import fleet_admin_handlers
                handlers.extend(fleet_admin_handlers(self.control.fleet))
        return handlers

    def model_state(self) -> dict:
        """Model-lifecycle state for /model.json: version, step, last
        promotion/rollback, drift gauges, store inventory."""
        out: dict = {
            "lifecycle_enabled": self._lifecycle is not None,
            "live_step": getattr(self._scorer, "_step", None),
            "scorer": type(self._scorer).__name__
            if self._scorer is not None else None,
            "degraded": bool(self.board.degraded),
            # "100% scored" is measured, not asserted
            "requests_total": self._requests.value,
            "scored_total": self._scored.value,
            "scored_fraction": round(self._scored_fraction(), 6),
            # in-data-plane tier: blob version/CRC, publish (swap)
            # count, native-vs-JAX scored split
            "native_tier": self.native_tier_state(),
        }
        breaker = getattr(self._scorer, "breaker", None)
        if breaker is not None:
            out["breaker"] = {
                "state": breaker.state,
                "next_probe_in_s": round(breaker.next_probe_in_s(), 3),
            }
        tier_fn = getattr(self._scorer, "tier_state", None)
        if tier_fn is not None:
            out["tiers"] = tier_fn()
        device_fn = getattr(self._scorer, "device_state", None)
        if device_fn is not None:
            # platform / device_kind / count as JAX reports them, the
            # score path built (fused kernel, XLA, or mesh shape) and
            # the batch buckets dispatched so far
            out["device"] = device_fn()
        if self._scorer_pool is not None:
            out["scorer_pool"] = self._scorer_pool.status()
        if self.distill is not None:
            # the per-route bank view: generation, every specialist
            # head's lineage, live drift shifts, pending retrains
            out["distill"] = self.distill.state()
        if self._lifecycle is not None:
            out.update(self._lifecycle.status())
        return out

    def close(self) -> None:
        self._stop.set()
        if self.control is not None:
            self.control.close()
        if self._lifecycle is not None and self._scorer is not None:
            # best-effort shutdown snapshot (sync/in-process scorers
            # only): a router restart must not silently reset the model
            # to random init. Saved as a candidate — restart prefers the
            # last PROMOTED version when one exists (latest_good()).
            snap_fn = getattr(self._scorer, "snapshot", None)
            if snap_fn is not None \
                    and not asyncio.iscoroutinefunction(snap_fn):
                try:
                    self._lifecycle.store.save(
                        snap_fn(), status="candidate",
                        parent=self._lifecycle.serving_version)
                except Exception:  # noqa: BLE001 — shutdown must proceed
                    log.exception("shutdown checkpoint failed")
        if self._scorer is not None:
            self._scorer.close()
        if self._flow_scorer is not None:
            self._flow_scorer.close()


# -- score-driven failure accrual -------------------------------------------


@register("failureAccrual", "io.l5d.jaxAnomaly")
@dataclass
class AnomalyFailureAccrualConfig:
    """Failure accrual that tightens when the anomaly scorer flags the mesh:
    endpoints are marked dead after ``anomalousFailures`` consecutive
    failures while the (EWMA) anomaly level exceeds ``threshold``, else
    after ``failures`` — learned signal replacing the hand-tuned constant
    (the BASELINE.json north-star feedback loop)."""

    failures: int = 5
    anomalousFailures: int = 2
    threshold: float = 0.5

    needs_board = True

    def mk(self, board: Optional[ScoreBoard] = None):
        from linkerd_tpu.router.failure_accrual import FailureAccrualPolicy
        return AnomalyFailureAccrualPolicy(
            board or ScoreBoard(), self.failures, self.anomalousFailures,
            self.threshold)


class AnomalyFailureAccrualPolicy:
    """See AnomalyFailureAccrualConfig. Implements FailureAccrualPolicy."""

    def __init__(self, board: ScoreBoard, failures: int,
                 anomalous_failures: int, threshold: float,
                 backoffs=None):
        from linkerd_tpu.router.failure_accrual import _default_backoffs
        self.board = board
        self.failures = failures
        self.anomalous_failures = anomalous_failures
        self.threshold = threshold
        self._consecutive = 0
        self._mk_backoffs = (lambda: backoffs) if backoffs else _default_backoffs
        self._backoffs = self._mk_backoffs()

    def _anomaly_level(self) -> float:
        # staleness-decayed and degraded-aware: while the scorer path is
        # down or its scores are stale, this reads 0 and the policy
        # degrades to its reference `failures` threshold
        return self.board.anomaly_level()

    def record_success(self) -> None:
        self._consecutive = 0

    def record_failure(self):
        self._consecutive += 1
        limit = (self.anomalous_failures
                 if self._anomaly_level() >= self.threshold
                 else self.failures)
        if self._consecutive >= limit:
            return next(self._backoffs)
        return None

    def revived(self) -> None:
        self._consecutive = 0
        self._backoffs = self._mk_backoffs()
