"""Specialist model bank + continuous in-plane learning tests
(linkerd_tpu/distill/, native/scorer.h bank/delta/int4, COMPONENTS.md
§2.18).

The contracts under test:

- blob-format compatibility: ``L5DWTS01`` blobs load unchanged through
  the new bank reader; ``L5DWTS02`` banks roundtrip with per-route head
  select; corruption/truncation/unsorted heads/bad fences are rejected
  publishes, never silently-wrong scores;
- int4: the third quant level's parity bound vs the f32 evaluator AND
  the jitted serving scorer is pinned (alongside the existing f32 1e-5
  and int8 3e-2 bounds), and its blobs are the smallest;
- delta patches: generation-fenced apply under the same double-buffered
  reader-recheck discipline — torn-weights stress extended to deltas on
  the multi-worker shared slab;
- the continuous-learning loop: injected per-route distribution shift
  -> RouteDriftMonitor trigger -> retrain from the route's replay rows
  -> PromotionGate shadow pass -> delta publish -> 2-worker engines
  score that route with the specialist head (stats + /model.json),
  while a poisoned candidate is rejected and a single-route rollback
  leaves the other heads serving.
"""

import asyncio
import struct
import threading
import time
import zlib
from types import SimpleNamespace

import numpy as np
import pytest

from linkerd_tpu.distill import DistillConfig
from linkerd_tpu.distill.monitor import RouteDriftMonitor, RouteReplayWindow
from linkerd_tpu.lifecycle.export import (
    BANK_MAGIC, blob_meta, export_bank_blob, export_delta_blob,
    export_weight_blob, route_hash, _model_section, _sealed,
)
from linkerd_tpu.telemetry.anomaly import (
    FeatureVector, InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.linerate import NATIVE_COL_SCORED, NATIVE_ROW_WIDTH
from linkerd_tpu.telemetry.metrics import MetricsTree
from linkerd_tpu.testing.faults import EchoBackend

native = pytest.importorskip("linkerd_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.ensure_built(), reason="native toolchain unavailable")


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 180))


# -- numpy-only fake snapshots (export/parse paths need no JAX) --------------


def _fake_snap(seed: int = 0, scale: float = 0.2):
    """A snapshot-shaped object with tiny seeded dense layers in the
    geometry the parser requires (36 -> 8 -> 36 recon, 8 -> 1 cls)."""
    rng = np.random.default_rng(seed)
    dim, z = 36, 8

    def layer(rows, cols):
        return {"w": rng.standard_normal((rows, cols)).astype(np.float32)
                * scale,
                "b": rng.standard_normal(cols).astype(np.float32) * 0.1}

    return SimpleNamespace(
        params={"enc": [layer(dim, z)], "dec": [layer(z, dim)],
                "cls": [layer(z, 1)]},
        mu=np.zeros(dim, np.float32),
        var=np.ones(dim, np.float32),
        norm_initialized=True, step=seed,
        cfg=SimpleNamespace(recon_weight=0.5))


@pytest.fixture(scope="module")
def trained_snapshot():
    """One real trained snapshot shared by the parity tests."""
    async def go():
        scorer = InProcessScorer(seed=3, learning_rate=5e-3)
        rng = np.random.default_rng(3)
        try:
            for _ in range(6):
                x = rng.standard_normal(
                    (32, scorer.cfg.in_dim)).astype(np.float32) * 2.0 + 1.0
                labels = (rng.random(32) > 0.8).astype(np.float32)
                await scorer.fit(x, labels, np.ones(32, np.float32))
            ref_x = rng.standard_normal(
                (256, scorer.cfg.in_dim)).astype(np.float32)
            jitted = np.asarray(await scorer.score(ref_x))
            return scorer.snapshot(), ref_x, jitted
        finally:
            scorer.close()

    return run(go())


class TestRouteHashParity:
    def test_python_hash_matches_engines(self):
        """route_hash must be the engines' FNV-1a bit for bit — the
        head a delta upserts is the head the data plane selects."""
        for s in ("/svc/web", "/fp/a", "x", "/#/io.l5d.fs/big-svc"):
            assert route_hash(s) == native.tenant_hash_native(s.encode())
        # 0 is reserved for "no head pushed"
        assert route_hash("/svc/web") != 0

    def test_python_hash_matches_tenancy(self):
        from linkerd_tpu.router.tenancy import tenant_hash
        assert route_hash("/svc/web") == tenant_hash("/svc/web")


class TestBankBlobFormat:
    def test_bank_roundtrips_with_head_select(self):
        base = _fake_snap(1)
        h_a, h_b = _fake_snap(2, scale=0.5), _fake_snap(3, scale=0.05)
        ra, rb = route_hash("/svc/a"), route_hash("/svc/b")
        bank = export_bank_blob(base, 7, 3,
                                {ra: (11, h_a), rb: (12, h_b)})
        meta = blob_meta(bank)
        assert meta["format"] == "bank"
        assert meta["generation"] == 3 and meta["heads"] == 2
        info = native.score_blob_info(bank)
        assert info["format"] == 2 and info["heads"] == 2
        assert info["generation"] == 3 and info["version"] == 7
        x = np.random.default_rng(0).standard_normal(
            (16, 36)).astype(np.float32)
        s_base, spec = native.score_eval_route(bank, 12345, x)
        assert not spec  # unknown hash: base model serves
        s_a, spec_a = native.score_eval_route(bank, ra, x)
        s_b, spec_b = native.score_eval_route(bank, rb, x)
        assert spec_a and spec_b
        assert np.abs(s_a - s_base).max() > 1e-6
        assert np.abs(s_a - s_b).max() > 1e-6
        # base eval equals the plain v1 export of the same base
        v1 = export_weight_blob(base, 7)
        assert np.allclose(native.score_eval(v1, x), s_base, atol=1e-6)

    def test_v1_blobs_load_in_the_new_reader(self):
        """Backward compatibility: every pre-bank blob keeps working —
        engine publish, slab publish, bank-reader eval (headless bank,
        generation = model version)."""
        v1 = export_weight_blob(_fake_snap(4), 9)
        assert blob_meta(v1)["format"] == "model"
        info = native.score_blob_info(v1)
        assert info["format"] == 1
        assert info["generation"] == 9 and info["heads"] == 0
        slab = native.ScoreSlab()
        try:
            slab.publish(v1)
            st = slab.stats()
            assert st["version"] == 9 and st["generation"] == 9
            assert st["heads"] == 0
            x = np.zeros((2, 36), np.float32)
            scores, spec = slab.score_route(x, route_hash("/svc/a"))
            assert (spec == 0).all()
        finally:
            slab.close()
        eng = native.FastPathEngine()
        try:
            eng.publish_weights(v1)  # no exception: accepted
        finally:
            eng.close()

    def test_unsorted_heads_rejected(self):
        base, head = _fake_snap(1), _fake_snap(2)
        chunks = [BANK_MAGIC, struct.pack("<II", 1, 2)]
        chunks += _model_section(base, 1, "f32")
        for rh in (2000, 1000):  # descending: must be rejected
            chunks.append(struct.pack("<I", rh))
            chunks += _model_section(head, 1, "f32")
        bad = _sealed(chunks)
        with pytest.raises(ValueError, match="ascending"):
            native.score_blob_info(bad)

    def test_corrupted_bank_rejected(self):
        bank = bytearray(export_bank_blob(
            _fake_snap(1), 1, 1, {1000: (1, _fake_snap(2))}))
        bank[len(bank) // 2] ^= 0x20
        with pytest.raises(ValueError, match="crc"):
            native.score_blob_info(bytes(bank))
        with pytest.raises(ValueError):
            native.score_blob_info(bytes(bank[:100]))

    def test_export_caps_head_count(self):
        from linkerd_tpu.lifecycle.export import MAX_HEADS
        heads = {1000 + i: (i, _fake_snap(0)) for i in range(MAX_HEADS + 1)}
        with pytest.raises(ValueError, match="heads"):
            export_bank_blob(_fake_snap(1), 1, 1, heads)


class TestInt4:
    def test_int4_blob_is_smallest(self):
        snap = _fake_snap(5)
        f32 = export_weight_blob(snap, 1, "f32")
        i8 = export_weight_blob(snap, 1, "int8")
        i4 = export_weight_blob(snap, 1, "int4")
        assert len(i4) < len(i8) < len(f32)
        # the weight payload halves again vs int8 (nibble packing)
        assert native.score_blob_info(i4)["quant"] == 2

    def test_int4_parity_bounds_pinned(self, trained_snapshot):
        """The acceptance bound: int4 native eval vs the f32 evaluator
        AND vs the jitted serving scorer, pinned alongside the existing
        f32 1e-5 / int8 3e-2 bounds (measured ~0.06 max; 2x headroom).
        """
        snap, x, jitted = trained_snapshot
        f32 = export_weight_blob(snap, 1, "f32")
        i4 = export_weight_blob(snap, 1, "int4")
        a = native.score_eval(f32, x)
        b = native.score_eval(i4, x)
        assert np.abs(a - b).max() < 0.12
        assert np.abs(a - b).mean() < 0.04
        assert np.abs(jitted - b).max() < 0.12
        assert np.abs(jitted - b).mean() < 0.04
        assert np.isfinite(b).all()
        assert (b >= 0.0).all() and (b <= 1.0).all()

    def test_existing_bounds_still_hold(self, trained_snapshot):
        snap, x, jitted = trained_snapshot
        f32 = export_weight_blob(snap, 1, "f32")
        i8 = export_weight_blob(snap, 1, "int8")
        a = native.score_eval(f32, x)
        assert np.abs(a - jitted).max() < 0.05          # f32 vs bf16 jit
        assert np.abs(a - native.score_eval(i8, x)).max() < 0.03

    def test_int4_engine_publish(self):
        eng = native.FastPathEngine()
        try:
            eng.publish_weights(export_weight_blob(_fake_snap(2), 3,
                                                   "int4"))
            st = eng.stats()["native_scorer"]
            assert st["weights"] and st["version"] == 3
        finally:
            eng.close()


class TestDeltaFormat:
    def test_delta_roundtrip_meta(self):
        d = export_delta_blob(4, 5, {1000: (2, _fake_snap(1))},
                              removes=[2000])
        meta = blob_meta(d)
        assert meta["format"] == "delta"
        assert meta["base_generation"] == 4
        assert meta["new_generation"] == 5 and meta["ops"] == 2
        info = native.score_blob_info(d)
        assert info["format"] == 3 and info["ops"] == 2

    def test_corrupted_and_truncated_deltas_rejected(self):
        d = bytearray(export_delta_blob(1, 2, {1000: (1, _fake_snap(1))}))
        flipped = bytearray(d)
        flipped[len(flipped) // 2] ^= 0x08
        slab = native.ScoreSlab()
        try:
            slab.publish(export_bank_blob(_fake_snap(0), 1, 1, {}))
            with pytest.raises(ValueError, match="crc"):
                slab.publish_delta(bytes(flipped))
            with pytest.raises(ValueError):
                slab.publish_delta(bytes(d[: len(d) // 2]))
            # unknown op id survives CRC but fails the parse
            bad_op = bytearray(d[:-4])
            struct.pack_into("<I", bad_op, 8 + 12, 7)
            bad_op = bytes(bad_op) + struct.pack(
                "<I", zlib.crc32(bytes(bad_op)))
            with pytest.raises(ValueError, match="op"):
                slab.publish_delta(bad_op)
            # every rejection left the serving bank untouched
            assert slab.stats()["generation"] == 1
            assert slab.stats()["delta_swaps"] == 0
        finally:
            slab.close()

    def test_generation_fence_and_absent_remove(self):
        slab = native.ScoreSlab()
        try:
            with pytest.raises(ValueError, match="no bank"):
                slab.publish_delta(export_delta_blob(
                    0, 1, {1000: (1, _fake_snap(1))}))
            slab.publish(export_bank_blob(_fake_snap(0), 1, 5, {}))
            with pytest.raises(ValueError, match="generation"):
                slab.publish_delta(export_delta_blob(
                    4, 6, {1000: (1, _fake_snap(1))}))
            with pytest.raises(ValueError, match="absent"):
                slab.publish_delta(export_delta_blob(5, 6,
                                                     removes=[1234]))
            ok = export_delta_blob(5, 6, {1000: (1, _fake_snap(1))})
            slab.publish_delta(ok)
            assert slab.stats()["generation"] == 6
            assert slab.stats()["heads"] == 1
            # replaying the SAME delta is fenced out (gen moved on)
            with pytest.raises(ValueError, match="generation"):
                slab.publish_delta(ok)
        finally:
            slab.close()

    def test_export_refuses_degenerate_deltas(self):
        with pytest.raises(ValueError, match="exceed"):
            export_delta_blob(3, 3, {1000: (1, _fake_snap(1))})
        with pytest.raises(ValueError, match="at least one"):
            export_delta_blob(1, 2)


class TestTornWeightsDeltaStress:
    def test_concurrent_delta_and_full_publish_never_torn(self):
        """The §2.14 torn-weights stress extended to delta patches on
        the multi-worker shared slab: while one publisher alternates a
        full bank publish and a generation-fenced delta upsert as fast
        as it can, every concurrently observed score for the patched
        route matches the bank's head or the delta's head EXACTLY — a
        half-applied patch would produce a third value."""
        rh = 1000  # the C test bank keys heads from 1000
        bank = native.score_test_bank(generation=1, seed=5, n_heads=1)
        delta = native.score_test_delta(1, 2, rh, seed=77)
        x = np.random.default_rng(4).standard_normal(
            (1, native.score_feature_dim())).astype(np.float32)
        slab = native.ScoreSlab()
        try:
            slab.publish(bank)
            s_bank = float(slab.score_route(x, rh)[0][0])
            slab.publish_delta(delta)
            s_delta = float(slab.score_route(x, rh)[0][0])
            assert abs(s_bank - s_delta) > 1e-6
            stop = threading.Event()
            bad = []
            applied = [0]

            def publisher():
                while not stop.is_set():
                    slab.publish(bank)        # resets to generation 1
                    slab.publish_delta(delta)  # fenced 1 -> 2
                    applied[0] += 1

            def scorer_thread():
                while not stop.is_set():
                    out = slab.score_route(x, rh)
                    s = float(out[0][0])
                    if (abs(s - s_bank) > 1e-6
                            and abs(s - s_delta) > 1e-6):
                        bad.append(s)

            threads = [threading.Thread(target=publisher)] + [
                threading.Thread(target=scorer_thread) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join()
            assert applied[0] > 10
            assert bad == [], f"torn scores observed: {bad[:5]}"
            assert slab.stats()["delta_swaps"] > 10
        finally:
            slab.close()


async def _paced(port: int, n: int, host: bytes = b"svc"):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    rsp = b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok"
    try:
        for _ in range(n):
            w.write(b"GET / HTTP/1.1\r\nHost: " + host + b"\r\n\r\n")
            await w.drain()
            await r.readexactly(len(rsp))
    finally:
        w.close()
        try:
            await w.wait_closed()
        except Exception:  # noqa: BLE001
            pass


class TestEngineBankServing:
    def test_two_worker_engine_serves_specialist_head(self):
        """Real loopback traffic through a 2-worker shard group: rows
        for the hashed route score on the specialist head (shared
        slab), a REMOVE delta rolls just that route back to the base
        model, and the merged stats carry the bank generation/heads."""
        dst = "/fp/spec"
        rh = route_hash(dst)
        base, head = _fake_snap(1), _fake_snap(2, scale=0.5)

        async def go():
            eng = native.FastPathEngine(workers=2)
            port = eng.listen("127.0.0.1", 0)
            srv = await EchoBackend().start()
            bport = srv.bound_port
            try:
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                assert eng.set_route_feature("svc", 14, 1.0)
                assert eng.set_route_hash("svc", rh)
                assert not eng.set_route_hash("ghost", rh)
                eng.publish_weights(export_bank_blob(
                    base, 1, 3, {rh: (1, head)}))
                # spread over both workers: several connections
                for _ in range(4):
                    await _paced(port, 10)
                await asyncio.sleep(0.15)
                st = eng.stats()["native_scorer"]
                assert st["weights"] and st["generation"] == 3
                assert st["heads"] == 1
                assert st["scored"] == 40
                assert st["specialist_scored"] == 40
                rows = eng.drain_features()
                assert (rows[:, NATIVE_COL_SCORED] == 1.0).all()
                # single-route rollback: REMOVE delta, base serves
                eng.publish_delta(export_delta_blob(3, 4, removes=[rh]))
                for _ in range(2):
                    await _paced(port, 10)
                await asyncio.sleep(0.15)
                st = eng.stats()["native_scorer"]
                assert st["generation"] == 4 and st["heads"] == 0
                assert st["scored"] == 60
                assert st["specialist_scored"] == 40  # frozen: base now
                assert st["delta_swaps"] == 1
            finally:
                eng.close()
                await srv.close()

        run(go())

    def test_h2_engine_control_surface(self):
        eng = native.H2FastPathEngine()
        try:
            eng.set_route("svc", [("127.0.0.1", 1)])
            assert eng.set_route_hash("svc", 77)
            eng.publish_weights(native.score_test_bank(
                generation=1, seed=1, n_heads=1))
            eng.publish_delta(native.score_test_delta(1, 2, 1000,
                                                      seed=2))
            st = eng.stats()["native_scorer"]
            assert st["generation"] == 2 and st["heads"] == 1
        finally:
            eng.close()


class TestRouteMonitors:
    def test_drift_trigger_and_re_anchor(self):
        mon = RouteDriftMonitor(threshold=1.0, min_rows=16)
        rng = np.random.default_rng(0)
        for _ in range(4):
            mon.observe(["/a"] * 8, rng.normal(0.2, 0.02, 8))
        assert mon.score_shift("/a") < 0.5
        assert mon.triggered() == []
        for _ in range(8):
            mon.observe(["/a"] * 8, rng.normal(0.8, 0.02, 8))
        assert mon.score_shift("/a") > 1.0
        assert mon.triggered() == ["/a"]
        mon.re_anchor("/a")
        assert mon.score_shift("/a") == 0.0
        assert mon.triggered() == []

    def test_replay_window_bounds(self):
        w = RouteReplayWindow(per_route_rows=16, max_routes=2)
        x = np.arange(40, dtype=np.float32).reshape(10, 4)
        lab = np.zeros(10, np.float32)
        w.add(["/a"] * 10, x, lab, lab)
        w.add(["/a"] * 10, x + 100, lab, lab)
        assert w.rows("/a") == 16
        xa, _, _ = w.sample("/a")
        assert xa[-1, 0] == 136.0  # newest rows kept
        w.add(["/b"] * 10, x, lab, lab)
        w.add(["/c"] * 10, x, lab, lab)  # evicts the stalest (/a)
        assert w.rows("/a") == 0
        assert w.rows("/b") == 10 and w.rows("/c") == 10


class _SnapScorer:
    """Sync-snapshot scorer stub: the pipeline only needs snapshot()."""

    def __init__(self, snap):
        self._snap = snap
        self._step = snap.step

    def snapshot(self):
        return self._snap


def _shifted_pipeline(cfg=None, store=None):
    """A pipeline with route /a warmed on low scores then shifted —
    pending_route() == '/a'."""
    p = (cfg or DistillConfig(maxHeads=4, driftThreshold=0.5,
                              minRouteRows=32, retrainSteps=2,
                              cooldownS=0.0)).mk(None, store=store)
    rng = np.random.default_rng(0)
    dim = 36
    for loc, n in ((0.1, 6), (0.9, 6)):
        for _ in range(n):
            x = rng.standard_normal((16, dim)).astype(np.float32)
            s = rng.normal(loc, 0.02, 16).astype(np.float32)
            p.observe_batch(["/a"] * 16, x, s, np.zeros(16, np.float32),
                            np.zeros(16, np.float32))
    return p


class TestPipeline:
    def test_promote_publishes_delta_and_records_lineage(
            self, trained_snapshot, tmp_path):
        from linkerd_tpu.lifecycle import CheckpointStore
        snap, _, _ = trained_snapshot
        store = CheckpointStore(str(tmp_path / "ck"))
        pipe = _shifted_pipeline(store=store)
        published = []
        pipe.set_publisher(lambda full, delta:
                           published.append((full, delta)) or True)
        assert pipe.pending_route() == "/a"
        out = run(pipe.run_once(_SnapScorer(snap), base_version=42))
        assert out is not None and out["action"] == "promoted"
        assert out["delta_published"]
        assert pipe.bank.generation == 1 and len(pipe.bank) == 1
        (full, delta), = published
        assert blob_meta(full)["format"] == "bank"
        dm = blob_meta(delta)
        assert dm["format"] == "delta" and dm["new_generation"] == 1
        # delta is the per-route increment, smaller than the full bank
        assert len(delta) < len(full)
        # manifest lineage: the head's dst/base checkpoint/delta CRC
        rh = str(route_hash("/a"))
        spec = store.specialists()
        assert spec[rh]["dst"] == "/a"
        assert spec[rh]["base_version"] == 42
        assert spec[rh]["delta_crc"] == dm["crc"]
        # survives a reload
        assert CheckpointStore(str(tmp_path / "ck")).specialists() == spec
        # the trigger cleared: reference re-anchored
        assert pipe.pending_route() is None

    def test_poisoned_candidate_rejected(self, trained_snapshot,
                                         monkeypatch, tmp_path):
        """A candidate whose fine-tune went bad (poisoned rows -> NaN
        params) regresses on the held-out rows and never publishes."""
        import linkerd_tpu.distill.pipeline as pipeline_mod
        snap, _, _ = trained_snapshot
        real = pipeline_mod.distill_head

        def poisoned(base_snap, x, labels, mask, steps, lr):
            import copy
            bad = copy.deepcopy(real(base_snap, x, labels, mask, 1, lr))
            bad.params["enc"][0]["w"] = np.full_like(
                np.asarray(bad.params["enc"][0]["w"]), np.nan)
            return bad

        monkeypatch.setattr(pipeline_mod, "distill_head", poisoned)
        from linkerd_tpu.lifecycle import CheckpointStore
        store = CheckpointStore(str(tmp_path / "ck"))
        pipe = _shifted_pipeline(store=store)
        published = []
        pipe.set_publisher(lambda full, delta:
                           published.append((full, delta)) or True)
        out = run(pipe.run_once(_SnapScorer(snap)))
        assert out is not None and out["action"] == "rejected"
        assert "finite" in out["decision"]["reason"] \
            or "regressed" in out["decision"]["reason"]
        assert published == []
        assert pipe.bank.generation == 0 and len(pipe.bank) == 0
        assert store.specialists() == {}

    def test_rollback_route_removes_single_head(self, trained_snapshot,
                                                tmp_path):
        from linkerd_tpu.lifecycle import CheckpointStore
        snap, _, _ = trained_snapshot
        store = CheckpointStore(str(tmp_path / "ck"))
        pipe = _shifted_pipeline(store=store)
        published = []
        pipe.set_publisher(lambda full, delta:
                           published.append((full, delta)) or True)
        run(pipe.run_once(_SnapScorer(snap)))
        assert len(pipe.bank) == 1
        assert run(pipe.rollback_route("/a")) is True
        assert len(pipe.bank) == 0 and pipe.bank.generation == 2
        _, delta = published[-1]
        assert blob_meta(delta)["ops"] == 1
        assert store.specialists() == {}
        assert run(pipe.rollback_route("/a")) is False

    def test_bank_capacity_blocks_new_routes(self):
        pipe = DistillConfig(maxHeads=1, driftThreshold=0.5,
                             minRouteRows=16, cooldownS=0.0).mk(None)
        rng = np.random.default_rng(1)
        for dst in ("/a", "/b"):
            for loc in (0.1, 0.9):
                for _ in range(4):
                    x = rng.standard_normal((16, 36)).astype(np.float32)
                    s = rng.normal(loc, 0.02, 16).astype(np.float32)
                    pipe.observe_batch([dst] * 16, x, s,
                                       np.zeros(16, np.float32),
                                       np.zeros(16, np.float32))
        # both shifted; fill the bank with /a manually
        pipe.bank.upsert("/a", _fake_snap(1), 1, 1, 1)
        # /a may retrain (existing head), /b may not (bank full)
        assert pipe.pending_route() in ("/a",)

    def test_cooldown_blocks_immediate_retrain(self, trained_snapshot):
        snap, _, _ = trained_snapshot
        pipe = _shifted_pipeline(
            DistillConfig(maxHeads=4, driftThreshold=0.5,
                          minRouteRows=32, retrainSteps=1,
                          cooldownS=3600.0))
        pipe.set_publisher(lambda full, delta: True)
        out = run(pipe.run_once(_SnapScorer(snap)))
        assert out is not None
        # even if the route drifts again, the cooldown holds it
        rng = np.random.default_rng(2)
        for _ in range(6):
            x = rng.standard_normal((16, 36)).astype(np.float32)
            s = rng.normal(0.02, 0.01, 16).astype(np.float32)
            pipe.observe_batch(["/a"] * 16, x, s,
                               np.zeros(16, np.float32),
                               np.zeros(16, np.float32))
        assert pipe.pending_route() is None


class TestContinuousLearningE2E:
    def test_drift_to_specialist_loop(self):
        """The acceptance loop: per-route shift -> trigger -> retrain
        from the route's replay -> shadow gate -> delta publish -> a
        2-worker engine serves the route with the specialist head
        (stats + /model.json), a poisoned candidate is rejected, and a
        single-route rollback leaves the other head serving."""
        dst_a, dst_b = "/fp/spec", "/fp/beta"

        async def go():
            cfg = JaxAnomalyConfig(
                maxBatch=256, trainEveryBatches=0,
                distill=DistillConfig(maxHeads=4, driftThreshold=0.5,
                                      minRouteRows=32, retrainSteps=2,
                                      cooldownS=0.0))
            mt = MetricsTree()
            tele = JaxAnomalyTelemeter(cfg, mt)
            eng = native.FastPathEngine(workers=2)
            port = eng.listen("127.0.0.1", 0)
            srv = await EchoBackend().start()
            bport = srv.bound_port
            try:
                eng.start()
                eng.set_route("svc", [("127.0.0.1", bport)])
                eng.set_route_feature("svc", 14, 1.0)
                eng.set_route_hash("svc", route_hash(dst_a))
                eng.set_route("beta", [("127.0.0.1", bport)])
                eng.set_route_feature("beta", 15, -1.0)
                eng.set_route_hash("beta", route_hash(dst_b))
                tele.register_weight_sink(
                    eng.publish_weights, delta_sink=eng.publish_delta)
                assert await tele.refresh_native_weights() is True
                assert eng.stats()["native_scorer"]["weights"]

                rng = np.random.default_rng(0)

                async def feed(dst, lat, status, batches):
                    for _ in range(batches):
                        for _ in range(32):
                            tele.ring.append((FeatureVector(
                                dst_path=dst,
                                latency_ms=float(rng.uniform(*lat)),
                                status=status), None))
                        await tele.drain_once()

                async def wait_outcome(action, route):
                    for _ in range(600):
                        o = tele.distill.last_outcome
                        if o is not None and o["action"] == action \
                                and o["route"] == route:
                            return o
                        await asyncio.sleep(0.05)
                    raise AssertionError(
                        f"no {action} outcome for {route}; last: "
                        f"{tele.distill.last_outcome}")

                # route A: normal phase anchors, shift triggers
                await feed(dst_a, (5, 10), 200, 6)
                await feed(dst_a, (2000, 4000), 503, 8)
                out = await wait_outcome("promoted", dst_a)
                assert out["delta_published"]
                gen_a = out["generation"]
                # the engines observed the delta: generation + head
                st = eng.stats()["native_scorer"]
                assert st["generation"] == gen_a and st["heads"] == 1
                # and the route's live traffic scores on the specialist
                await _paced(port, 20)
                await asyncio.sleep(0.15)
                st = eng.stats()["native_scorer"]
                assert st["specialist_scored"] >= 20
                # /model.json: bank generation + per-head lineage
                ms = tele.model_state()
                bank = ms["distill"]["bank"]
                assert bank["generation"] == gen_a
                assert str(route_hash(dst_a)) in bank["heads"]

                # route B promotes too (two heads serving)
                tele.distill.last_outcome = None
                await feed(dst_b, (5, 10), 200, 6)
                await feed(dst_b, (2000, 4000), 503, 8)
                out_b = await wait_outcome("promoted", dst_b)
                assert eng.stats()["native_scorer"]["heads"] == 2

                # poisoned candidate for a third route is rejected and
                # nothing about the serving bank changes
                import linkerd_tpu.distill.pipeline as pipeline_mod
                real = pipeline_mod.distill_head

                def poisoned(base_snap, x, labels, mask, steps, lr):
                    import copy
                    bad = copy.deepcopy(real(base_snap, x, labels,
                                             mask, 1, lr))
                    bad.params["enc"][0]["w"] = np.full_like(
                        np.asarray(bad.params["enc"][0]["w"]), np.nan)
                    return bad

                pipeline_mod.distill_head = poisoned
                try:
                    tele.distill.last_outcome = None
                    await feed("/fp/poison", (5, 10), 200, 6)
                    await feed("/fp/poison", (2000, 4000), 503, 8)
                    out_p = await wait_outcome("rejected", "/fp/poison")
                finally:
                    pipeline_mod.distill_head = real
                st = eng.stats()["native_scorer"]
                assert st["heads"] == 2
                assert st["generation"] == out_b["generation"]
                flat = mt.flatten()
                assert flat["anomaly/distill/rejections"] == 1
                assert flat["anomaly/distill/promotions"] == 2

                # single-route rollback: A's head goes, B's stays and
                # keeps serving its specialist
                assert await tele.distill.rollback_route(dst_a)
                st = eng.stats()["native_scorer"]
                assert st["heads"] == 1
                before = st["specialist_scored"]
                await _paced(port, 10, host=b"beta")   # B: specialist
                await _paced(port, 10, host=b"svc")    # A: base again
                await asyncio.sleep(0.15)
                st = eng.stats()["native_scorer"]
                assert st["specialist_scored"] == before + 10
            finally:
                tele.close()
                eng.close()
                await srv.close()

        run(go())


class TestControllerStatsExport:
    def test_specialist_stats_reach_metrics_tree(self):
        """The controller's stats loop exports the bank fields under
        rt/<label>/fastpath/scorer/* — the live proof surface the e2e
        acceptance reads (specialist_scored / delta_swaps counters,
        generation / heads gauges)."""
        from linkerd_tpu.core import Dtab, Path
        from linkerd_tpu.router.fastpath import FastPathController

        class StubEngine:
            def stats(self):
                return {"native_scorer": {
                    "weights": True, "version": 3, "crc": 1,
                    "generation": 5, "heads": 2,
                    "swaps": 4, "delta_swaps": 3, "retries": 0,
                    "scored": 100, "specialist_scored": 60,
                    "unscored": 0, "score_ns_hist": []}}

        mt = MetricsTree()
        ctl = FastPathController(
            StubEngine(), interpreter=None, base_dtab=Dtab.read(""),
            prefix=Path.read("/svc"), label="fp", metrics=mt)
        ctl._export_stats()
        flat = mt.flatten()
        assert flat["rt/fp/fastpath/scorer/scored"] == 100
        assert flat["rt/fp/fastpath/scorer/specialist_scored"] == 60
        assert flat["rt/fp/fastpath/scorer/delta_swaps"] == 3
        assert flat["rt/fp/fastpath/scorer/generation"] == 5.0
        assert flat["rt/fp/fastpath/scorer/heads"] == 2.0


class TestConfigAndState:
    def test_distill_config_parses_from_yaml(self):
        from linkerd_tpu.config.parser import instantiate
        cfg = instantiate("telemeter", {
            "kind": "io.l5d.jaxAnomaly",
            "distill": {"maxHeads": 8, "driftThreshold": 1.5,
                        "quant": "int4"},
        }, "telemetry[0]")
        assert cfg.distill.maxHeads == 8
        assert cfg.distill.quant == "int4"

    def test_telemeter_validates_distill_quant(self):
        with pytest.raises(ValueError, match="distill.quant"):
            JaxAnomalyTelemeter(
                JaxAnomalyConfig(distill=DistillConfig(quant="fp8")),
                MetricsTree())

    def test_pipeline_validates_knobs(self):
        for kw in ({"maxHeads": 0}, {"driftThreshold": 0.0},
                   {"minRouteRows": 2}, {"retrainSteps": 0},
                   {"learningRate": 0.0}, {"cooldownS": -1.0}):
            with pytest.raises(ValueError):
                DistillConfig(**kw).mk(None)
