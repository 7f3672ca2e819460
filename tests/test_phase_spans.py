"""The phase clock of the accelerator tier's host path
(``telemetry/phases.py``): what ``RingDispatcher.dispatch``, the drainer
and ``InProcessScorer.fit`` write into it, the scorer-span tags read off
it, the benchmark's readers over it, and the names the device programs
carry. CPU, tiny sizes: spans' order, tiling and counts, never a time."""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest

from linkerd_tpu.models.features import FeatureVector
from linkerd_tpu.router.tracing import TraceId
from linkerd_tpu.telemetry import phases
from linkerd_tpu.telemetry.anomaly import (
    InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.linerate import RingDispatcher
from linkerd_tpu.telemetry.metrics import MetricsTree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mlp36-online.drain32"


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


def one_chip_scorer(**kw):
    """On the first of the test mesh's eight CPU devices: the fused
    single-chip path's shapes, not the mesh's multiples of eight."""
    import jax
    return InProcessScorer(devices=jax.devices()[:1], **kw)


def since(t, kind):
    """The log's calls of ``kind`` that began at ``t`` or later: a test
    reads the clock, makes its calls, and finds them so."""
    return [c for c in phases.records() if c.t0 >= t and c.kind == kind]


class Held:
    """A step's result whose readback keeps the drainer until released."""

    def __init__(self, release: threading.Event):
        self.release = release

    def __array__(self, dtype=None, copy=None):
        self.release.wait(10)  # on the drainer thread
        return np.zeros(4, np.float32)


def assert_tiles(call):
    """The phases lie end to end from the call's entry to its last stamp,
    none negative."""
    kids = list(call.spans())
    assert [n for n, _, _ in kids] == [n for n, _ in call.marks]
    assert kids[0][1] == call.t0 and kids[-1][2] == call.marks[-1][1]
    for (_, _, end), (_, start, _) in zip(kids, kids[1:]):
        assert start == end
    assert all(end >= start for _, start, end in kids)
    assert sum(e - s for _, s, e in kids) == pytest.approx(
        call.marks[-1][1] - call.t0, rel=1e-9)


class TestScorePath:
    def test_every_score_root_has_its_eight_children_in_order(self):
        async def go():
            scorer = one_chip_scorer()
            try:
                x = np.ones((5, scorer.cfg.in_dim), np.float64)
                t = time.monotonic()
                await asyncio.gather(scorer.score(x), scorer.score(x))
                await scorer.score(x)
                return t
            finally:
                scorer.close()

        calls = since(run(go()), phases.SCORE)
        assert len(calls) == 3
        for c in calls:
            assert tuple(n for n, _ in c.marks) == phases.SCORE_PHASES
            assert_tiles(c)
            # 5 rows pad to the 8-row bucket, which ships; 8 scores back
            assert c.counts == {"score.calls": 1, "put.bytes": 8 * 36 * 4,
                                "readback.bytes": 8 * 4}

    def test_a_flow_call_maps_its_flows_between_slot_and_stage(self):
        """A model with state per flow: ``flow.map`` after the slot is
        held, the table's counts and the device step's on the record."""
        import jax
        from linkerd_tpu.models.spec import latent_moe
        from tests.test_latent_moe import CFG, rows_of

        async def go():
            scorer = InProcessScorer(seed=1, spec=latent_moe(CFG),
                                     devices=jax.devices()[:1])
            try:
                t = time.monotonic()
                rows = rows_of({5: [3, 4, 5], 6: [7, 8]})
                await asyncio.gather(scorer.score(rows), scorer.score(rows))
                return t, scorer.last_timing, scorer.device_state()
            finally:
                scorer.close()

        t, timing, state = run(go())
        calls = since(t, phases.SCORE)
        assert len(calls) == 2
        for i, c in enumerate(calls):
            assert tuple(n for n, _ in c.marks) == (
                phases.SLOT_WAIT, phases.FLOW_MAP) + phases.SCORE_PHASES[1:]
            assert_tiles(c)
            pairs = c.counts.pop("moe.local_pairs")
            assert 0 <= c.counts.pop("moe.max_expert_tokens") <= pairs <= 20
            # tiles of 8 rows the expert loop ran: they hold the pairs
            tiles = c.counts.pop("moe.tiles")
            assert pairs <= 8 * tiles < pairs + 2 * 4 * 8
            # XLA's loop reads an expert's weights a tile
            assert c.counts.pop("moe.weight_loads") == tiles
            # 5 rows pad to the 8-row bucket of int32 triples
            assert c.counts == {
                "score.calls": 1, "put.bytes": 8 * 3 * 4,
                "readback.bytes": 8 * 4, "flow.events": 5,
                "flow.restarts": 2 if i == 0 else 0, "flow.evictions": 0,
                "flow.wraps": 0, "flow.resident": 2,
                "cache.positions": 7 if i == 0 else 12,
                # XLA's attention: 3 layers x 2 flows, each slot whole
                "attn.kv_blocks": 6, "attn.kv_blocks_whole": 6,
                # the append: a window of T + 1 = 5 of a slot's 64
                # positions a flow a layer
                "cache.rows_written": 3 * 2 * 5,
                "cache.rows_whole": 3 * 2 * 64,
                # 2 flows a layer, none by the append's kernel (XLA's here)
                "append.flows": 3 * 2, "append.flows_in_kernel": 0}
        assert timing["bytes"] == 8 * 3 * 4 + 8 * 4
        assert state["flow"]["layouts"] == {"2x4": 2}
        assert state["flow"]["append"] == "xla"
        assert np.shape(state["flow"]["expert_tokens"]) == (2, 4)

    def test_third_dispatch_at_depth_two_waits_for_a_slot(self):
        release = threading.Event()

        async def go():
            d = RingDispatcher(2, lambda n: 4, depth=2)
            t = time.monotonic()
            try:
                tasks = [asyncio.ensure_future(d.dispatch(
                    np.ones((2, 2), np.float32), lambda s: Held(release),
                    lambda s: s)) for _ in range(3)]
                await asyncio.sleep(0.05)
                release.set()
                await asyncio.gather(*tasks)
            finally:
                release.set()
                d.close()
            return t

        calls = sorted(since(run(go()), phases.SCORE), key=lambda c: c.t0)
        assert [c.counts.get("slot.waits", 0) for c in calls] == [0, 0, 1]
        assert calls[2].ms(phases.SLOT_WAIT) >= 40.0
        assert calls[0].ms(phases.SLOT_WAIT) < 40.0

    def test_failed_dispatch_leaves_a_closed_record_and_a_free_slot(self):
        async def go():
            d = RingDispatcher(2, lambda n: 4, depth=1)

            def boom(staging):
                raise RuntimeError("no")

            try:
                t = time.monotonic()
                with pytest.raises(RuntimeError):
                    await d.dispatch(np.ones((2, 2), np.float32), boom,
                                     lambda s: s)
                assert not any(s.busy for s in d._slots[4])
                out = await d.dispatch(np.ones((2, 2), np.float32),
                                       lambda s: s.sum(axis=1), lambda s: s)
                assert (out == 2.0).all()
            finally:
                d.close()
            return d, t

        d, t = run(go())
        failed, ok = since(t, phases.SCORE)
        assert [n for n, _ in failed.marks] == [
            phases.SLOT_WAIT, phases.STAGE, phases.PUT]
        assert "score.calls" not in failed.counts
        assert_tiles(failed)
        assert d.last is ok and ok.counts["score.calls"] == 1

    def test_a_cancelled_call_closes_as_it_stood_and_is_not_written_again(
            self):
        release = threading.Event()

        async def go():
            d = RingDispatcher(2, lambda n: 4)
            t = time.monotonic()
            try:
                task = asyncio.ensure_future(d.dispatch(
                    np.ones((2, 2), np.float32), lambda s: Held(release),
                    lambda s: s))
                await asyncio.sleep(0.05)
                task.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await task
                (closed,) = since(t, phases.SCORE)
                stood = closed.marks
                release.set()           # the drainer goes on, and stamps
                while any(s.busy for s in d._slots[4]):
                    await asyncio.sleep(0.001)
                # the slot is free and the ring serves the next call
                await d.dispatch(np.ones((2, 2), np.float32),
                                 lambda s: s.sum(axis=1), lambda s: s)
            finally:
                release.set()
                d.close()
            return t, closed, stood

        t, closed, stood = run(go())
        # no hop was made: the loop's four phases, and the drainer's first
        assert [n for n, _ in stood][:4] == list(phases.SCORE_PHASES[:4])
        assert phases.HOP not in [n for n, _ in stood]
        assert closed.marks is stood and isinstance(stood, tuple)
        assert_tiles(closed)
        assert since(t, phases.SCORE)[0] is closed

    def test_the_log_keeps_4096_records_and_no_more(self):
        assert phases.LOG_CAPACITY == 4096
        first = phases.Call(phases.SCORE).close()
        for _ in range(phases.LOG_CAPACITY - 1):
            phases.Call(phases.SCORE).close()
        log = phases.records()
        assert len(log) == phases.LOG_CAPACITY and log[0] is first
        second = log[1]
        phases.Call(phases.SCORE).close()
        log = phases.records()
        assert len(log) == phases.LOG_CAPACITY and log[0] is second

    def test_a_closed_record_is_a_copy_no_one_writes(self):
        live = phases.Call(phases.FIT)
        live.mark(phases.UPDATE_NORM)
        live.count("fit.calls")
        done = live.close()
        live.mark(phases.PREP)
        live.count("fit.calls")
        assert [n for n, _ in done.marks] == [phases.UPDATE_NORM]
        assert done.counts == {"fit.calls": 1} and done.t0 == live.t0
        assert phases.records()[-1] is done


class TestFitPath:
    def test_fit_root_has_a_step_a_train_step_and_counts_what_it_ships(self):
        async def go():
            scorer = one_chip_scorer(fit_steps=3)
            try:
                d = scorer.cfg.in_dim
                x = np.random.default_rng(0).normal(
                    size=(8, d)).astype(np.float32)
                labels = np.array([0, 1, 0, 0, 1, 0, 0, 0], np.float32)
                mask = np.array([1, 1, 0, 0, 0, 1, 0, 0], np.float32)
                t = time.monotonic()
                await scorer.fit(x[:6], labels[:6], mask[:6])
                await scorer.fit(x, labels, mask)
                return t
            finally:
                scorer.close()

        padded, exact = since(run(go()), phases.FIT)
        for c in (padded, exact):
            assert [n for n, _ in c.marks] == [
                phases.PREP, phases.UPDATE_NORM, phases.THREAD_HOP,
                phases.STEP, phases.STEP, phases.STEP,
                phases.LOSS_WAIT, phases.RETURN_HOP]
            assert_tiles(c)
        # 6 rows pad to 8: x, labels, mask and the row mask ship, once a
        # fit and not once a step
        assert padded.counts == {
            "fit.calls": 1, "fit.shipped_bytes": 8 * 36 * 4 + 3 * 8 * 4}
        # 8 rows are a bucket: no row mask
        assert exact.counts == {
            "fit.calls": 1, "fit.shipped_bytes": 8 * 36 * 4 + 2 * 8 * 4}


class TestScorerSpanTags:
    def test_with_a_sink_every_call_rides_the_ring_and_tags_come_from_it(
            self):
        class Sink:
            def __init__(self):
                self.spans = []

            def record(self, span):
                self.spans.append(span)

        async def go():
            sink = Sink()
            tele = JaxAnomalyTelemeter(
                JaxAnomalyConfig(trainEveryBatches=0), MetricsTree())
            tele.set_tracer(sink)
            scorer = tele._ensure_scorer()
            try:
                assert scorer.last_timing is None
                for _ in range(8):
                    tele.ring.append((FeatureVector(), None,
                                      TraceId.mk_root(True), None, None))
                    assert await tele.drain_once() == 1
                assert sum(scorer._dispatcher.batches.values()) == 8
                return sink.spans, scorer.last_timing, \
                    scorer._dispatcher.last, scorer._bucket_target(1)
            finally:
                tele.close()

        spans, timing, last, bucket = run(go())
        shipped = bucket * 36 * 4 + bucket * 4  # the padded rows, their scores
        assert sorted(timing) == ["bytes", "device_ms", "hop_ms",
                                  "queue_ms", "transfer_ms"]
        assert timing["bytes"] == shipped
        assert timing["device_ms"] == last.ms(phases.DEVICE_WAIT)
        assert timing["transfer_ms"] == pytest.approx(
            last.ms(phases.STAGE) + last.ms(phases.PUT)
            + last.ms(phases.READBACK))
        per_request = [s for s in spans if s["name"] == "scorer"]
        batches = [s for s in spans if s["name"] == "scorer.batch"]
        assert len(per_request) == 8 and len(batches) == 8
        for s in per_request + batches:
            for key in ("queue_ms", "device_ms", "transfer_ms", "hop_ms"):
                assert float(s["tags"][f"scorer.{key}"]) >= 0.0
            assert s["tags"]["scorer.bytes"] == str(shipped)
        # the newest batch span carries the newest ring call's own numbers
        assert batches[-1]["tags"]["scorer.device_ms"] == \
            f"{timing['device_ms']:.3f}"


# -- the benchmark's readers --------------------------------------------------

T = 1e9     # a clock no real record of this process reaches


def _call(kind, t0, marks, counts):
    c = phases.Call(kind)
    c.t0 = T + t0
    c.marks = [(name, T + t) for name, t in marks]
    c.counts = counts
    return c.close()


@pytest.fixture(scope="module")
def hand_made_run():
    """Three programs on one device and five spans on two threads, on a
    slice of 10 s that opens 100 s into the trace's clock.

    Device busy 102-103 (two operations), 105-106, 108-109: idle 7 s of
    the slice, in gaps 100-102, 103-105, 106-108, 109-110. Loop thread:
    ``fit.update_norm`` 100.5-104 and ``dispatch.stage`` 104-104.5;
    worker: ``fit.step`` 104-107; drainer: ``drain.device_wait`` 104.5-108
    (a wait) and ``drain.readback`` 108-109.5."""
    _call(phases.FIT, 100.5,
          [(phases.UPDATE_NORM, 104.0), (phases.STEP, 107.0)],
          {"fit.calls": 1, "fit.shipped_bytes": 3 * 2 ** 20})
    _call(phases.SCORE, 104.0,
          [(phases.STAGE, 104.5), (phases.DEVICE_WAIT, 108.0),
           (phases.READBACK, 109.5)],
          {"score.calls": 1, "slot.waits": 1})
    # a call before the window opened: in the log, in no window statistic
    _call(phases.SCORE, 50.0, [(phases.STAGE, 59.0)],
          {"score.calls": 1})
    # a flow call's own span and counts, past the traced slice
    _call(phases.SCORE, 120.0, [(phases.FLOW_MAP, 120.25)],
          {"moe.local_pairs": 960, "moe.max_expert_tokens": 40,
           "moe.tiles": 10, "moe.weight_loads": 4,
           "cache.positions": 5000, "flow.events": 64})

    def program(start, ops):
        return {"plane": "/device:TPU:0", "name": "jit_score",
                "start": start * 1e9, "dur": 1e9,
                "ops": [{"start": a * 1e9, "dur": (b - a) * 1e9,
                         "name": "%op"} for a, b in ops]}

    yield {"window": {"t0": T + 96.0, "t_end": T + 140.0},
           "trace_marks": {"clock0": T, "lo": T + 100.0, "hi": T + 110.0},
           "trace": {"programs": [
               program(102, [(102, 102.5), (102.5, 103)]),
               program(105, [(105, 106)]), program(108, [(108, 109)]),
               # another chip's programs are not the first device's
               {"plane": "/device:TPU:1", "name": "jit_score",
                "start": 100e9, "dur": 10e9, "ops": [
                    {"start": 100e9, "dur": 10e9, "name": "%op"}]}]}}
    # the log is the process's: a later test of this worker that picks its
    # calls by ``t0 >=`` a reading of the real clock would find these, on
    # their clock far ahead of it, among its own
    with phases._lock:
        real = [c for c in phases._log if c.t0 < T]
        phases._log.clear()
        phases._log.extend(real)


READINGS = [
    ("program_span_stat", {"span": "dispatch.stage", "stat": "mean"}, 500.0),
    ("program_span_stat", {"span": "fit.step", "stat": 95}, 3000.0),
    ("program_span_stat", {"span": ["fit.update_norm", "fit.step"],
                           "stat": "mean", "sum_per_call": True}, 6500.0),
    ("program_span_stat", {"span": "fit.loss_wait", "stat": "mean"}, None),
    ("program_count_per", {"count": "fit.shipped_bytes", "per": "fit.calls",
                           "scale": 2.0 ** -20}, 3.0),
    ("program_count_per", {"count": "slot.waits", "per": "score.calls",
                           "scale": 100}, 100.0),
    ("program_count_per", {"count": "slot.waits", "per": "no.such"}, None),
    ("program_span_stat", {"span": "flow.map", "stat": "mean"}, 250.0),
    # the fullest of 4 x 12 held experts over their mean
    ("program_count_per", {"count": "moe.max_expert_tokens",
                           "per": "moe.local_pairs", "scale": 48}, 2.0),
    ("program_count_per", {"count": "cache.positions",
                           "per": "flow.events"}, 78.125),
    # whole experts' weights read, of one a tile (the two
    # ``*weight_loads_share`` metrics' file)
    ("program_count_per", {"count": "moe.weight_loads", "per": "moe.tiles",
                           "scale": 100}, 40.0),
    # idle inside update_norm: 100.5-102 and 103-104
    ("idle_by_span", {"spans": ["fit.update_norm"]}, 25.0),
    # inside fit.step 104-107: 104-105 and 106-107
    ("idle_by_span", {"spans": ["fit.step"]}, 20.0),
    # stage 104-104.5 lies inside the step's idle second too: they overlap
    ("idle_by_span", {"spans": ["dispatch.stage", "fit.step"]}, 20.0),
    # readback 108-109.5: idle 109-109.5
    ("idle_by_span", {"spans": ["drain.readback"]}, 5.0),
    # no span but the wait covers 100-100.5, 107-108, 109.5-110
    ("idle_by_span", {"outside_all_but": ["drain.device_wait"]}, 20.0),
    # with the wait counted as a span only 100-100.5 and 109.5-110 are left
    ("idle_by_span", {"outside_all_but": []}, 10.0),
]


@pytest.mark.parametrize("reader,how,want", READINGS)
def test_reader_on_a_hand_made_run(hand_made_run, reader, how, want):
    from chipbench import harness
    got = harness.load_code("readers", reader).read(hand_made_run, how)
    assert got == (want if want is None else pytest.approx(want, rel=1e-9))


@pytest.mark.parametrize("reader,how", [
    ("program_span_stat", {"span": "dispatch.stage", "stat": "mean"}),
    ("program_count_per", {"count": "slot.waits", "per": "score.calls"}),
    ("idle_by_span", {"spans": ["fit.update_norm"]}),
])
def test_reader_returns_nothing_without_spans(hand_made_run, monkeypatch,
                                              reader, how):
    """As with ``--entry control_fp8``, or a program that has no log."""
    from chipbench import harness
    monkeypatch.setattr(phases, "records", lambda: [])
    assert harness.load_code("readers", reader).read(
        hand_made_run, how) is None
    assert harness.load_code("readers", "idle_by_span").read(
        {**hand_made_run, "trace": None}, {"spans": ["fit.step"]}) is None


# two variants of one program, as a process that compiled it for two layouts
# holds them: the instructions of the first run a loop whose body's kernel
# carries no scope of its own (it takes the loop's), a copy no scope at all
SCOPED_HLO = """HloModule jit_scoped_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %inside = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/nowhere"}
}

%body (p: (f32[8])) -> (f32[8]) {
  %p = (f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%p), index=0
  %fusion.1 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/layer1.ffn/expert_tiles/while/body/mul"}
  %custom-call.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  ROOT %tuple.1 = (f32[8]{0}) tuple(%custom-call.1)
}

%cond (q: (f32[8])) -> pred[] {
  %q = (f32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/layer0.attention/project/dot_general"}
  %tuple.2 = (f32[8]{0}) tuple(%fusion.2)
  %while.1 = (f32[8]{0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step)/layer1.ffn/expert_tiles/while"}
  %gte.2 = f32[8]{0} get-tuple-element(%while.1), index=0
  %fusion.3 = f32[8]{0} fusion(%gte.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/head/reduce_max"}
  %copy.1 = f32[8]{0} copy(%fusion.3)
  ROOT %fusion.4 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/layer2.conv/mul"}
}
"""
OTHER_LAYOUT_HLO = """HloModule jit_scoped_step

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/layer0.attention/project/mul"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%f, metadata={op_name="jit(step)/layer0.attention/append/scatter"}
  ROOT %custom-call.8 = f32[8]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer0.attention/attend/pallas_call"}
}
"""
PARTS = ["project", "out", "append", "attend", "conv", "route",
         "expert_tiles", "dense", "head"]


def _op(name, kind, a, b):
    return {"plane": "/device:TPU:0", "name": f"%{name} = f32[8]{{0}} "
            f"{kind}(%x), metadata={{}}", "start": a * 1e9,
            "dur": (b - a) * 1e9}


@pytest.fixture(scope="module")
def scoped_run():
    """Executions of three programs on one device, in seconds on the
    trace's clock. ``jit_scoped_step`` (200-210, by the first variant):
    ``project`` 200-202; the loop 202-207 holding its body's fusion
    202.5-204 and kernel 204-206.5, both ``expert_tiles``, so 5 s of which
    the loop's own is 1; ``head`` 207-208; a copy of no scope 208-208.5;
    ``conv`` 208.5-209.5; idle 209.5-210. Again (300-310, by the second):
    ``project`` 300-303, ``append`` 303-305, ``attend`` 305-309. Each has
    10% in no part. ``jit_unmapped_step``: an operation its one variant
    does not name. ``jit_unnamed_step``: no variant registered."""
    phases.program("jit_scoped_step", lambda: SCOPED_HLO)
    phases.program("jit_scoped_step", lambda: OTHER_LAYOUT_HLO)
    phases.program("jit_unmapped_step", lambda: OTHER_LAYOUT_HLO)

    def program(name, start, ops):
        return {"plane": "/device:TPU:0", "name": name, "start": start * 1e9,
                "dur": 10e9, "ops": ops}
    yield {"trace": {"programs": [
        program("jit_scoped_step", 200, [
            _op("fusion.2", "fusion", 200, 202),
            _op("while.1", "while", 202, 207),
            _op("fusion.1", "fusion", 202.5, 204),
            _op("custom-call.1", "custom-call", 204, 206.5),
            _op("fusion.3", "fusion", 207, 208),
            _op("copy.1", "copy", 208, 208.5),
            _op("fusion.4", "fusion", 208.5, 209.5)]),
        program("jit_scoped_step", 300, [
            _op("fusion.7", "fusion", 300, 303),
            _op("fusion.8", "fusion", 303, 305),
            _op("custom-call.8", "custom-call", 305, 309)]),
        program("jit_unmapped_step", 400, [
            _op("fusion.7", "fusion", 400, 401),
            _op("fusion.9", "fusion", 401, 402)]),
        program("jit_unnamed_step", 500, [
            _op("fusion.7", "fusion", 500, 501)])]}}


SCOPE_READINGS = [
    # the first execution's 2 s and the second's 3: the median of two
    ("^jit_scoped_step$", ["project", "out"], "ms", 2500.0),
    # the loop's own 1 s and its body's 4 (the kernel's by the loop's
    # scope), not its 5 again: 5 s and 0
    ("^jit_scoped_step$", ["expert_tiles"], "ms", 2500.0),
    ("^jit_scoped_step$", ["conv"], "ms", 500.0),
    ("^jit_scoped_step$", ["attend"], "ms", 2000.0),
    ("^jit_scoped_step$", ["dense"], "ms", 0.0),
    # every part: 9 s of each 10, and the copy, which no part names, and
    # the idle half second the other tenth: the two make the step
    ("^jit_scoped_step$", PARTS, "ms", 9000.0),
    ("^jit_scoped_step$", PARTS, "outside_pct", 10.0),
    ("^jit_scoped_step$", ["head"], "outside_pct", 95.0),
    ("^jit_unmapped_step$", PARTS, "ms", None),
    ("^jit_unnamed_step$", PARTS, "outside_pct", None),
]


@pytest.mark.parametrize("program,scopes,stat,want", SCOPE_READINGS)
def test_program_scope_reader_on_a_hand_made_run(scoped_run, program, scopes,
                                                 stat, want):
    from chipbench import harness
    got = harness.load_code("readers", "program_scope_ms").read(
        scoped_run, {"program": program, "scopes": scopes, "stat": stat})
    assert got == (want if want is None else pytest.approx(want, rel=1e-9))


def test_program_scope_reader_reads_nothing_without_the_registry(
        scoped_run, monkeypatch):
    """As on the parent of PR 38, whose ``phases`` registers no program:
    the driver lays this benchmark's files over it for a traced run."""
    from chipbench import harness
    monkeypatch.delattr(phases, "program_scopes")
    assert harness.load_code("readers", "program_scope_ms").read(
        {"trace": scoped_run["trace"]}, {"program": "^jit_scoped_step$",
                                         "scopes": PARTS, "stat": "ms"}) is None


def test_every_new_per_layer_entry_has_its_files_and_its_arrow():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    moved = {m["name"] for m in manifest["end_to_end"]
             if CELL in m.get("workloads", [CELL])}
    mine = [m for m in manifest["per_layer"] if m["name"].startswith(
        ("dispatch.", "drain.", "fit.", "device.idle_"))
        and m["name"] not in ("dispatch.host_ms", "fit.host_ms",
                              "device.idle_pct")]
    assert len(mine) == 18
    layers = {m["layer"] for m in manifest["per_layer"][:9]}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] in moved
        assert m["layer"] in layers
        assert m["source"] in ("host_clock", "program_counter",
                               "device_trace")
        with open(os.path.join(REPO, "chipbench", "metrics",
                               m["name"] + ".json")) as f:
            how = json.load(f)
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "readers", how["reader"] + ".py"))
        named = how.get("span", how.get("spans", how.get(
            "outside_all_but", [])))
        for name in [named] if isinstance(named, str) else named:
            assert name in vars(phases).values(), name


def test_every_flow_entry_has_its_files_and_reads_what_the_program_writes():
    flow_cell = "kimi-k2-6-ep32.flows64x64"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [flow_cell]]
    assert len(mine) == 18     # 17 of PRs 28-31, ``moe.weight_loads_share``
    # and the step's parts and the share of the flows the append's kernel
    # took, each over the flow cells that have it
    shared = [m for m in manifest["per_layer"] if m not in mine
              and flow_cell in m.get("workloads", [])]
    assert [m["name"] for m in shared] == [
        f"flow_step.{p}" for p in ("project_ms", "append_ms", "attend_ms",
                                   "route_ms", "experts_ms", "dense_ms",
                                   "head_ms", "unattributed_pct")] + [
        "append.in_kernel_share"]
    with open(os.path.join(REPO, "chipbench", "metrics",
                           "flow_step.unattributed_pct.json")) as f:
        parts = json.load(f)["scopes"]
    with open(os.path.join(REPO, "linkerd_tpu", "telemetry",
                           "phases.py")) as f:
        documented = f.read()
    for m in mine + shared:
        assert m["moves"] in e2e
        with open(os.path.join(REPO, "chipbench", "metrics",
                               m["name"] + ".json")) as f:
            how = json.load(f)
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "readers", how["reader"] + ".py"))
        if "span" in how:
            assert how["span"] in vars(phases).values()
        for count in (how.get("count"), how.get("per")):
            assert count is None or f"``{count}``" in documented, count
        if "program" in how:
            assert how["program"] == "^jit_flow_step$"
        # a part's names are among those the step opens (the
        # ``_lowered_for_the_chip`` test below holds the step to them)
        assert set(how.get("scopes", [])) <= set(parts)


# -- names on the device ------------------------------------------------------


def test_lowered_programs_carry_their_scopes_and_the_kernel_its_name():
    import jax
    import jax.numpy as jnp

    from linkerd_tpu.models.anomaly import AnomalyModelConfig, init_params
    from linkerd_tpu.ops import scoring

    cfg = AnomalyModelConfig()
    params = init_params(jax.random.key(0), cfg)
    x = jnp.zeros((512, cfg.in_dim), jnp.float32)
    mu, var = jnp.zeros(cfg.in_dim), jnp.ones(cfg.in_dim)
    # lowered for the chip from here: Mosaic serialises without one
    score = scoring.best_scorer(cfg, "tpu", donate=True)
    text = score.trace(params, x, mu, var).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "jit(score)/normalize/" in text
    assert "jit(score)/score_rows/anomaly_score_fused/pallas_call" in text

    scorer = one_chip_scorer()
    try:
        rows = jnp.zeros(512, jnp.float32)
        text = scorer._train_step.trace(
            scorer.params, scorer._opt_state, x, rows, rows, None, mu,
            var).lower().as_text(debug_info=True)
    finally:
        scorer.close()
    for scope in ("normalize", "loss_grad", "adam"):
        assert f"jit(step)/{scope}/" in text, scope


FLOW_CELLS = {"latent_moe": "kimi-k2-6-ep32.flows64x64",
              "lfm2_moe": "lfm2-24b-a2b.flows64x64-fullvocab",
              "laguna_moe": "laguna-xs.2.flows64x64-long"}


def _tiny(model):
    from tests.test_laguna_moe import CFG as LAGUNA
    from tests.test_latent_moe import MODELS
    return LAGUNA if model == "laguna_moe" else MODELS[model].cfg


@pytest.mark.parametrize("model", sorted(FLOW_CELLS))
def test_a_flow_step_lowered_for_the_chip_carries_the_scopes_its_cell_reads(
        model):
    """The step as a TPU gets it (its kernels), at a tiny size, opens
    every scope that a ``program_scope_ms`` entry listing the model's cell
    puts down a part to: ``layer<l>.conv`` for LFM2's convolutions."""
    import re

    import jax
    import jax.numpy as jnp

    from linkerd_tpu.models import latent_moe as lm
    from linkerd_tpu.ops.cache_append import best_append
    from linkerd_tpu.ops.expert_product import best_expert_product
    from linkerd_tpu.ops.flow_attention import best_attention

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    wanted = set()
    for m in manifest["per_layer"]:
        with open(os.path.join(REPO, "chipbench", "metrics",
                               m["name"] + ".json")) as f:
            how = json.load(f)
        if (how["reader"] == "program_scope_ms" and how["stat"] == "ms"
                and FLOW_CELLS[model] in m["workloads"]):
            wanted |= set(how["scopes"])
    assert len(wanted) == (9 if model == "lfm2_moe" else 8)
    cfg = _tiny(model)
    params = jax.eval_shape(lambda: lm.init(jax.random.key(0), cfg))
    state = jax.eval_shape(lambda: lm.init_state(cfg))[:3] + (
        lm.start_shapes(cfg),)
    text = jax.jit(lm.flow_step, static_argnames=(
        "cfg", "F", "T", "attend", "experts", "append")).trace(
        params, state, jax.ShapeDtypeStruct((64, 3), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), cfg=cfg, F=8, T=8,
        attend=best_attention("tpu", model != "latent_moe"),
        experts=best_expert_product("tpu"),
        append=best_append("tpu")).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    opened = set(re.findall(r"[/.](\w+)(?=/)", text))
    assert wanted <= opened, wanted - opened


def test_the_flow_steps_registered_map_names_the_compiled_steps_instructions():
    """A scorer registers its step once, on its first call; the thunk runs
    when the scopes are read, compiles nothing (the lowering is the call's
    own, in JAX's cache), and names exactly the instructions that run as
    operations of the compiled step: its entry's and its loops'."""
    import re

    import jax

    from linkerd_tpu.models import latent_moe as lm
    from linkerd_tpu.models.spec import latent_moe
    from tests.test_latent_moe import CFG, rows_of

    async def go():
        scorer = InProcessScorer(seed=1, spec=latent_moe(CFG),
                                 devices=jax.devices()[:1])
        try:
            before = len(phases.program_scopes("jit_flow_step"))
            rows = rows_of({5: [3, 4, 5], 6: [7, 8]})
            for _ in range(3):
                await scorer.score(rows)
            return before, scorer.params, rows
        finally:
            scorer.close()

    before, params, rows = run(go())
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_k: compiles.append(event))
    maps = phases.program_scopes("jit_flow_step")
    assert len(maps) == before + 1 and not [
        e for e in compiles if "backend_compile" in e]
    scopes = maps[-1]

    # the same program compiled anew, its instructions listed apart: those
    # of every computation but what runs inside one instruction (a
    # fusion's, a reduction's or a sort's comparator, a custom call's)
    state = jax.eval_shape(lambda: lm.init_state(CFG))[:3] + (
        lm.start_shapes(CFG),)
    text = jax.jit(lm.flow_step, static_argnames=(
        "cfg", "F", "T", "attend", "experts"), donate_argnums=(1, 2)).lower(
        params, state, jax.ShapeDtypeStruct((8, 3), rows.dtype),
        np.int32(5), cfg=CFG, F=2, T=4, attend=lm.attend_xla,
        experts=lm.ExpertOps()).compile().as_text()
    inside = {name.strip().lstrip("%") for pair in re.findall(
        r"(?:calls|to_apply)=%?([\w.\-]+)|called_computations=\{([^}]*)\}",
        text) for names in pair for name in names.split(",") if name}
    names, current = set(), None
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if header:
            current = header.group(1)
        named = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if named and current not in inside:
            names.add(named.group(1))
    assert set(scopes) == names
    assert {p.split("/")[1] for p in scopes.values() if "/" in p} >= {
        "layer0.attention", "layer1.ffn", "head"}
