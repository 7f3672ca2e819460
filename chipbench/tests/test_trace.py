"""The reduction from trace events to metrics: by hand on a few made-up
events, and on the small slice recorded on the chip
(``trace/sample_trace.json``: 45 ms of ``mlp36-online.backlog1024``)."""

import json
import os

import pytest

from chipbench import harness
from chipbench.readers import (
    device_idle_pct, kernel_roofline, program_device_ms, program_mfu,
    span_minus_device)
from chipbench.tests.conftest import HERE
from chipbench.trace import reduce as R

SPEC = harness.load_json("trace", "events.json")
MS = 1e6
DEV = "/device:TPU:0"


def _ev(line, name, start_ms, dur_ms, plane=DEV):
    return [plane, line, name, start_ms * MS, dur_ms * MS]


def test_by_hand():
    events = [
        _ev("XLA Modules", "jit_score(123)", 10, 30),
        _ev("XLA Ops", "%fusion.1 = f32[8,36]{1,0} fusion(f32[8,36] %x)", 10, 8),
        _ev("XLA Ops", "%score.1 = f32[8,1]{1,0} custom-call(f32[8,36] %f)", 18, 16),
        _ev("Async XLA Ops", "%copy-start = (f32[4]) copy-start(f32[4] %w)", 9, 3),
        _ev("XLA Ops", "%reduce = f32[8]{0} reduce(f32[8,1] %score.1)", 36, 4),
        _ev("XLA Modules", "jit_step(9)", 60, 20),
        _ev("XLA Ops", "%fusion.7 = bf16[8,256] fusion(bf16[8,36] %a)", 60, 20),
        _ev("XLA Modules", "jit_score(123)", 95, 10),   # cut by the slice
        _ev("XLA Ops", "%score.1 = f32[8,1]{1,0} custom-call(f32[8,36] %f)", 95, 10),
        _ev("Steps", "3", 0, 100),                      # not a busy line
        _ev("python3", "anything", 0, 100, plane="/host:CPU"),
    ]
    spans = {"score_call": [(5 * MS, 45 * MS), (85 * MS, 120 * MS)],
             "fit_call": [(42 * MS, 90 * MS)]}
    t = R.reduce(events, SPEC, 0.0, 100 * MS, spans)
    assert t["window_s"] == pytest.approx(0.100)
    # 9..34 (async copy joins 10..34), 36..40, 60..80, 95..100
    assert t["busy_s"] == pytest.approx((25 + 4 + 20 + 5) / 1e3)
    assert [p["name"] for p in t["programs"]] == ["jit_score", "jit_step"]
    assert R.program_seconds(t, "^jit_score$") == [pytest.approx(0.030)]
    assert R.op_seconds_per_execution(
        t, "^jit_score$", r" custom-call\(") == [pytest.approx(0.016)]
    assert t["spans"] == {"score_call": [(5 * MS, 45 * MS)],
                          "fit_call": [(42 * MS, 90 * MS)]}
    gaps = dict(t["breakdown"]["idle_gaps"])
    # 0..9, 34..36, 40..60 and 80..95, each by what covers its midpoint
    assert gaps["between calls"] == pytest.approx(0.009)
    assert gaps["inside score_call"] == pytest.approx(0.002)
    assert gaps["inside fit_call"] == pytest.approx(0.020)
    assert gaps["inside fit_call+score_call"] == pytest.approx(0.015)
    assert sum(gaps.values()) + t["busy_s"] == pytest.approx(t["window_s"])
    ops = dict(t["breakdown"]["device_ops"])
    assert ops["jit_score/%score.1 custom-call"] == pytest.approx(0.016)
    assert ops["jit_step/%fusion.7 fusion"] == pytest.approx(0.020)

    run = {"trace": t, "peaks": {"bf16_flops_per_s": 100e12,
                                 "hbm_bytes_per_s": 1e12},
           "config": harness.load_json("configs", "mlp36-online.json"),
           "rows_per_call": 1 << 20,
           "counts": harness.load_code("counts", "mlp36")}
    how = lambda name: harness.load_json("metrics", name + ".json")  # noqa: E731
    assert program_device_ms.read(
        run, how("score_step.device_ms")) == pytest.approx(30.0)
    flops = 192_768 * (1 << 20)
    assert program_mfu.read(run, how("score_step.mfu_pct")) == pytest.approx(
        100 * flops / 0.030 / 100e12)
    assert program_mfu.read(run, how("train_step.mfu_pct")) == pytest.approx(
        100 * 3 * flops / 0.020 / 100e12)
    # compute bound: 2.02 ms of FLOPs against 0.16 ms of bytes
    assert kernel_roofline.read(
        run, how("score_kernel_roofline")) == pytest.approx(
            100 * (flops / 100e12) / 0.016)
    assert span_minus_device.read(
        run, how("dispatch.host_ms")) == pytest.approx(40.0 - 30.0)
    assert span_minus_device.read(
        run, how("fit.host_ms")) == pytest.approx(48.0 - 4 * 20.0)
    assert device_idle_pct.read(run, {}) == pytest.approx(46.0)
    # a reader that finds nothing to read returns nothing
    assert program_device_ms.read(run, {"program": "^jit_other$"}) is None
    assert program_mfu.read({**run, "trace": None},
                            how("score_step.mfu_pct")) is None


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        R.reduce([_ev("python3", "x", 0, 1, plane="/host:CPU")], SPEC,
                 0.0, MS, {})


def test_recorded_slice():
    with open(os.path.join(HERE, "trace", "sample_trace.json")) as f:
        s = json.load(f)
    t = R.reduce(s["events"], SPEC, s["lo"], s["hi"], s["spans"])
    assert 0 < t["busy_s"] < t["window_s"] == pytest.approx(
        (s["hi"] - s["lo"]) / 1e9)
    names = {p["name"] for p in t["programs"]}
    assert names == {"jit_score", "jit_step"}
    score = R.program_seconds(t, "^jit_score$")
    step = R.program_seconds(t, "^jit_step$")
    kernel = R.op_seconds_per_execution(t, "^jit_score$", r" custom-call\(")
    assert len(score) == len(kernel) >= 4 and len(step) >= 4
    # one Pallas call in every score program, and part of it
    assert all(0 < k < p for k, p in zip(kernel, score))
    assert max(score) < 1.2 * min(score)
    gaps = dict(t["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) + t["busy_s"] == pytest.approx(t["window_s"])
    assert t["breakdown"]["device_ops"][0][1] > 0
    assert s["expected"]["busy_s"] == pytest.approx(t["busy_s"])
    assert s["expected"]["score_ms"] == pytest.approx(
        sorted(score)[len(score) // 2] * 1e3)
