"""The harness: finds by name what ``BENCHMARK.json`` lists, sets a cell
up, drives its window, reads the metrics and decides ``correct``.

Nothing here knows a configuration, a traffic mix, a metric or an entry:
each is a file of its own under this directory, found by the name the
manifest (or the file that names it) gives:

- ``configs/<config>.json``: the sizes as run, and which ``entry``,
  ``reference``, ``counts`` and ``check`` serve it;
- ``traffic/<traffic>.json``: a mix's parameters, and which ``generator``
  and ``driver`` read them; the rows of a call and the calls in flight are
  the configuration's own settings, named there by key;
- ``workloads/<cell>.json``: what belongs to the pair: the limits of the
  comparison, the traced slice;
- ``metrics/<metric>.json``: which ``reader`` takes the metric, with the
  reader's parameters (event-name patterns, percentiles);
- ``entries/``, ``traffic/``, ``drivers/``, ``readers/``, ``reference/``,
  ``counts/``, ``checks/``: the code those names point to.
"""

from __future__ import annotations

import asyncio
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Refused(Exception):
    """The run cannot be made as asked (no chip, an unknown name)."""


def process_start_monotonic() -> float:
    """``time.monotonic()`` at which this process began, from /proc."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    age = (time.clock_gettime(time.CLOCK_BOOTTIME)
           - ticks / os.sysconf("SC_CLK_TCK"))
    return time.monotonic() - age


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise Refused(f"no such file: chipbench/{'/'.join(parts)}")
    with open(path) as f:
        return json.load(f)


def load_code(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of this directory (of a copy of it,
    where a test has pointed ``HERE`` at one)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no such file: chipbench/{kind}/{name}.py")
    qualified = f"chipbench.{kind}.{name}"
    if os.path.dirname(os.path.dirname(path)) == os.path.dirname(
            os.path.abspath(__file__)):
        return importlib.import_module(qualified)
    spec = importlib.util.spec_from_file_location(qualified + "@copy", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def bucket(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def shape_name(n: int) -> str:
    """The program's name for a fit of ``n`` rows (padded ones are masked)."""
    return str(n) if bucket(n) == n else f"{bucket(n)}+mask"


def look_for_chip(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but a TPU of a kind
    the peaks table knows, in the number the cell asks for."""
    import jax
    peaks = load_json("trace", "peaks.json")
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Refused(f"no accelerator: JAX's platform is {d.platform!r}")
    if d.device_kind not in peaks["device_kind"]:
        raise Refused(f"device kind {d.device_kind!r} is not in "
                      "chipbench/trace/peaks.json")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devices)}")
    return peaks["device_kind"][d.device_kind]


def memory_now(chips: int) -> list:
    """Each chip's allocator counters at this instant ({} where the
    platform keeps none)."""
    import jax
    return [d.memory_stats() or {} for d in jax.devices()[:chips]]


def device_block(chips: int, at_start: list) -> dict:
    """The device as JAX reports it, read once the window has closed.

    ``memory_peak_bytes`` is what the fullest chip held at one instant of
    the window: the allocator's ``peak_bytes_in_use`` (the buffers: rows,
    labels, scores, parameters) and, beside them, the bytes the runtime
    keeps set aside for the loaded programs' temporaries
    (``bytes_reserved``). That reservation is made when a program first
    runs, in set-up, and stands: it is counted only as far as it stood both
    when the window opened and when it closed (the lesser of the two
    readings), so the sum is of two things held together, not of two peaks.
    Both parts are given apart."""
    import jax
    devices = jax.devices()
    fullest = (0, 0, 0, 0)
    for before, stats in zip(at_start, memory_now(chips)):
        in_use = int(stats.get("peak_bytes_in_use", 0))
        opened = int(before.get("bytes_reserved", 0))
        closed = int(stats.get("bytes_reserved", 0))
        fullest = max(fullest, (in_use + min(opened, closed), in_use,
                                opened, closed))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": fullest[0],
            "peak_bytes_in_use": fullest[1],
            "bytes_reserved_at_open": fullest[2],
            "bytes_reserved_at_close": fullest[3]}


def cell_metrics(manifest: dict, cell: str, section: str) -> list:
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


async def _trace_slice(spec: dict, out_dir: str, marks: dict) -> None:
    import jax
    await asyncio.sleep(spec["start_s"])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0      # the device alone: see trace/reduce.py
    marks["clock0"] = time.monotonic()   # the trace's clock starts here
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    marks["lo"] = time.monotonic()
    try:
        await asyncio.sleep(spec["seconds"])
    finally:
        marks["hi"] = time.monotonic()
        jax.profiler.stop_trace()


async def _drive(run: dict, entry, scorer, driver, seconds, trace_dir,
                 keep) -> None:
    import jax
    pool, mix = run["pool"], run["mix"]
    outstanding, fit_every = run["outstanding"], run["fit_every"]
    x, labels, mask = run["setup_rows"]
    per = mix["setup_fit_rows_per_call"]
    run["setup_fits"] = []
    for a in range(0, len(x), per):
        rows = (x[a:a + per], labels[a:a + per], mask[a:a + per])
        run["setup_fits"].append(
            {"rows": rows, "loss": await entry.fit(scorer, *rows)})
    # where a fit's rows differ in number from the set-up fit's, its shape
    # is warmed by fits of the pool's first batches: set-up fits as well,
    # which the reference follows
    for rows in pool[:mix.get("warm_fits_on_pool", 0) if fit_every else 0]:
        run["setup_fits"].append(
            {"rows": rows, "loss": await entry.fit(scorer, *rows)})
    for r in range(mix["warm_rounds"]):
        await asyncio.gather(*(
            entry.score(scorer, pool[(r * outstanding + i) % len(pool)][0])
            for i in range(outstanding)))
    run["snap_setup"] = entry.snapshot(scorer)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append(event))
    marks: dict = {}
    tracer = None
    run["memory_at_start"] = memory_now(run["chips"])
    if trace_dir is not None:
        tracer = asyncio.ensure_future(
            _trace_slice(run["cell"]["trace"], trace_dir, marks))

    def on_start(t0: float) -> None:
        # the runtime's own start-up (7.3-10.7 s from run to run on one
        # machine, my chip runs, PR 25) is neither the program's work nor
        # the benchmark's, and is left out: PERF.md section 2
        run["setup_s"] = t0 - run["t_process"] - run["chip_init_s"]

    try:
        run["window"] = await driver.run(
            entry, scorer, pool, seconds=seconds, outstanding=outstanding,
            fit_every=fit_every, keep=keep,
            follow_fits=run["cell"]["check"]["follow_fits"],
            anchor=run["anchor"], on_start=on_start)
    finally:
        if tracer is not None:
            if "lo" not in marks:       # the window closed before the slice
                tracer.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await tracer
    run["window_compiles"] = sum(e == COMPILE_EVENT for e in compiles)
    run["trace_marks"] = marks


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             entry_name: str | None = None, on_chip: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict. A test
    passes ``on_chip=False`` to skip the look for a chip (and the placing
    of the compile cache, which only a fresh process can do) and drive the
    rest of the run on whatever JAX has."""
    t_process = process_start_monotonic()
    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no cell {workload!r} in BENCHMARK.json")
    spec = cells[workload]
    config = load_json("configs", spec["config"] + ".json")
    mix = load_json("traffic", spec["traffic"] + ".json")
    cell = load_json("workloads", workload + ".json")
    entry = load_code("entries", entry_name or config["entry"])
    if on_chip:
        entry.place_cache()

    # the rows are made on the host, by NumPy alone, while JAX starts and
    # finds the chip: neither waits for the other
    rows_per_call = int(mix["rows_per_call"] if "rows_per_call" in mix
                        else config["telemeter"][mix["rows_per_call_key"]])
    generator = load_code("traffic", mix["generator"])
    driver = load_code("drivers", mix["driver"])
    made: dict = {}

    def make_rows() -> None:
        try:
            made["rows"] = generator.generate(
                mix, rows_per_call, config["model"]["in_dim"], seed)
        except BaseException as e:  # noqa: BLE001 - raised again below, in the main thread
            made["error"] = e

    maker = threading.Thread(target=make_rows, daemon=True)
    maker.start()

    import jax
    import numpy as np
    phases = {"imported": time.monotonic() - t_process}
    jax.devices()           # the runtime starts up and finds the chip here
    phases["chip_found"] = time.monotonic() - t_process
    peaks = look_for_chip(spec["chips"]) if on_chip else None
    platform = jax.devices()[0].platform
    tel = config["telemeter"]
    fit_every = int(tel["trainEveryBatches"])
    outstanding = mix["routers"] * int(tel[mix["outstanding_per_router_key"]])
    # which calls' whole outputs the comparison gets, where they met a
    # state it knows, and which fits lie between two snapshots: both drawn
    # from the seed, by call and by fit number
    keep = (np.random.default_rng([seed, 1]).random(cell["check"]["draw_from"])
            < cell["check"]["sample_share"])
    keep[0] = True      # the first call always: it met the set-up's state
    # one fit in every ``anchor_every``, from an offset past the followed
    # horizon: however few fits a window makes, their number is not chance
    every = int(cell["check"]["anchor_every"])
    anchor = np.zeros(cell["check"]["draw_from"] if every else 0, bool)
    if every:
        first = cell["check"]["follow_fits"]
        anchor[int(np.random.default_rng([seed, 2]).integers(
            first, max(every, first + 1)))::every] = True
    scorer = entry.build(config, seed)
    snap_init = entry.snapshot(scorer)
    phases["scorer_built"] = time.monotonic() - t_process
    maker.join()
    phases["rows_made"] = time.monotonic() - t_process
    if "error" in made:
        entry.close(scorer)
        raise made["error"]
    batches = made["rows"]
    run = {"t_process": t_process, "seed": seed, "config": config,
           "cell": cell, "mix": mix, "pool": batches["pool"],
           "setup_rows": batches["setup"], "peaks": peaks,
           "platform": platform, "snap_init": snap_init,
           "rows_per_call": rows_per_call, "outstanding": outstanding,
           "fit_every": fit_every, "anchor": anchor, "chips": spec["chips"],
           "chip_init_s": phases["chip_found"] - phases["imported"]}
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        asyncio.run(_drive(run, entry, scorer, driver, seconds, trace_dir,
                           keep))
        device = device_block(spec["chips"], run["memory_at_start"])
        run["entry_state"] = entry.state(scorer)
    finally:
        entry.close(scorer)
    del scorer
    run["device"] = device

    # -- metrics ------------------------------------------------------------
    run["trace"] = None
    breakdown = None
    if trace:
        try:
            marks = run["trace_marks"]
            if "hi" not in marks:
                raise Refused("the window closed before the traced slice")

            def ns(t: float) -> float:
                return (t - marks["clock0"]) * 1e9
            w = run["window"]
            spans = {"score_call": [(ns(c["due"]), ns(c["done"]))
                                    for c in w["calls"]],
                     "fit_call": [(ns(f["start"]), ns(f["end"]))
                                  for f in w["fits"]]}
            run["trace"] = load_code("trace", "reduce").reduce_dir(
                trace_dir, load_json("trace", "events.json"),
                ns(marks["lo"]), ns(marks["hi"]), spans)
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            breakdown = run["trace"]["breakdown"]
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run["counts"] = load_code("counts", config["counts"])
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, workload, section):
        how = load_json("metrics", m["name"] + ".json")
        value = load_code("readers", how["reader"]).read(run, how)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- correct ------------------------------------------------------------
    run["reference"] = load_code("reference", config["reference"])
    run["expected_shapes"] = {
        "score": [str(bucket(rows_per_call))],
        "fit": [shape_name(mix["setup_fit_rows_per_call"])]
        + ([shape_name(rows_per_call)] if fit_every else [])}
    run["expected_score_path"] = getattr(
        entry, "EXPECT_SCORE_PATH", {}).get(platform)
    t_check = time.monotonic()
    verdict = load_code("checks", config["check"]).compare(run)
    verdict["info"]["check_s"] = time.monotonic() - t_check
    compared = {name: {"value": value, "limit": cell["limits"][name]}
                for name, value in verdict["numbers"].items()
                if name in cell["limits"]}
    missing = sorted(set(cell["limits"]) - set(compared))
    correct = not missing and all(
        c["value"] <= c["limit"] for c in compared.values())
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    for name in missing:
        print(f"compared {name} missing", file=sys.stderr)
    calls = run["window"]["calls"]
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": int(verdict["numbers"]["failed_calls"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    lat = sorted(c["done"] - c["due"] for c in calls)
    result["info"] = {**verdict["info"], "workload": workload, "seed": seed,
                      "latency_ms": {q: lat[min(len(lat) - 1,
                                                int(len(lat) * q / 100))] * 1e3
                                     for q in (50, 90, 95, 100)},
                      "fits": len(run["window"]["fits"]),
                      "entry": entry_name or config["entry"],
                      "outstanding": run["outstanding"],
                      "setup_phases_s": phases,
                      "state": run["entry_state"],
                      "not_compared": {k: v for k, v in
                                       verdict["numbers"].items()
                                       if k not in compared}}
    result["compared"] = compared
    return result
