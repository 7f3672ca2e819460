"""From a profiler trace to what the per-layer readers read.

The trace is of the device alone (the host tracer, at the level that
records annotations, also records every chunk of XLA's host-side
relayout: 100 MB and more a slice, and calls three times slower). Its
clock starts within 50 us of the instant before ``start_trace`` was called
(my chip run, PR 25), so the harness brings its own host spans (a call
from due to done, a fit from start to end) onto that clock.

``read_xplane`` takes the device planes' programs and operations out of
an ``.xplane.pb``; ``reduce`` is plain arithmetic on those events and the
spans, and is what ``tests/test_trace.py`` checks against
``trace/sample_trace.json``. Names and patterns of the trace's planes,
lines and events live in ``trace/events.json``."""

from __future__ import annotations

import bisect
import glob
import os
import re


def read_xplane(path: str, spec: dict) -> list:
    """``[[plane, line, name, start_ns, duration_ns], ...]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device = re.compile(spec["device_plane"])
    lines = {spec["programs_line"], spec["ops_line"], *spec["busy_lines"]}
    events = []
    for plane in data.planes:
        if not device.search(plane.name):
            continue
        for line in plane.lines:
            if line.name in lines:
                events.extend(
                    [plane.name, line.name, e.name, float(e.start_ns),
                     float(e.duration_ns)] for e in line.events)
    return events


def union_seconds(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` that ``intervals`` leave."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def reduce(events: list, spec: dict, lo: float, hi: float,
           spans: dict) -> dict:
    """``lo``, ``hi``: the traced slice, and ``spans``: ``{kind: [(start,
    end), ...]}`` of the host's calls, all in ns on the trace's clock.
    Programs that lie wholly inside the slice are kept; busy time is the
    union of the operations' intervals, clipped to the slice."""
    device = re.compile(spec["device_plane"])
    program_name = re.compile(spec["program_name"])
    op_name = re.compile(spec["op_name"])
    planes = sorted({e[0] for e in events if device.search(e[0])})
    if not planes:
        raise ValueError("the trace holds no device plane")
    programs, ops = [], []
    busy = {p: [] for p in planes}
    for plane, line, name, start, dur in events:
        if plane not in busy or start + dur <= lo or start >= hi:
            continue
        if line in spec["busy_lines"]:
            busy[plane].append((max(start, lo), min(start + dur, hi)))
        if lo <= start and start + dur <= hi:
            if line == spec["programs_line"]:
                m = program_name.search(name)
                programs.append({"plane": plane, "start": start, "dur": dur,
                                 "name": m.group("name") if m else name})
            elif line == spec["ops_line"]:
                ops.append({"plane": plane, "start": start, "dur": dur,
                            "name": name})
    if not any(busy.values()):
        raise ValueError("no operation ran on the device in the trace")
    # each operation belongs to the program whose interval holds its start
    # (programs of one device do not overlap)
    programs.sort(key=lambda p: (p["plane"], p["start"]))
    keys = [(p["plane"], p["start"]) for p in programs]
    for p in programs:
        p["ops"] = []
    for op in ops:
        i = bisect.bisect_right(keys, (op["plane"], op["start"])) - 1
        if i >= 0:
            p = programs[i]
            if (p["plane"] == op["plane"]
                    and op["start"] <= p["start"] + p["dur"]):
                p["ops"].append(op)
    busy_s = sum(union_seconds(v) for v in busy.values()) / len(planes)
    window_s = (hi - lo) / 1e9

    # what ran longest, by program and operation
    by_op: dict = {}
    for p in programs:
        for op in p["ops"]:
            m = op_name.search(op["name"])
            short = (f"{m.group('name')} {m.group('kind')}" if m
                     else op["name"][:60])
            key = f"{p['name']}/{short}"
            by_op[key] = by_op.get(key, 0.0) + op["dur"] / 1e9
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    # idle time of the first device, by what the host was inside of
    by_host: dict = {}
    for a, b in gaps(busy[planes[0]], lo, hi):
        mid = (a + b) / 2
        inside = [k for k in sorted(spans)
                  if any(s <= mid <= t for s, t in spans[k])]
        key = "inside " + "+".join(inside) if inside else "between calls"
        by_host[key] = by_host.get(key, 0.0) + (b - a) / 1e9
    idle_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": busy_s, "programs": programs,
            "spans": {k: [(a, b) for a, b in v if lo <= a and b <= hi]
                      for k, v in spans.items()},
            "breakdown": {"device_ops": [[k, v] for k, v in device_ops],
                          "idle_gaps": [[k, v] for k, v in idle_gaps]}}


def reduce_dir(trace_dir: str, spec: dict, lo: float, hi: float,
               spans: dict) -> dict:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {len(found)}")
    return reduce(read_xplane(found[0], spec), spec, lo, hi, spans)


def program_seconds(trace: dict, program: str) -> list:
    """Device durations of every execution of the programs matching."""
    pat = re.compile(program)
    return [p["dur"] / 1e9 for p in trace["programs"] if pat.search(p["name"])]


def op_seconds_per_execution(trace: dict, program: str, op: str) -> list:
    """For each execution of a matching program, the summed device time of
    its operations that match ``op``."""
    pat, op_pat = re.compile(program), re.compile(op)
    return [sum(o["dur"] for o in p["ops"] if op_pat.search(o["name"])) / 1e9
            for p in trace["programs"] if pat.search(p["name"])]
