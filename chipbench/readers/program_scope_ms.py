"""Device time of a program's parts, by the scopes the program itself names.

The program registers each variant of a compiled program it runs, and
gives after the window, per variant, the scope path (``op_name``) that each
of its instructions was made under (``linkerd_tpu/telemetry/phases.py``:
``program``, ``program_scopes``). For each execution of a program that
matches ``program`` and lies wholly inside the traced slice, each
operation's **self time** is its duration less the union of the
operations of the same execution that nest inside it (a loop is not
counted again with its body). An operation is named by the instruction
that ``trace/events.json``'s ``op_name`` pattern takes out of its event,
and put down to the innermost component of its scope path that ``scopes``
lists: a component matches a name if it is that name or ends in ``.`` and
that name (``layer1.conv`` matches ``conv``).

``stat``: ``ms``, the self time of the operations put down to ``scopes``,
summed per execution; ``outside_pct``, 100 x (the execution's duration less
the self time of every operation put down to ``scopes``) over the
duration: what no part holds, gaps inside the program included. The median
over the executions either way.

An execution is read by the variant whose instructions name all of its
operations. Where no variant does (or two that do give an operation two
scope paths), or the program registers no scopes, the reader reads
nothing."""

import re
import statistics

from chipbench.harness import load_json
from chipbench.trace.reduce import union_seconds


def self_times(ops: list) -> list:
    """Each operation's duration less the union of the operations that
    nest inside it, in the order given (the operations of one execution,
    on one plane)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i]["start"], -ops[i]["dur"]))
    inner = [[] for _ in ops]   # the intervals nested directly inside each
    stack = []
    for i in order:
        a = ops[i]["start"]
        b = a + ops[i]["dur"]
        while stack and ops[stack[-1]]["start"] + ops[stack[-1]]["dur"] < b:
            stack.pop()
        if stack:
            inner[stack[-1]].append((a, b))
        stack.append(i)
    return [op["dur"] - union_seconds(kids) * 1e9
            for op, kids in zip(ops, inner)]


def _scope_maps(name: str) -> list:
    try:
        from linkerd_tpu.telemetry import phases
    except ImportError:
        return []
    read = getattr(phases, "program_scopes", None)
    return read(name) if read is not None else []


def executions(run: dict, program: str):
    """``[(duration ns, {scope path: self time ns})]`` a matching
    execution, or None where one cannot be read; kept on ``run``, as the
    metrics of one program read the same executions."""
    kept = run.setdefault("program_scope_executions", {})
    if program in kept:
        return kept[program]
    pattern = re.compile(program)
    op_name = re.compile(load_json("trace", "events.json")["op_name"])
    maps, out = {}, []
    for p in run["trace"]["programs"]:
        if not pattern.search(p["name"]):
            continue
        if p["name"] not in maps:
            maps[p["name"]] = _scope_maps(p["name"])
        names = []
        for op in p["ops"]:
            m = op_name.search(op["name"])
            names.append(m.group("name").lstrip("%") if m else None)
        covering = [s for s in maps[p["name"]]
                    if all(n is not None and n in s for n in names)]
        paths = {n: {s[n] for s in covering} for n in set(names)}
        if not covering or any(len(v) != 1 for v in paths.values()):
            out = None
            break
        by_path: dict = {}
        for n, t in zip(names, self_times(p["ops"])):
            path = next(iter(paths[n]))
            by_path[path] = by_path.get(path, 0.0) + t
        out.append((p["dur"], by_path))
    kept[program] = out or None
    return kept[program]


def part(path: str, scopes: list):
    """The innermost component of ``path`` that names one of ``scopes``
    (that scope), or None."""
    for component in reversed(path.split("/")):
        for s in scopes:
            if component == s or component.endswith("." + s):
                return s
    return None


def read(run: dict, how: dict):
    if run["trace"] is None:
        return None
    runs = executions(run, how["program"])
    if not runs:
        return None
    scopes = how["scopes"]
    values = []
    for dur, by_path in runs:
        mine = sum(t for path, t in by_path.items()
                   if part(path, scopes) is not None)
        values.append(100.0 * (dur - mine) / dur
                      if how["stat"] == "outside_pct" else mine / 1e6)
    return statistics.median(values)
