"""The phase clock of the accelerator tier's host path: an in-memory log
of where each ``score`` and ``fit`` call spent its time, written on the
path that serves, always on.

A call is one ``Call``: stamped at entry, then ``mark(name)`` at each
boundary (one stamp ends phase ``name`` and begins the next, so the
phases tile the call) and ``count(name, n)`` for what it moved, then
``close()``, which appends a frozen copy to the process-wide log (the
newest ``LOG_CAPACITY`` calls). Times are ``time.monotonic()``. The log
outlives the scorer, so a reader that no longer holds one picks calls by
when they began.

Counts, each on the call that made it: ``score.calls``, ``put.bytes``
(handed to ``device_put``: the whole padded bucket), ``slot.waits`` (a
dispatch that found no free slot), ``readback.bytes``; for a model with
state per flow also what the host's table did for the call
(``flow.events``, ``flow.restarts``: chunks that began a flow,
``flow.evictions``, ``flow.wraps``: flows restarted for want of
positions, ``flow.resident`` after the call) and what the device step
reported (``cache.positions``: the sum of the flows' lengths after the
call, ``moe.local_pairs``: token-expert pairs computed here,
``moe.max_expert_tokens``: the fullest held expert of the call, over its
layers, ``moe.tiles``: the tiles of ``expert_tile`` rows of one expert that
held a pair, ``moe.weight_loads``: whole-expert equivalents of weights the
expert layers' grouped product brought to the chip, by the schedule it
ran: one for every held expert with a pair on the fused kernel where an
expert is one block (``device_state()["flow"]["expert_product"]`` says
which form runs), one a tile on XLA's loop, ``attn.kv_blocks``: the blocks of cache positions attention ran
over, one for every tile of query rows that ran over it, summed over
flows and layers, ``attn.kv_blocks_whole``: what the slots whole would
have been; XLA's attention runs over every slot whole as one block, so
there the two are equal; ``cache.rows_written``: the rows of the cache
the step wrote, a window of ``T + 1`` positions a live flow a layer
where XLA appends in place, the whole tiles of 128 positions the
append's kernel writes back where it does, ``cache.rows_whole``: the
live flows' slots whole, which a step that gathers the slots and
scatters them back writes; ``append.flows``: the flows appended to, a
live flow an attention layer, ``append.flows_in_kernel``: of those, the
ones the append's kernel took); ``fit.calls``,
``fit.shipped_bytes`` (the padded host arrays a fit places on the
device: rows, labels, mask and, where rows were padded, the row mask;
once a fit, whatever ``fit_steps``).

Score call (``RingDispatcher.dispatch``), in order: SLOT_WAIT, FLOW_MAP
(only where the model keeps state per flow: stream key to slot, the
call's layout), STAGE, PUT, LAUNCH on the event loop; QUEUE_WAIT, DEVICE_WAIT, READBACK on the
drainer; HOP back onto the loop, up to the awaiting coroutine having its
result. Fit call (``InProcessScorer.fit``): PREP (pad and cast), then
UPDATE_NORM (the batch placed on the device, the statistics program
launched, the scorer's statistics repointed: calls that return before a
byte moves) on the loop, before the coroutine first yields; THREAD_HOP,
STEP x ``fit_steps`` (each train-step call, on the resident batch),
LOSS_WAIT (the one wait: transfer, statistics and steps) on the worker
thread; RETURN_HOP back onto the loop.

Beside the calls, the compiled programs' scopes: a step that wants its
device time read by part registers each variant it compiles (``program``:
a name and a thunk that gives the optimised program's text), and a reader
after the window gets, per variant, which device scope (``op_name``) each
instruction of the program was made under (``program_scopes``): the thunk
runs then, once, and never on the path that serves.
"""

from __future__ import annotations

import collections
import copy
import re
import threading
import time
from typing import Callable, Dict, Iterator, List, Sequence, Tuple

LOG_CAPACITY = 4096

SCORE, FIT = "score", "fit"
SLOT_WAIT, STAGE = "dispatch.slot_wait", "dispatch.stage"
FLOW_MAP = "flow.map"
PUT, LAUNCH = "dispatch.put", "dispatch.launch"
QUEUE_WAIT, DEVICE_WAIT = "drain.queue_wait", "drain.device_wait"
READBACK, HOP = "drain.readback", "drain.hop"
UPDATE_NORM, PREP = "fit.update_norm", "fit.prep"
THREAD_HOP, STEP = "fit.thread_hop", "fit.step"
LOSS_WAIT, RETURN_HOP = "fit.loss_wait", "fit.return_hop"
SCORE_PHASES = (SLOT_WAIT, STAGE, PUT, LAUNCH, QUEUE_WAIT, DEVICE_WAIT,
                READBACK, HOP)

_log: "collections.deque[Call]" = collections.deque(maxlen=LOG_CAPACITY)
_lock = threading.Lock()


class Call:
    """One ``score`` or ``fit`` call. One thread writes it at a time: the
    hand-overs between loop, drainer and worker order the writes."""

    __slots__ = ("kind", "t0", "marks", "counts")

    def __init__(self, kind: str):
        self.kind = kind
        self.marks: Sequence[Tuple[str, float]] = []
        self.counts: Dict[str, int] = {}
        self.t0 = time.monotonic()

    def mark(self, name: str) -> None:
        self.marks.append((name, time.monotonic()))

    def count(self, name: str, n: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + n

    def close(self) -> "Call":
        """Log the call as it stands and return what was logged: a copy
        no one writes. A drainer or worker whose awaiter was cancelled
        goes on stamping this one, which no one reads any more."""
        done = copy.copy(self)
        done.marks, done.counts = tuple(self.marks), dict(self.counts)
        with _lock:
            _log.append(done)
        return done

    def spans(self) -> Iterator[Tuple[str, float, float]]:
        """``(name, start, end)`` of the phases, in the order stamped: the
        first begins at ``t0``, each at the end of the one before."""
        start = self.t0
        for name, t in self.marks:
            yield (name, start, t)
            start = t

    def ms(self, *names: str) -> float:
        """Milliseconds this call spent in the named phases."""
        return 1e3 * sum(end - start for name, start, end in self.spans()
                         if name in names)


def records() -> List[Call]:
    """The log as it stands, oldest first."""
    with _lock:
        return list(_log)


# -- the compiled programs' scopes --------------------------------------------

PROGRAM_VARIANTS = 16       # kept a name, the newest

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# a loop's computations, whose instructions run on the device as the
# entry's do (a fusion's or a reduction's are inside one instruction)
_LOOP = re.compile(r"\b(?:condition|body)=%?([\w.\-]+)")


class _Program:
    """One registered variant: its thunk until first read, then its map."""

    __slots__ = ("describe", "scopes")

    def __init__(self, describe: Callable[[], str]):
        self.describe = describe
        self.scopes: Dict[str, str] = {}


_programs: Dict[str, "collections.deque[_Program]"] = {}


def program(name: str, describe: Callable[[], str]) -> None:
    """Register a variant of the compiled program ``name`` (the name a
    device trace gives it, ``jit_flow_step``). ``describe()`` returns the
    optimised program's text (``compile().as_text()``) and must hold no
    array: it is kept after the program's owner is gone, and runs only
    when the scopes are first read."""
    with _lock:
        _programs.setdefault(name, collections.deque(
            maxlen=PROGRAM_VARIANTS)).append(_Program(describe))


def program_scopes(name: str) -> List[Dict[str, str]]:
    """Per registered variant of ``name``, oldest first: ``{instruction:
    scope path}`` over the instructions that run as operations of the
    program (``instruction_scopes``)."""
    with _lock:
        variants = list(_programs.get(name, ()))
    for v in variants:
        if v.describe is not None:
            v.scopes, v.describe = instruction_scopes(v.describe()), None
    return [v.scopes for v in variants]


def instruction_scopes(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of an optimised HLO module's text: the
    instructions of its entry computation and of every loop's body and
    condition among them, each by its name as a device trace gives it
    (without ``%``). An instruction that carries no scope takes its
    loop's ("" in the entry)."""
    bodies: Dict[str, List[Tuple[str, str]]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if line.endswith("{") and not line[:1].isspace():
            current = _HEADER.match(line).group(1)
            bodies[current] = []
            if line.startswith("ENTRY"):
                entry = current
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if m:
            bodies[current].append(m.groups())
        elif line.startswith("}"):
            current = None
    scopes: Dict[str, str] = {}
    todo = [(entry, "")] if entry is not None else []
    seen = set()
    while todo:
        computation, outer = todo.pop()
        if computation in seen or computation not in bodies:
            continue
        seen.add(computation)
        for name, rest in bodies[computation]:
            m = _OP_NAME.search(rest)
            scopes[name] = m.group(1) if m else outer
            todo.extend((c, scopes[name]) for c in _LOOP.findall(rest))
    return scopes
