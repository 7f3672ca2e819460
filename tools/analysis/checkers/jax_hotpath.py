"""jax-hotpath — per-call device seams on the score dispatch path.

The line-rate scoring contract (COMPONENTS.md §2.11): the score
dispatch path pays ONE host memcpy into a persistent staging buffer and
rides JAX async dispatch; readback happens on the single drainer
thread. Three call shapes silently reintroduce the old per-call seam
and its latency (39.95 ms ``score_batch_p50_ms`` vs the ≤5 ms bar in a
pre-round record taken through a shared remote chip; on a local chip:
not measured):

- ``jax.device_put`` — a fresh per-call host→device transfer instead of
  the staging ring;
- ``asyncio.to_thread`` / ``run_in_executor`` — a thread hop per call
  (dispatch must not serialize through the executor);
- ``np.asarray`` / ``jax.block_until_ready`` — host readback or a
  device barrier on the dispatch path (readback belongs on the drainer
  thread).

The rule flags these calls inside functions REACHABLE from the score
dispatch roots (``score``, ``dispatch*``, ``drain_once``,
``_score_and_publish``) through same-module call edges, including
nested defs/lambdas (closures handed to the dispatcher execute on the
path). Deliberate uses — the staging-buffer placement inside the
dispatcher's put closure, host-side dtype casts that are not readbacks
— carry the usual justified
``# l5d: ignore[jax-hotpath] — why``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, Optional, Set, Tuple

from tools.analysis.core import (
    Checker, Finding, Project, SourceFile, dotted_name, register_checker,
    walk_functions,
)

# dispatch-path entry points: a function with one of these names (or a
# name starting with "dispatch") anchors reachability.
# _publish_native_batch is the in-data-plane tier's board publish (runs
# per drained batch — a device seam there would put the per-batch
# latency right back on the native path); export_weight_blob is the
# promote-time weight export, which must stay host-side numpy on an
# already-gathered snapshot (it runs next to the serving loop).
ROOT_NAMES = {"score", "drain_once", "_score_and_publish",
              "_publish_native_batch", "export_weight_blob",
              "export_bank_blob", "export_delta_blob"}

FLAGGED_CALLS = {
    "jax.device_put": "per-call device_put on the score dispatch path; "
                      "batches belong in the persistent staging ring "
                      "(telemetry/linerate.RingDispatcher)",
    "asyncio.to_thread": "thread hop on the score dispatch path; "
                         "dispatch rides JAX async dispatch and the "
                         "drainer thread does readback",
    "jax.block_until_ready": "device barrier on the score dispatch "
                             "path; only the drainer thread may block "
                             "on device completion",
    "np.asarray": "host-side asarray on the score dispatch path: a "
                  "readback blocks on device completion (readback "
                  "belongs on the drainer thread)",
    "numpy.asarray": "host-side asarray on the score dispatch path: a "
                     "readback blocks on device completion (readback "
                     "belongs on the drainer thread)",
}
FLAGGED_ATTRS = {
    "run_in_executor": "executor hop on the score dispatch path; "
                       "dispatch rides JAX async dispatch and the "
                       "drainer thread does readback",
}


def _is_root(name: str) -> bool:
    return name in ROOT_NAMES or name.startswith("dispatch")


def _flag_reason(call: ast.Call) -> Optional[Tuple[str, str]]:
    name = dotted_name(call.func)
    if name is not None and name in FLAGGED_CALLS:
        return name, FLAGGED_CALLS[name]
    if isinstance(call.func, ast.Attribute) \
            and call.func.attr in FLAGGED_ATTRS:
        return call.func.attr, FLAGGED_ATTRS[call.func.attr]
    return None


def _local_callee(call: ast.Call) -> Optional[Tuple[Optional[str], str]]:
    f = call.func
    if isinstance(f, ast.Name):
        return (None, f.id)
    if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id == "self"):
        return ("self", f.attr)
    return None


@register_checker
class JaxHotpathChecker(Checker):
    rule = "jax-hotpath"
    description = ("per-call device_put / to_thread / host asarray "
                   "readback reachable from the score dispatch path")
    scope = ("linkerd_tpu/telemetry", "linkerd_tpu/parallel",
             "linkerd_tpu/ops", "linkerd_tpu/lifecycle")

    def check(self, src: SourceFile, project: Project) -> Iterator[Finding]:
        funcs = [(fn, cls) for fn, cls in walk_functions(src.tree)
                 if not isinstance(fn, ast.Lambda)]
        by_key: Dict[Tuple[Optional[str], str], ast.AST] = {}
        for fn, cls in funcs:
            by_key.setdefault((cls, fn.name), fn)
        # reachability from the dispatch roots over same-module call
        # edges; a root's whole lexical subtree (nested defs, lambdas)
        # executes on the path, so edges come from ast.walk, not just
        # the top frame
        reachable: Set[Tuple[Optional[str], str]] = {
            key for key in by_key if _is_root(key[1])}
        frontier = list(reachable)
        while frontier:
            key = frontier.pop()
            fn = by_key.get(key)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                local = _local_callee(node)
                if local is None:
                    continue
                hint, name = local
                for cand in ((key[0] if hint == "self" else None, name),
                             (None, name)):
                    if cand in by_key and cand not in reachable:
                        reachable.add(cand)
                        frontier.append(cand)
        # report flagged calls anywhere in a reachable function's
        # subtree — dedup'd, since a nested def is both part of its
        # parent's subtree and possibly reachable itself
        seen: Set[Tuple[int, int]] = set()
        out = []
        for key in reachable:
            fn = by_key.get(key)
            if fn is None:
                continue
            # don't re-scan nested reachable defs under this one twice
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                hit = _flag_reason(node)
                if hit is None:
                    continue
                where = (node.lineno, node.col_offset)
                if where in seen:
                    continue
                seen.add(where)
                callee, reason = hit
                out.append(Finding(
                    self.rule, src.rel, node.lineno, node.col_offset,
                    f"{callee}() in '{key[1]}', reachable from the "
                    f"score dispatch path: {reason}"))
        out.sort(key=lambda f: (f.line, f.col))
        yield from out
