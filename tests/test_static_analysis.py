"""l5dlint self-tests: every rule fires on a positive fixture, stays
quiet on the matching negative, suppressions require justification, and
the real tree is clean (the tier-1 gate).

Fixtures are tiny synthetic repos written under tmp_path with the same
layout the scope filters expect (``linkerd_tpu/router/...`` etc.), so
the checkers run exactly as they do against the real tree.
"""

import os
import textwrap

import pytest

from tools.analysis import run_analysis, rule_ids

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mk_repo(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def findings_of(tmp_path, files, rule):
    root = mk_repo(tmp_path, files)
    out = run_analysis(["linkerd_tpu"], repo_root=root, rules=[rule])
    return [f for f in out if f.rule == rule]


class TestAsyncBlocking:
    def test_direct_blocking_call_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                async def handle(req):
                    time.sleep(0.1)
                    return req
            """}, "async-blocking")
        assert len(got) == 1 and "time.sleep" in got[0].message
        assert got[0].path == "linkerd_tpu/router/x.py"
        assert got[0].line == 4

    def test_reachable_through_sync_helper(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/protocol/x.py": """
                import time
                def helper():
                    time.sleep(1)
                async def handle(req):
                    helper()
            """}, "async-blocking")
        assert len(got) == 1 and "helper" in got[0].message

    def test_async_sleep_and_to_thread_are_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio, time
                async def handle(req):
                    await asyncio.sleep(0.1)
                    await asyncio.to_thread(time.sleep, 1)
            """}, "async-blocking")
        assert got == []

    def test_out_of_scope_package_is_ignored(self, tmp_path):
        # startup/control-plane code may block; the rule is data-plane
        got = findings_of(tmp_path, {
            "linkerd_tpu/namerd/x.py": """
                import time
                async def boot():
                    time.sleep(1)
            """}, "async-blocking")
        assert got == []

    def test_blocking_call_in_lambda_inside_async_def_fires(self, tmp_path):
        # regression: lambda bodies are frames body_calls skips, so a
        # blocking call hidden in one passed silently
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio, time
                async def handle(req, loop):
                    loop.call_soon(lambda: time.sleep(1))
                    return req
            """}, "async-blocking")
        assert len(got) == 1 and "lambda" in got[0].message

    def test_offloaded_lambda_is_clean(self, tmp_path):
        # to_thread/run_in_executor run the lambda in a worker thread —
        # blocking there is the sanctioned escape hatch
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio, time
                async def handle(req, loop):
                    await asyncio.to_thread(lambda: time.sleep(1))
                    await loop.run_in_executor(None, lambda: time.sleep(1))
                    return req
            """}, "async-blocking")
        assert got == []

    def test_lambda_in_nested_async_def_reported_once(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio, time
                async def outer(loop):
                    async def inner():
                        loop.call_soon(lambda: time.sleep(1))
                    await inner()
            """}, "async-blocking")
        assert len(got) == 1 and "inner" in got[0].message


class TestTaskLeak:
    def test_dropped_spawn_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio
                def go(loop, coro):
                    loop.create_task(coro)
            """}, "task-leak")
        assert len(got) == 1 and "dropped" in got[0].message

    def test_held_or_chained_spawn_is_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio
                def go(loop, coro, cb):
                    t = loop.create_task(coro)
                    loop.create_task(coro).add_done_callback(cb)
                    return t
            """}, "task-leak")
        assert got == []

    def test_spawn_inside_callback_lambda_fires(self, tmp_path):
        # regression: call_soon discards its callback's return value, so
        # a lambda-body spawn drops the Task — this passed silently
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio
                def go(loop, mk):
                    loop.call_soon(lambda: loop.create_task(mk()))
            """}, "task-leak")
        assert len(got) == 1 and "lambda" in got[0].message

    def test_spawning_lambda_used_as_factory_is_clean(self, tmp_path):
        # the lambda's return value is consumed — not a leak
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import asyncio
                def go(loop, mk):
                    factory = lambda: loop.create_task(mk())
                    t = factory()
                    return t
            """}, "task-leak")
        assert got == []


class TestSwallowedException:
    def test_broad_pass_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/grpc/x.py": """
                def f(x):
                    try:
                        return x()
                    except Exception:
                        pass
            """}, "swallowed-exception")
        assert len(got) == 1

    def test_bare_except_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                def f(x):
                    try:
                        return x()
                    except:
                        pass
            """}, "swallowed-exception")
        assert len(got) == 1 and "bare" in got[0].message

    def test_narrow_logged_or_reraised_are_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/protocol/x.py": """
                import logging
                log = logging.getLogger(__name__)
                def f(x):
                    try:
                        return x()
                    except (OSError, RuntimeError):
                        pass
                def g(x):
                    try:
                        return x()
                    except Exception as e:
                        log.debug("boom: %r", e)
                def h(x):
                    try:
                        return x()
                    except Exception:
                        raise
            """}, "swallowed-exception")
        assert got == []


class TestStreamRelease:
    def test_unreleased_frame_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/grpc/x.py": """
                async def recv(stream):
                    frame = await stream.read()
                    return bytes(frame.data)
            """}, "stream-release")
        assert len(got) == 1 and "frame" in got[0].message

    def test_dropped_read_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/protocol/h2/x.py": """
                async def drain(stream):
                    await stream.read()
            """}, "stream-release")
        assert len(got) == 1

    def test_released_or_forwarded_is_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/grpc/x.py": """
                async def recv(stream):
                    frame = await stream.read()
                    try:
                        return bytes(frame.data)
                    finally:
                        frame.release()
                async def tee(stream, out):
                    frame = await stream.read()
                    out.offer(frame)
                async def read_bytes(reader):
                    data = await reader.read(4096)  # byte read, not a frame
                    return data
            """}, "stream-release")
        assert got == []


class TestJaxPurity:
    def test_impure_jit_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/models/x.py": """
                import jax
                import numpy as np
                @jax.jit
                def used_step(x):
                    print("tracing")
                    return np.asarray(x)
            """,
            "linkerd_tpu/models/user.py": "from linkerd_tpu.models.x "
                                          "import used_step\n",
        }, "jax-purity")
        msgs = " ".join(f.message for f in got)
        assert "print" in msgs and "np.asarray" in msgs

    def test_captured_state_mutation_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/parallel/x.py": """
                import jax
                class M:
                    def mk(self):
                        @jax.jit
                        def step(x):
                            self.count = self.count + 1
                            return x
                        return step
            """}, "jax-purity")
        assert any("self.count" in f.message for f in got)

    def test_dead_helper_fires_and_wired_helper_is_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/ops/x.py": """
                def dead_helper(x):
                    return x + 1
                def live_helper(x):
                    return x * 2
            """,
            "tests/test_x.py": "from linkerd_tpu.ops.x import live_helper\n",
        }, "jax-purity")
        assert len(got) == 1 and "dead_helper" in got[0].message

    def test_pallas_kernel_via_partial_is_scanned(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/ops/x.py": """
                import functools
                from jax.experimental import pallas as pl
                def my_kernel(ref, out):
                    print("host io")
                    out[...] = ref[...]
                def run(x):
                    kernel = functools.partial(my_kernel)
                    return pl.pallas_call(kernel)(x)
            """}, "jax-purity")
        assert any("print" in f.message and "my_kernel" in f.message
                   for f in got)


class TestFloatTime:
    def test_direct_duration_subtraction_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                def measure(fn):
                    t0 = time.time()
                    fn()
                    return time.time() - t0
            """}, "float-time")
        assert len(got) == 1 and got[0].line == 6

    def test_variable_flow_flags_the_assignment(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                def deadline_of(timeout_s, now_mono):
                    wall = time.time()
                    return now_mono < wall + timeout_s
            """}, "float-time")
        assert len(got) == 1
        assert got[0].line == 4 and "assigned here" in got[0].message

    def test_deadline_comparison_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import time
                def expired(deadline):
                    return time.time() > deadline
            """}, "float-time")
        assert len(got) == 1

    def test_method_bodies_are_scanned(self, tmp_path):
        # regression: walk_functions used to skip class methods entirely
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                class Filter:
                    async def apply(self, req, service):
                        t0 = time.time()
                        rsp = await service(req)
                        self.latency = time.time() - t0
                        return rsp
            """}, "float-time")
        assert len(got) >= 1

    def test_lambda_bodies_are_scanned(self, tmp_path):
        # regression: lambdas are frames the per-frame walk skips, so a
        # wall-clock duration inside one passed silently
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                def mk_age_fn(t0):
                    return lambda: time.time() - t0
            """}, "float-time")
        assert len(got) == 1

    def test_rebound_variable_clears_wall_clock_taint(self, tmp_path):
        # t0 first holds a reported wall timestamp, then is rebound to
        # monotonic before the arithmetic — no bug, no finding
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                def span():
                    t0 = time.time()
                    stamp = int(t0 * 1e6)
                    t0 = time.monotonic()
                    return stamp, time.monotonic() - t0
            """}, "float-time")
        assert got == []

    def test_timestamps_and_unit_conversion_are_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import time
                def span_fields():
                    ts_us = int(time.time() * 1e6)  # unit conversion
                    t0 = time.monotonic()
                    return {"ts": round(time.time(), 3),  # reported stamp
                            "elapsed": time.monotonic() - t0,
                            "timestamp": ts_us}
            """}, "float-time")
        assert got == []

    def test_out_of_scope_control_plane_is_ignored(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/namerd/x.py": """
                import time
                def uptime(start):
                    return time.time() - start
            """}, "float-time")
        assert got == []


class TestConfigRegistry:
    FILES = {
        "linkerd_tpu/cfg.py": """
            from dataclasses import dataclass
            from linkerd_tpu.config import register
            @register("namer", "io.l5d.good")
            @dataclass
            class GoodConfig:
                '''A documented kind.'''
                port: int = 0
            @register("namer", "io.l5d.bad")
            class BadConfig:
                pass
        """,
        "tests/test_cfg.py": "KIND = 'io.l5d.good'\n",
        "README.md": "uses io.l5d.good\n",
    }

    def test_loose_undocumented_unexercised_fire(self, tmp_path):
        got = findings_of(tmp_path, self.FILES, "config-registry")
        bad = [f for f in got if "io.l5d.bad" in f.message]
        msgs = " ".join(f.message for f in bad)
        assert "not a @dataclass" in msgs
        assert "undocumented" in msgs
        assert "exercised by no test" in msgs

    def test_documented_exercised_dataclass_is_clean(self, tmp_path):
        got = findings_of(tmp_path, self.FILES, "config-registry")
        assert not [f for f in got if "io.l5d.good" in f.message]


class TestSuppressions:
    LEAK = """
        import asyncio
        def go(loop, coro):
            loop.create_task(coro)  {comment}
    """

    def test_justified_suppression_suppresses(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.LEAK.format(
            comment="# l5d: ignore[task-leak] — daemon owns its lifetime")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        leaks = [f for f in out if f.rule == "task-leak"]
        assert len(leaks) == 1 and leaks[0].suppressed
        assert "daemon" in leaks[0].justification
        assert not [f for f in out if f.rule == "suppression"]

    def test_suppression_requires_justification(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.LEAK.format(
            comment="# l5d: ignore[task-leak]")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        leaks = [f for f in out if f.rule == "task-leak"]
        # the bare ignore does NOT silence the finding...
        assert len(leaks) == 1 and not leaks[0].suppressed
        # ...and is itself reported
        sup = [f for f in out if f.rule == "suppression"]
        assert len(sup) == 1 and "justification" in sup[0].message

    def test_unknown_rule_in_suppression_is_reported(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.LEAK.format(
            comment="# l5d: ignore[no-such-rule] — because")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        sup = [f for f in out if f.rule == "suppression"]
        assert len(sup) == 1 and "unknown rule" in sup[0].message

    def test_trailing_suppression_binds_to_its_line_only(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": textwrap.dedent("""
            import asyncio
            def go(loop, coro):
                x = 1  # l5d: ignore[task-leak] — wrong line on purpose
                loop.create_task(coro)
        """)})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        leaks = [f for f in out if f.rule == "task-leak"]
        assert len(leaks) == 1 and not leaks[0].suppressed

    def test_comment_line_above_applies(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": textwrap.dedent("""
            import asyncio
            def go(loop, coro):
                # l5d: ignore[task-leak] — fire-and-forget by design here
                loop.create_task(coro)
        """)})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        leaks = [f for f in out if f.rule == "task-leak"]
        assert len(leaks) == 1 and leaks[0].suppressed


class TestStaleSuppressions:
    """The stale-suppression meta-rule: a justified waiver that no
    longer silences anything is itself a finding — it would hide the
    next regression on that line."""

    FIXED = """
        import asyncio
        def go(loop, coro):
            t = loop.create_task(coro)  {comment}
            return t
    """

    def test_stale_justified_waiver_is_flagged(self, tmp_path):
        # the task IS held: the waiver excuses nothing
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.FIXED.format(
            comment="# l5d: ignore[task-leak] — daemon owns its "
                    "lifetime")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        stale = [f for f in out if f.rule == "stale-suppression"]
        assert len(stale) == 1, out
        assert "no longer silences" in stale[0].message
        assert "task-leak" in stale[0].message

    def test_live_waiver_is_not_stale(self, tmp_path):
        root = mk_repo(tmp_path, {
            "linkerd_tpu/x.py": TestSuppressions.LEAK.format(
                comment="# l5d: ignore[task-leak] — daemon owns its "
                        "lifetime")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        assert not [f for f in out if f.rule == "stale-suppression"]

    def test_rule_filtered_runs_skip_the_stale_check(self, tmp_path):
        # with --rule only a subset of checkers runs, so "nothing
        # fired" is not evidence of staleness
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.FIXED.format(
            comment="# l5d: ignore[task-leak] — daemon owns its "
                    "lifetime")})
        out = run_analysis(["linkerd_tpu"], repo_root=root,
                           rules=["task-leak"])
        assert not [f for f in out if f.rule == "stale-suppression"]

    def test_unjustified_waiver_is_not_double_flagged(self, tmp_path):
        # the bare ignore is already a suppression finding; stale on
        # top would be noise
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.FIXED.format(
            comment="# l5d: ignore[task-leak]")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        assert [f for f in out if f.rule == "suppression"]
        assert not [f for f in out if f.rule == "stale-suppression"]

    def test_foreign_suite_waivers_are_left_alone(self, tmp_path):
        # a waiver naming a race/seam rule is the other analyzer's to
        # judge — l5dlint never ran those checkers
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": self.FIXED.format(
            comment="# l5d: ignore[await-atomicity] — probe is "
                    "read-only")})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        assert not [f for f in out if f.rule == "stale-suppression"]

    def test_stale_finding_is_itself_suppressible(self, tmp_path):
        root = mk_repo(tmp_path, {"linkerd_tpu/x.py": textwrap.dedent("""
            import asyncio
            def go(loop, coro):
                # l5d: ignore[stale-suppression] — kept while the refactor lands
                t = loop.create_task(coro)  # l5d: ignore[task-leak] — daemon owns it
                return t
        """)})
        out = run_analysis(["linkerd_tpu"], repo_root=root)
        stale = [f for f in out if f.rule == "stale-suppression"]
        assert len(stale) == 1 and stale[0].suppressed
        assert "refactor" in stale[0].justification


class TestMetricsScope:
    def test_slashed_name_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                def install(metrics):
                    metrics.counter("rt/out/server/requests").incr()
            """}, "metrics-scope")
        assert len(got) == 1 and "rt/out/server/requests" in got[0].message

    def test_slashed_scope_component_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                def install(metrics):
                    node = metrics.scope("namerd/http")
                    node.stat("latency_ms")
            """}, "metrics-scope")
        assert len(got) == 1 and "namerd/http" in got[0].message

    def test_component_args_and_sanitized_dynamic_are_clean(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                def install(metrics, path):
                    metrics.scope("rt", "out", "server").counter("requests")
                    metrics.gauge(path.replace("/", "."))
            """}, "metrics-scope")
        assert got == []

    def test_justified_suppression_suppresses(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                def install(metrics):
                    metrics.counter("a/b")  # l5d: ignore[metrics-scope] — wire-format key, not a scope
            """}, "metrics-scope")
        assert len(got) == 1 and got[0].suppressed


class TestJaxHotpath:
    """Per-call device seams reachable from the score dispatch path:
    device_put / to_thread / asarray readback must not creep back into
    the line-rate path (the per-call seam shape COMPONENTS.md §2.11
    removed)."""

    def test_device_put_and_to_thread_in_score_fire(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import asyncio
                import jax

                class Scorer:
                    async def score(self, x):
                        xd = jax.device_put(x, self.dev)
                        return await asyncio.to_thread(self._run, xd)
            """}, "jax-hotpath")
        assert len(got) == 2
        assert any("device_put" in f.message for f in got)
        assert any("to_thread" in f.message for f in got)

    def test_reachable_through_helper_fires(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import numpy as np

                class Scorer:
                    async def score(self, x):
                        return self._readback(x)

                    def _readback(self, r):
                        return np.asarray(r)
            """}, "jax-hotpath")
        assert len(got) == 1 and "asarray" in got[0].message

    def test_nested_step_closure_fires(self, tmp_path):
        # closures handed to the dispatcher execute on the path
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import jax

                class Scorer:
                    async def score(self, x):
                        def step(staging):
                            return jax.device_put(staging, self.dev)
                        return await self.dispatcher.dispatch(x, step)
            """}, "jax-hotpath")
        assert len(got) == 1 and "device_put" in got[0].message

    def test_off_path_device_put_is_clean(self, tmp_path):
        # placement during init/restore is not the dispatch path
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import jax

                class Scorer:
                    def restore(self, snap):
                        self.params = jax.device_put(snap.params, self.dev)

                    def _place_norm(self):
                        self.mu_d = jax.device_put(self.mu, self.dev)
            """}, "jax-hotpath")
        assert got == []

    def test_out_of_scope_package_is_ignored(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/router/x.py": """
                import jax
                async def score(x):
                    return jax.device_put(x, None)
            """}, "jax-hotpath")
        assert got == []

    def test_weight_export_root_fires_in_lifecycle(self, tmp_path):
        # the native weight export must stay host-side numpy on an
        # already-gathered snapshot: a readback inside it (or a helper
        # it calls) fires
        got = findings_of(tmp_path, {
            "linkerd_tpu/lifecycle/x.py": """
                import numpy as np

                def export_weight_blob(snap, version):
                    return _pack(snap.params)

                def _pack(params):
                    return np.asarray(params["w"]).tobytes()
            """}, "jax-hotpath")
        assert len(got) == 1 and "asarray" in got[0].message

    def test_native_publish_root_fires(self, tmp_path):
        # the in-data-plane tier's per-batch board publish is a root: a
        # device barrier there would put the old per-batch latency back
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import jax

                class Tele:
                    def _publish_native_batch(self, ns):
                        jax.block_until_ready(ns["scores"])
            """}, "jax-hotpath")
        assert len(got) == 1 and "block_until_ready" in got[0].message

    def test_justified_suppression_suppresses(self, tmp_path):
        got = findings_of(tmp_path, {
            "linkerd_tpu/telemetry/x.py": """
                import numpy as np
                async def score(x):
                    return np.asarray(x, np.float32)  # l5d: ignore[jax-hotpath] — host dtype cast, not a readback
            """}, "jax-hotpath")
        assert len(got) == 1 and got[0].suppressed

    def test_real_tree_dispatch_path_is_clean(self):
        # the contract the rule exists to keep: the shipped score
        # dispatch path has no unsuppressed per-call seams
        out = run_analysis(["linkerd_tpu"], repo_root=REPO,
                           rules=["jax-hotpath"])
        unsuppressed = [f for f in out if not f.suppressed]
        assert unsuppressed == [], "\n" + "\n".join(
            f.show() for f in unsuppressed)


class TestRepoGate:
    """The tier-1 gate: the suite itself over the real tree."""

    def test_rule_inventory(self):
        assert sorted(rule_ids()) == [
            "async-blocking", "config-registry", "float-time",
            "jax-hotpath", "jax-purity", "metrics-scope",
            "stream-release", "swallowed-exception", "task-leak",
        ]

    def test_repo_has_zero_unsuppressed_findings(self):
        out = run_analysis(["linkerd_tpu"], repo_root=REPO)
        unsuppressed = [f for f in out if not f.suppressed]
        assert unsuppressed == [], "\n" + "\n".join(
            f.show() for f in unsuppressed)

    def test_every_repo_suppression_is_justified(self):
        # run_analysis already enforces this via the meta-rule; assert
        # the invariant directly so the intent is explicit in the gate
        out = run_analysis(["linkerd_tpu"], repo_root=REPO)
        for f in out:
            if f.suppressed:
                assert f.justification.strip(), f.show()
