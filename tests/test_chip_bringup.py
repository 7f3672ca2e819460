"""Bring-up invariants for the accelerator tier: the scorer is selected by
platform and never probed, failures on that path propagate, the compile
cache is placed from outside, and ``chip_smoke.py`` cannot pass without a
chip. (Sorts before test_multicore.py on purpose: tier-1 is time-boxed.)"""

import ast
import re
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from linkerd_tpu.models.anomaly import (
    AnomalyModelConfig, anomaly_scores, init_params,
)
from linkerd_tpu.ops import scoring

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
CFG = AnomalyModelConfig()


def _run(argv, env=None, cwd=REPO, timeout=120):
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(extra)
    return env


# What the children that compile a flow cell's whole step print of the
# routed experts (``models/latent_moe.routed_experts``), for every
# instruction of the optimised program: ``EXPERT_MATRIX`` where one under
# the scope ``expert_tiles`` makes an array of one expert's matrix (the loop
# of PRs 28-32 sliced the three out of the layer's tensors every tile), and
# ``ROWS`` where any makes float32 rows of the hidden width by the ten
# thousand (the sort's worst case ``R`` is 34,304 rows where 12 of 384
# experts are held: nothing may be sized by it).
EXPERTS_PATTERNS = (
    "D_, I_ = cfg.hidden_size, cfg.moe_intermediate_size\n"
    "matrix = re.compile(rf'bf16\\[(?:1,)?(?:{D_},{I_}|{I_},{D_})\\]')\n"
    "many = re.compile(rf'f32\\[(\\d+),{D_}\\]')\n")
EXPERTS_SEEN = (
    "    if 'expert_tiles' in line and matrix.search(typ):\n"
    "        print('EXPERT_MATRIX', rest.split('(', 1)[0], name, typ[:60])\n"
    "    if any(int(n) >= 16384 for n in many.findall(typ)):\n"
    "        print('ROWS', rest.split('(', 1)[0], name, typ[:60])\n"
    + "    if re.search(r'op_name=\"[^\"]*/append/', rest):\n"
    "        print('APPEND', rest.split('(', 1)[0], name)\n")
# ... and of the attention layers' append (``ops/cache_append.py``): how
# many calls of the kernel the step makes (``NAMED append <n>``), and
# ``APPEND <opcode> <name>`` for every instruction made under an
# ``append`` scope, in the entry and in every loop body
APPEND_NAMED = (
    "print('NAMED append', len(re.findall(\n"
    "    r'^\\s*%cache_append_fused[\\w.]* = ', text, re.M)))\n")
# what may be done under ``append`` by a step that appends by the kernel:
# lay the call's entries along the lanes, count, call the kernel; no loop of
# windows (``while``, ``dynamic-slice``, ``dynamic-update-slice``), no
# gather or scatter
APPEND_LOOPS = {"while", "dynamic-slice", "dynamic-update-slice", "gather",
                "scatter"}


def appended_by_the_kernel(compiled: str, layers: int) -> None:
    """The step's attention layers append by one call of the kernel each,
    aliased in place (no instruction under ``append`` is one of XLA's
    window loops)."""
    assert f"NAMED append {layers}" in compiled
    made = [line.split()[1:] for line in compiled.splitlines()
            if line.startswith("APPEND")]
    assert sum(m[0] == "custom-call" for m in made) == layers, made
    assert not [m for m in made if m[0] in APPEND_LOOPS], made


class TestScorerSelection:
    def test_cpu_gets_xla_without_touching_pallas(self, monkeypatch):
        def no_kernel(*a, **k):
            raise AssertionError("pallas_call reached on a cpu platform")

        monkeypatch.setattr(scoring.pl, "pallas_call", no_kernel)
        assert scoring.scorer_kind("cpu") == "xla"
        params = init_params(jax.random.key(0), CFG)
        x = jax.random.normal(jax.random.key(1), (8, CFG.in_dim))
        got = scoring.best_scorer(CFG, "cpu")(params, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(anomaly_scores(params, x, CFG)),
            atol=1e-6)

    def test_kernel_error_on_tpu_platform_propagates(self, monkeypatch):
        class MosaicRefused(Exception):
            pass

        def refuse(*a, **k):
            raise MosaicRefused("kernel does not compile")

        monkeypatch.setattr(scoring, "fused_anomaly_scores", refuse)
        assert scoring.scorer_kind("tpu") == "fused_pallas"
        params = init_params(jax.random.key(0), CFG)
        x = jnp.zeros((4, CFG.in_dim), jnp.float32)
        with pytest.raises(MosaicRefused):
            scoring.best_scorer(CFG, "tpu")(params, x)

    def test_selection_contains_no_exception_handler(self):
        with open(scoring.__file__, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        assert not [n for n in ast.walk(tree) if isinstance(n, ast.Try)]
        assert not hasattr(scoring, "fused_available")

    def test_kernel_compiles_for_v5e_with_the_real_mosaic(self):
        """libtpu's compile-only client needs no chip: lower the fused
        kernel for a v5e device and run Mosaic's own compile on it (tier-1
        otherwise only ever runs the kernel with interpret=True)."""
        code = (
            "import jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models.anomaly import (\n"
            "    AnomalyModelConfig, init_params)\n"
            "from linkerd_tpu.ops.scoring import best_scorer\n"
            "cfg = AnomalyModelConfig()\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,"
            " sharding=sh)\n"
            "params = jax.tree.map(S, init_params(jax.random.key(0), cfg))\n"
            "vec = S(jnp.zeros((cfg.in_dim,)))\n"
            "for rows in (8, 1024):\n"
            "    best_scorer(cfg, 'tpu', donate=True).lower(\n"
            "        params, S(jnp.zeros((rows, cfg.in_dim))), vec, vec\n"
            "    ).compile()\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], env=_clean_env(
            JAX_PLATFORMS="cpu", TPU_ACCELERATOR_TYPE="v5litepod-4",
            TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout

    def test_statistics_program_compiles_for_v5e_and_copies_no_batch(self):
        """The fit's statistics program (``models.anomaly.running_norm``)
        at the benchmark cell's 2,097,152 rows and at its set-up fit's
        65,536, with and without a row mask, compiled by the TPU's own
        compiler with no chip: two fused passes over the resident batch,
        so no temporary of anything like the batch's size (302 MB)."""
        code = (
            "import functools, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models.anomaly import running_norm\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "f32 = functools.partial(S, jnp.float32)\n"
            "step = jax.jit(functools.partial(running_norm, momentum=0.2))\n"
            "norm = (f32(36), f32(36), S(jnp.bool_))\n"
            "for rows in (65536, 2097152):\n"
            "    for mask in ((), (f32(rows),)):\n"
            "        m = step.lower(norm, f32(rows, 36), f32(rows),\n"
            "                       f32(rows), *mask).compile(\n"
            "                       ).memory_analysis()\n"
            "        print('TEMP', m.temp_size_in_bytes)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], env=_clean_env(
            JAX_PLATFORMS="cpu", TPU_ACCELERATOR_TYPE="v5litepod-4",
            TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        temps = [int(line.split()[1]) for line in proc.stdout.splitlines()
                 if line.startswith("TEMP")]
        assert len(temps) == 4 and max(temps) < 32 * 2 ** 20, temps

    @pytest.fixture(scope="class")
    def flow_step_compiled(self):
        """What a child says of the flow model's step
        (``models.latent_moe.flow_step``) as the benchmark's cell runs it:
        the configuration ``kimi-k2-6-ep32`` (hidden 7,168, 12 of 384
        experts held, a cache of 512 slots x 1,024 positions x 576 a
        layer), 64 flows x 64 events, the attention a TPU gets, compiled
        by the TPU's own compiler with no chip. One compile (half a
        minute) for the tests below; the child prints the program's bytes,
        its kernels, and every instruction of the optimised program whose
        result is a layer's cache or a range of its positions (``WHOLE
        <opcode> <name> <type>``)."""
        code = (
            "import json, re, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models import latent_moe as lm\n"
            "from linkerd_tpu.ops.cache_append import best_append\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    best_expert_product)\n"
            "from linkerd_tpu.ops.flow_attention import best_attention\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "with open('chipbench/configs/kimi-k2-6-ep32.json') as f:\n"
            "    cfg = lm.LatentMoEConfig.from_config(json.load(f))\n"
            "assert cfg == lm.LatentMoEConfig()\n"
            "held = cfg.experts_held[1] - cfg.experts_held[0]\n"
            "params = {'layers': [{} for _ in range(cfg.layers)]}\n"
            "for name, (shape, _, _, each) in lm.tensor_table(cfg).items():\n"
            "    a = S(jnp.bfloat16, *((held,) if each else ()), *shape)\n"
            "    p = name.split('.')\n"
            "    if p[0] == 'layers': params['layers'][int(p[1])][p[2]] = a\n"
            "    else: params[name] = a\n"
            "place = lambda t: jax.tree_util.tree_map(\n"
            "    lambda a: S(a.dtype, *a.shape), t)\n"
            "state = place(jax.eval_shape(\n"
            "    lambda: lm.init_state(cfg)))[:3] + (\n"
            "    place(lm.start_shapes(cfg)),)\n"
            "step = jax.jit(lm.flow_step, donate_argnums=(1, 2),\n"
            "               static_argnames=('cfg', 'F', 'T', 'attend',\n"
            "                                'experts', 'append'))\n"
            "c = step.lower(params, state, S(jnp.int32, 4096, 3),\n"
            "               S(jnp.int32), cfg=cfg, F=64, T=64,\n"
            "               attend=best_attention('tpu'),\n"
            "               experts=best_expert_product('tpu'),\n"
            "               append=best_append('tpu')).compile()\n"
            "m = c.memory_analysis()\n"
            "print('BYTES', m.argument_size_in_bytes, m.temp_size_in_bytes,\n"
            "      m.alias_size_in_bytes)\n"
            "text = c.as_text()\n"
            "print('KERNELS', text.count(\n"
            "    'custom_call_target=\"tpu_custom_call\"'))\n"
            + APPEND_NAMED +
            "# a layer's cache, or 128 and more of its positions, in either\n"
            "# order of the two minor dimensions\n"
            + EXPERTS_PATTERNS +
            "S_, P, E = cfg.slots, cfg.positions, cfg.entry_width\n"
            "whole = re.compile(rf'bf16\\[{S_},(\\d+),{E}\\]|'\n"
            "                   rf'bf16\\[{S_},{E},(\\d+)\\]')\n"
            "for line in text.splitlines():\n"
            "    name, eq, rest = line.strip().partition(' = ')\n"
            "    if not eq or name.startswith('//'): continue\n"
            "    if rest.startswith('('):\n"
            "        depth = 0\n"
            "        for i, ch in enumerate(rest):\n"
            "            depth += (ch == '(') - (ch == ')')\n"
            "            if depth == 0: break\n"
            "        typ, rest = rest[:i + 1], rest[i + 2:]\n"
            "    else:\n"
            "        typ, _, rest = rest.partition(' ')\n"
            "    if any(int(a or b) >= 128 for a, b in whole.findall(typ)):\n"
            "        print('WHOLE', rest.split('(', 1)[0], name, typ[:80])\n"
            + EXPERTS_SEEN +
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=900,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        return proc.stdout

    def test_flow_step_compiles_for_v5e_at_the_published_widths(
            self, flow_step_compiled):
        """It fits one v5e (15.75 GiB) with its weights and its cache as
        arguments, the cache is updated in place (aliased), and the
        attention is the fused kernel, one call a layer."""
        args, temp, alias = (int(v) for v in next(
            line for line in flow_step_compiled.splitlines()
            if line.startswith("BYTES")).split()[1:])
        # weights 6.99 GB and the cache 3.02 GB; the cache comes back aliased
        assert 9.9e9 < args < 10.2e9 and alias > 3.0e9
        # no block of float32 scores among the temporaries, and since PR 31
        # no slice of a layer's cache either (0.78 GiB here; 1.60 with the
        # slots gathered; 1.78 with XLA's attention too; my compile-only
        # readings, PRs 29 and 31)
        assert temp < 0.95 * 2 ** 30 and args + temp < 15.75 * 2 ** 30
        # an append and an attention kernel a layer; a grouped product and
        # a combine an expert layer
        assert "KERNELS 18" in flow_step_compiled

    def test_flow_step_runs_the_experts_as_one_grouped_product(
            self, flow_step_compiled):
        """No instruction under ``expert_tiles`` makes one expert's matrix
        (88 MB here: the kernel's block specs take an expert's blocks from
        the layer's tensors where they lie), and nothing is sized by the
        sort's worst case: 34,304 rows of 7,168 float32 would be 983 MB
        for the ~1,000 pairs a layer this chip's 12 experts get."""
        lines = flow_step_compiled.splitlines()
        assert not [l for l in lines if l.startswith("EXPERT_MATRIX")]
        assert not [l for l in lines if l.startswith("ROWS")]

    def test_flow_step_makes_no_copy_of_a_layers_cache(
            self, flow_step_compiled):
        """Reading 64 slots and appending 4,096 rows makes no array of a
        layer's size. The compiler stores a layer positions-minor
        (``bf16[512,1024,576]{1,2,0}``: 576 is no multiple of 128 lanes)
        and, while the step gathered the flows' slots, first sliced the
        **whole** layer into three ranges of positions (ten operations,
        fifteen arrays of ``[512, 256..384, 576]``: 604 MB read and
        written a layer whatever the call touched; PR 31). Now the
        optimised program names a layer's cache only to pass it on: as a
        parameter, as the operand and the aliased result of the append's
        kernel (once a loop of ``dynamic-update-slice``, one window in
        place a trip) and the attention's (the transpose to ``[slots,
        entry, positions]`` is a ``bitcast`` of that layout) and in the
        result."""
        seen = [line.split()[1:] for line in flow_step_compiled.splitlines()
                if line.startswith("WHOLE")]
        assert len(seen) >= 5                   # a parameter a layer
        passes_on = {"parameter", "get-tuple-element", "bitcast", "tuple",
                     "custom-call"}
        made = [s for s in seen if s[0] not in passes_on]
        assert not made, made[:10]
        appended_by_the_kernel(flow_step_compiled, 5)
        # the kernel's view of each layer is free
        assert sum(s[0] == "bitcast" and "[512,576,1024]" in s[2]
                   for s in seen) >= 5

    def test_flow_attention_compiles_for_v5e_at_every_kind_of_layout(self):
        """The fused attention alone at the published entry (512 + 64, 64
        heads, 1,024 positions), each flow's slot taken from a layer's
        cache of 512 by its number, in the layouts ``FlowTable`` makes: the
        cell's 64 x 64 (tiles of 16 events), chunks of 1 event (64 query
        rows a flow), one long flow, and calls of a few flows."""
        code = (
            "import functools, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.ops.flow_attention import (\n"
            "    latent_attention_fused)\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "bf = functools.partial(S, jnp.bfloat16)\n"
            "fn = jax.jit(functools.partial(latent_attention_fused,\n"
            "                               scale=0.135))\n"
            "for F, T in ((64, 64), (64, 1), (1, 64), (2, 8), (8, 512)):\n"
            "    text = fn.lower(bf(F, T, 64, 512), bf(F, T, 64, 64),\n"
            "                    bf(512, 1024, 576), S(jnp.int32, F),\n"
            "                    S(jnp.int32, F)).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (F, T)\n"
            "    print('LAYOUT', F, T)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=600,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        assert proc.stdout.count("LAYOUT") == 5


    @pytest.fixture(scope="class")
    def lfm2_step_compiled(self):
        """The same step over the second flow model's layers
        (``models/lfm2_moe.py``) as the benchmark's cell
        ``lfm2-24b-a2b.flows64x64-fullvocab`` runs it: the published widths
        (hidden 2,048, 64 of 64 experts held, the whole vocabulary), 9
        layers with two kinds of state (2 caches of keys and values ``[512,
        1024, 1024]``, 7 tails ``[512, 2, 2048]``), 64 flows x 64 events,
        the attention a TPU gets. The child prints what
        ``flow_step_compiled`` prints, and every instruction that makes
        an array of the tied embedding's size."""
        code = (
            "import json, re, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models import latent_moe as lm, lfm2_moe as lf\n"
            "from linkerd_tpu.ops.cache_append import best_append\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    best_expert_product)\n"
            "from linkerd_tpu.ops.flow_attention import best_attention\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "with open('chipbench/configs/lfm2-24b-a2b.json') as f:\n"
            "    cfg = lf.Lfm2MoEConfig.from_config(json.load(f))\n"
            "assert cfg == lf.Lfm2MoEConfig()\n"
            "held = cfg.experts_held[1] - cfg.experts_held[0]\n"
            "params = {'layers': [{} for _ in range(cfg.layers)]}\n"
            "for name, (shape, _, _, each) in cfg.tensors().items():\n"
            "    a = S(jnp.bfloat16, *((held,) if each else ()), *shape)\n"
            "    p = name.split('.')\n"
            "    if p[0] == 'layers': params['layers'][int(p[1])][p[2]] = a\n"
            "    else: params[name] = a\n"
            "place = lambda t: jax.tree_util.tree_map(\n"
            "    lambda a: S(a.dtype, *a.shape), t)\n"
            "state = place(jax.eval_shape(\n"
            "    lambda: lm.init_state(cfg)))[:3] + (\n"
            "    place(lm.start_shapes(cfg)),)\n"
            "step = jax.jit(lm.flow_step, donate_argnums=(1, 2),\n"
            "               static_argnames=('cfg', 'F', 'T', 'attend',\n"
            "                                'experts', 'append'))\n"
            "c = step.lower(params, state, S(jnp.int32, 4096, 3),\n"
            "               S(jnp.int32), cfg=cfg, F=64, T=64,\n"
            "               attend=best_attention('tpu', True),\n"
            "               experts=best_expert_product('tpu'),\n"
            "               append=best_append('tpu')).compile()\n"
            "m = c.memory_analysis()\n"
            "print('BYTES', m.argument_size_in_bytes, m.temp_size_in_bytes,\n"
            "      m.alias_size_in_bytes)\n"
            "text = c.as_text()\n"
            "print('KERNELS', text.count(\n"
            "    'custom_call_target=\"tpu_custom_call\"'))\n"
            + APPEND_NAMED
            + EXPERTS_PATTERNS +
            "S_, P, E = cfg.slots, cfg.positions, cfg.entry_width\n"
            "V = cfg.vocab_slice\n"
            "whole = re.compile(rf'bf16\\[{S_},(\\d+),{P}\\]|'\n"
            "                   rf'bf16\\[{S_},{E},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[{V},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[4096,({V})\\]')\n"
            "for line in text.splitlines():\n"
            "    name, eq, rest = line.strip().partition(' = ')\n"
            "    if not eq or name.startswith('//'): continue\n"
            "    if rest.startswith('('):\n"
            "        depth = 0\n"
            "        for i, ch in enumerate(rest):\n"
            "            depth += (ch == '(') - (ch == ')')\n"
            "            if depth == 0: break\n"
            "        typ, rest = rest[:i + 1], rest[i + 2:]\n"
            "    else:\n"
            "        typ, _, rest = rest.partition(' ')\n"
            "    if any(int(next(x for x in g if x)) >= 128\n"
            "           for g in whole.findall(typ)):\n"
            "        op = rest.split('(', 1)[0]\n"
            "        if op == 'fusion' and 'calls=%bitcast_fusion' in rest:\n"
            "            op = 'bitcast'     # a fusion of a bitcast alone\n"
            "        print('WHOLE', op, name, typ[:80])\n"
            + EXPERTS_SEEN +
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=900,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        return proc.stdout

    def test_lfm2_step_compiles_for_v5e_at_the_published_widths(
            self, lfm2_step_compiled):
        """It fits one v5e (15.75 GiB) with all 9 layers' weights and both
        kinds of state as arguments, the state is updated in place
        (aliased), the attention is the fused kernel, one call an
        attention layer, and the head's logits come in blocks."""
        args, temp, alias = (int(v) for v in next(
            line for line in lfm2_step_compiled.splitlines()
            if line.startswith("BYTES")).split()[1:])
        # weights 10.36 GB; keys and values 2 x 1.07 GB, tails 7 x 2 MB
        assert 12.4e9 < args < 12.7e9 and alias > 2.17e9
        # the float32 logits of 4,096 events over 65,536 ids would be 1
        # GiB at once: 0.36 GiB of temporaries in all (my compile-only
        # reading, PR 32); 0.65 with a run of 192 tiles' rows in (101 MB)
        # and out (201 MB) of the grouped product (PR 33)
        assert temp < 0.75 * 2 ** 30 and args + temp < 14.5 * 2 ** 30
        # two attention layers of two kernels each (the append, the
        # attention); eight expert layers of two kernels each
        assert "KERNELS 20" in lfm2_step_compiled

    def test_lfm2_step_runs_the_experts_as_one_grouped_product(
            self, lfm2_step_compiled):
        """No instruction under ``expert_tiles`` makes one expert's matrix
        (6.3 MB each of three: 18.9 MB a tile, ~160 tiles a layer, in the
        loop this replaced). (Here the worst case is 1.5 times the pairs
        every call brings, 24,576 rows of 2,048: one run of tiles.)"""
        lines = lfm2_step_compiled.splitlines()
        assert not [l for l in lines if l.startswith("EXPERT_MATRIX")]

    def test_expert_product_compiles_for_v5e_at_both_cells_widths(self):
        """The grouped product and the combine alone, compiled by the real
        Mosaic for a described v5e at the published widths: LFM2's 64
        experts of 2,048 x 1,536 (one block an expert: two of them, 37.7
        MB, in VMEM) in a run of 192 tiles, Kimi's 12 of 7,168 x 2,048
        (four blocks of 22 MB) in runs of 54, and a run of one tile; at
        tiles of 128 rows and of 8; the combine into 4,096 tokens' rows, a
        block of 1,024 columns (16.8 MB, in and out) in VMEM."""
        code = (
            "import jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    add_rows_fused, column_block, row_block,\n"
            "    swiglu_tiles_fused)\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "bf = lambda *s: S(jnp.bfloat16, *s)\n"
            "for G, D, I, bi in ((64, 2048, 1536, 1536),\n"
            "                    (12, 7168, 2048, 512)):\n"
            "    assert column_block(D, I) == bi\n"
            "    assert row_block(4096, D) == 1024\n"
            "    for tiles, M in ((192, 128), (54, 128), (1, 128), (4, 8)):\n"
            "        text = swiglu_tiles_fused.lower(\n"
            "            bf(tiles * M, D), S(jnp.float32, tiles * M),\n"
            "            S(jnp.int32, tiles), S(jnp.int32), bf(G, D, I),\n"
            "            bf(G, D, I), bf(G, I, D)).compile().as_text()\n"
            "        assert 'tpu_custom_call' in text, (D, tiles, M)\n"
            "        text = add_rows_fused.lower(\n"
            "            S(jnp.float32, tiles * M, D),\n"
            "            S(jnp.int32, tiles, M), S(jnp.int32),\n"
            "            S(jnp.float32, 4096, D)).compile().as_text()\n"
            "        assert 'tpu_custom_call' in text, (D, tiles, M)\n"
            "        print('RUN', D, tiles, M)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=600,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        assert proc.stdout.count("RUN") == 8

    def test_lfm2_step_makes_no_copy_of_a_layers_keys_and_values(
            self, lfm2_step_compiled):
        """The cache of an attention layer lies ``[slots, entry,
        positions]`` as the model keeps it (no transpose on the way to the
        kernel), and the optimised program names it only to pass it on; no
        array of the embedding's size is made either (the tied head
        contracts with the embedding as it lies), nor the logits of all
        4,096 events at once."""
        seen = [line.split()[1:] for line in lfm2_step_compiled.splitlines()
                if line.startswith("WHOLE")]
        assert len(seen) >= 3                   # two caches, the embedding
        # (a ``while``: the head's loop over blocks of events, which passes
        # the embedding on)
        passes_on = {"parameter", "get-tuple-element", "bitcast", "tuple",
                     "while", "custom-call"}
        made = [s for s in seen if s[0] not in passes_on]
        assert not made, made[:10]
        appended_by_the_kernel(lfm2_step_compiled, 2)

    def test_grouped_attention_compiles_for_v5e_at_every_kind_of_layout(self):
        """The same kernel over keys and values in groups of heads, alone
        at the published sizes (32 query heads over 8 key/value heads of
        64; a cache ``[512, 1024, 1024]``, positions last) in the layouts
        ``FlowTable`` makes."""
        code = (
            "import functools, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models.grouped_attention import Queries\n"
            "from linkerd_tpu.ops.flow_attention import (\n"
            "    grouped_attention_fused)\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "bf = functools.partial(S, jnp.bfloat16)\n"
            "f32 = functools.partial(S, jnp.float32)\n"
            "fn = jax.jit(functools.partial(grouped_attention_fused,\n"
            "                               scale=0.125))\n"
            "for F, T in ((64, 64), (64, 1), (1, 64), (2, 8), (8, 512)):\n"
            "    # the queries as projected: the whole head of 64 turned on\n"
            "    # the tile, two heads to its 128 lanes; no gate\n"
            "    q = Queries(f32(F, T, 32 * 64), f32(F, T, 1, 32),\n"
            "                f32(F, T, 1, 32), None, 32)\n"
            "    text = fn.lower(q, bf(512, 1024, 1024),\n"
            "                    S(jnp.int32, F), S(jnp.int32, F)\n"
            "                    ).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (F, T)\n"
            "    print('LAYOUT', F, T)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=600,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        assert proc.stdout.count("LAYOUT") == 5


    @pytest.fixture(scope="class")
    def laguna_step_compiled(self):
        """The same step over the third flow model's layers
        (``models/laguna_moe.py``) as the benchmark's cell
        ``laguna-xs.2.flows64x64-long`` runs it: the published widths
        (hidden 2,048, heads of 128, 48 or 64 of them over 8, 256 of 256
        experts held beside a shared one, the whole vocabulary through a
        head of its own), 5 layers with two kinds of state (2 caches
        ``[128, 2048, 4224]``, 3 rings ``[128, 2048, 640]``), 64 flows x
        64 events, the attention a TPU gets. The child prints what
        ``lfm2_step_compiled`` prints: every instruction that makes an
        array of a cache's, a ring's, the embedding's, the head's or the
        whole logits' size."""
        code = (
            "import json, re, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models import latent_moe as lm\n"
            "from linkerd_tpu.models import laguna_moe as lg\n"
            "from linkerd_tpu.ops.cache_append import best_append\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    best_expert_product)\n"
            "from linkerd_tpu.ops.flow_attention import best_attention\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "with open('chipbench/configs/laguna-xs.2.json') as f:\n"
            "    cfg = lg.LagunaMoEConfig.from_config(json.load(f))\n"
            "assert cfg == lg.LagunaMoEConfig()\n"
            "held = cfg.experts_held[1] - cfg.experts_held[0]\n"
            "params = {'layers': [{} for _ in range(cfg.layers)]}\n"
            "for name, (shape, _, _, each) in cfg.tensors().items():\n"
            "    a = S(jnp.bfloat16, *((held,) if each else ()), *shape)\n"
            "    p = name.split('.')\n"
            "    if p[0] == 'layers': params['layers'][int(p[1])][p[2]] = a\n"
            "    else: params[name] = a\n"
            "place = lambda t: jax.tree_util.tree_map(\n"
            "    lambda a: S(a.dtype, *a.shape), t)\n"
            "state = place(jax.eval_shape(\n"
            "    lambda: lm.init_state(cfg)))[:3] + (\n"
            "    place(lm.start_shapes(cfg)),)\n"
            "step = jax.jit(lm.flow_step, donate_argnums=(1, 2),\n"
            "               static_argnames=('cfg', 'F', 'T', 'attend',\n"
            "                                'experts', 'append'))\n"
            "c = step.lower(params, state, S(jnp.int32, 4096, 3),\n"
            "               S(jnp.int32), cfg=cfg, F=64, T=64,\n"
            "               attend=best_attention('tpu', True),\n"
            "               experts=best_expert_product('tpu'),\n"
            "               append=best_append('tpu')).compile()\n"
            "m = c.memory_analysis()\n"
            "print('BYTES', m.argument_size_in_bytes, m.temp_size_in_bytes,\n"
            "      m.alias_size_in_bytes)\n"
            "text = c.as_text()\n"
            "print('KERNELS', text.count(\n"
            "    'custom_call_target=\"tpu_custom_call\"'))\n"
            + APPEND_NAMED +
            "for name in ('grouped', 'window'):\n"
            "    print('NAMED', name, len(re.findall(\n"
            "        rf'^\\s*%{name}_attention_fused[\\w.]* = ', text,\n"
            "        re.M)))\n"
            + EXPERTS_PATTERNS +
            "S_, P, R, E = cfg.slots, cfg.positions, cfg.ring, cfg.entry_width\n"
            "V = cfg.vocab_slice\n"
            "whole = re.compile(rf'bf16\\[{S_},(\\d+),(?:{P}|{R})\\]|'\n"
            "                   rf'bf16\\[{S_},{E},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[{V},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[(\\d+),{V}\\]')\n"
            "for line in text.splitlines():\n"
            "    name, eq, rest = line.strip().partition(' = ')\n"
            "    if not eq or name.startswith('//'): continue\n"
            "    if rest.startswith('('):\n"
            "        depth = 0\n"
            "        for i, ch in enumerate(rest):\n"
            "            depth += (ch == '(') - (ch == ')')\n"
            "            if depth == 0: break\n"
            "        typ, rest = rest[:i + 1], rest[i + 2:]\n"
            "    else:\n"
            "        typ, _, rest = rest.partition(' ')\n"
            "    if any(int(next(x for x in g if x)) >= 640\n"
            "           for g in whole.findall(typ)):\n"
            "        op = rest.split('(', 1)[0]\n"
            "        if op == 'fusion' and 'calls=%bitcast_fusion' in rest:\n"
            "            op = 'bitcast'     # a fusion of a bitcast alone\n"
            "        print('WHOLE', op, name, typ[:80])\n"
            + EXPERTS_SEEN +
            "# what touches an array as large as a layer's queries or its\n"
            "# attention's output, [F, T, heads x head] elements, under an\n"
            "# attention scope of the entry computation: QO scope opcode\n"
            "# name reads writes (arrays of that size among its operands\n"
            "# and in its result)\n"
            "sizes = {4096 * h * cfg.head_dim for h in cfg.heads_per_layer}\n"
            "shape = re.compile(r'(?:bf16|f32)\\[([\\d,]+)\\]')\n"
            "def large(typ):\n"
            "    n = 0\n"
            "    for dims in shape.findall(typ):\n"
            "        size = 1\n"
            "        for d in dims.split(','): size *= int(d)\n"
            "        n += size in sizes\n"
            "    return n\n"
            "types, entry = {}, []\n"
            "for line in text[text.index('ENTRY'):].splitlines():\n"
            "    name, eq, rest = line.strip().partition(' = ')\n"
            "    if not eq or name.startswith('//'): continue\n"
            "    name = name.replace('ROOT ', '')\n"
            "    if rest.startswith('('):\n"
            "        depth = 0\n"
            "        for i, ch in enumerate(rest):\n"
            "            depth += (ch == '(') - (ch == ')')\n"
            "            if depth == 0: break\n"
            "        typ, rest = rest[:i + 1], rest[i + 2:]\n"
            "    else:\n"
            "        typ, _, rest = rest.partition(' ')\n"
            "    types[name] = typ\n"
            "    entry.append((name, typ, rest))\n"
            "for name, typ, rest in entry:\n"
            "    scope = re.search(r'op_name=\"[^\"]*(layer\\d+\\.'\n"
            "                      r'(?:full|window)_attention)', rest)\n"
            "    if not scope: continue\n"
            "    args = rest.split(', metadata=')[0].split(', calls=')[0]\n"
            "    reads = sum(large(types.get(a, ''))\n"
            "                for a in re.findall(r'%[\\w.\\-]+', args))\n"
            "    if reads or large(typ):\n"
            "        print('QO', scope.group(1), rest.split('(', 1)[0], name,\n"
            "              reads, large(typ))\n"
            "# the parts the benchmark reads the step's device time by: how\n"
            "# many of them each instruction under a layer's scope lies in\n"
            "from linkerd_tpu.telemetry import phases\n"
            "with open('chipbench/metrics/flow_step.unattributed_pct.json'\n"
            "          ) as f:\n"
            "    parts = json.load(f)['scopes']\n"
            "for name, path in phases.instruction_scopes(text).items():\n"
            "    if re.search(r'(^|/)layer\\d+\\.', path):\n"
            "        found = {p for c in path.split('/') for p in parts\n"
            "                 if c == p or c.endswith('.' + p)}\n"
            "        print('PART', len(found), name, path)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=900,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        return proc.stdout

    def test_laguna_step_passes_q_and_o_through_hbm_once_each(
            self, laguna_step_compiled):
        """Inside ``layer<l>.full_attention`` / ``layer<l>.window_attention``
        three instructions touch an array of ``[F, T, heads x head]``
        elements (25 M on a full layer, 34 M on a sliding one) and no
        other does: ``wq``'s product writes ``q`` (float32, as the kernel
        takes it), the kernel reads it and writes ``o``, ``wo``'s product
        reads ``o``. RoPE's split and concatenation, the cast, the two
        transposes to and from the kernel's rows, the gate's pass and
        ``wo``'s cast, ten or so passes of 134 MB a layer in float32
        until PR 37, are on the kernel's tile; a view of ``q`` by heads
        is another tiling and a copy (0.67 ms a sliding layer by the
        compiler's estimate), so the operator keeps it ``[F, T, heads x
        head]``."""
        seen = [line.split()[1:] for line in laguna_step_compiled.splitlines()
                if line.startswith("QO")]
        by_layer = {}
        for scope, op, name, reads, writes in seen:
            by_layer.setdefault(scope, []).append(
                (op, int(reads), int(writes), name))
        assert sorted(by_layer) == [
            "layer0.full_attention", "layer1.window_attention",
            "layer2.window_attention", "layer3.window_attention",
            "layer4.full_attention"]
        for scope, touched in by_layer.items():
            kernel = "window" if "window" in scope else "grouped"
            assert sorted(t[:3] for t in touched) == [
                ("custom-call", 1, 1), ("fusion", 0, 1), ("fusion", 1, 0)
            ], (scope, touched)
            assert [name for op, *_, name in touched if op == "custom-call"
                    ][0].startswith(f"%{kernel}_attention_fused"), touched

    def test_laguna_step_puts_each_layer_instruction_in_one_part(
            self, laguna_step_compiled):
        """Every instruction of the optimised step (its entry's and its
        loops') made under a layer's scope lies in exactly one of the
        parts the benchmark reads the step's device time by
        (``chipbench/readers/program_scope_ms.py``): an attention layer's
        ``project``, ``append``, ``attend``, ``out``; the feed-forward's
        ``route``, ``dense``, ``expert_tiles``. None is in two, so no
        device time is counted twice, and none in none, so what no part
        holds is the embedding, the state's bookkeeping and what the
        compiler adds with no scope."""
        seen = [line.split(None, 3)[1:]
                for line in laguna_step_compiled.splitlines()
                if line.startswith("PART")]
        assert len(seen) > 500
        assert not [s for s in seen if s[0] != "1"], [
            s for s in seen if s[0] != "1"][:10]
        layers = {re.search(r"layer\d+\.\w+", path).group(0)
                  for _, _, path in seen}
        assert layers == {"layer0.full_attention", "layer4.full_attention",
                          *(f"layer{l}.window_attention" for l in (1, 2, 3)),
                          *(f"layer{l}.ffn" for l in range(5))}

    def test_laguna_step_compiles_for_v5e_at_the_published_widths(
            self, laguna_step_compiled):
        """It fits one v5e (15.75 GiB) with all 5 layers' weights and both
        kinds of state as arguments, the state is updated in place
        (aliased), and each kind of layer makes the call named for it:
        two ``grouped_attention_fused``, three ``window_attention_fused``."""
        args, temp, alias = (int(v) for v in next(
            line for line in laguna_step_compiled.splitlines()
            if line.startswith("BYTES")).split()[1:])
        # weights 7.74 GB; caches 2 x 2.21 GB, rings 3 x 0.34 GB: with all
        # five layers as caches the state alone would be 11.07 GB
        assert 13.0e9 < args < 13.4e9 and alias > 5.4e9
        # the float32 logits of 4,096 events over 100,352 ids would be 1.64
        # GB at once: 0.45 GiB of temporaries in all (my compile-only
        # reading, PR 34)
        assert temp < 0.6 * 2 ** 30 and args + temp < 15.75 * 2 ** 30
        # five attention layers of two kernels each (the append, the
        # attention); four expert layers of two kernels each
        assert "KERNELS 18" in laguna_step_compiled
        assert "NAMED grouped 2" in laguna_step_compiled
        assert "NAMED window 3" in laguna_step_compiled

    def test_laguna_step_runs_the_experts_as_one_grouped_product(
            self, laguna_step_compiled):
        """No instruction under ``expert_tiles`` makes one expert's matrix
        (2 MB each of three), and the rows that are made are a run's, 192
        tiles (24,576 rows of 2,048 float32, 201 MB), not the sort's worst
        case of 512 tiles."""
        lines = laguna_step_compiled.splitlines()
        assert not [l for l in lines if l.startswith("EXPERT_MATRIX")]
        rows = [l for l in lines if l.startswith("ROWS")]
        assert rows and all("[24576,2048]" in l for l in rows), rows

    def test_laguna_step_makes_no_copy_of_a_layers_cache_or_ring(
            self, laguna_step_compiled):
        """A cache and a ring lie ``[slots, entry, positions]`` as the
        model keeps them and the optimised program names them only to
        pass them on; no array of the embedding's or the head's size is
        made, nor the logits of more than a block of events. Each layer
        appends by one call of the kernel, in place, a ring's chunks that
        wrap in the same pass (XLA's append was a loop of
        ``dynamic-update-slice`` a cache and two a ring: eight in all)."""
        seen = [line.split()[1:] for line in laguna_step_compiled.splitlines()
                if line.startswith("WHOLE")]
        assert len(seen) >= 7       # five layers' state, embedding, head
        passes_on = {"parameter", "get-tuple-element", "bitcast", "tuple",
                     "while", "custom-call"}
        made = [s for s in seen if s[0] not in passes_on]
        assert not made, made[:10]
        assert not [s for s in seen if s[0] == "dynamic-update-slice"]
        appended_by_the_kernel(laguna_step_compiled, 5)

    def test_laguna_kernels_compile_for_v5e_at_every_kind_of_layout(self):
        """Both attention calls alone at the published sizes (48 query
        heads over 8 of 128 against a cache ``[128, 2048, 4224]``; 64
        against a ring ``[128, 2048, 640]`` through a window of 512) in
        the layouts ``FlowTable`` makes and the ring's slack admits, and
        the grouped product and the combine at this model's widths: 256
        experts of 2,048 x 512, one block an expert, in the longest run of
        192 tiles and a run of one."""
        code = (
            "import functools, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    add_rows_fused, column_block, row_block,\n"
            "    swiglu_tiles_fused)\n"
            "from linkerd_tpu.models.grouped_attention import Queries\n"
            "from linkerd_tpu.ops.flow_attention import (\n"
            "    grouped_attention_fused)\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "bf = functools.partial(S, jnp.bfloat16)\n"
            "f32 = functools.partial(S, jnp.float32)\n"
            "# the queries as projected, the angles of the rotated half of\n"
            "# a head (32 of 64 pairs on a full layer), a gate a head\n"
            "asked = lambda F, T, H, half: Queries(\n"
            "    f32(F, T, H * 128), f32(F, T, 1, half), f32(F, T, 1, half),\n"
            "    f32(F, T, H), H)\n"
            "full = jax.jit(functools.partial(grouped_attention_fused,\n"
            "                                 scale=128 ** -0.5))\n"
            "ring = jax.jit(functools.partial(grouped_attention_fused,\n"
            "                                 scale=128 ** -0.5, window=512))\n"
            "for F, T in ((64, 64), (64, 1), (1, 64), (2, 8), (8, 128)):\n"
            "    text = full.lower(asked(F, T, 48, 32), bf(128, 2048, 4224),\n"
            "                      S(jnp.int32, F), S(jnp.int32, F)\n"
            "                      ).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (F, T)\n"
            "    assert '%grouped_attention_fused' in text, (F, T)\n"
            "    text = ring.lower(asked(F, T, 64, 64), bf(128, 2048, 640),\n"
            "                      S(jnp.int32, F), S(jnp.int32, F)\n"
            "                      ).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (F, T)\n"
            "    assert '%window_attention_fused' in text, (F, T)\n"
            "    print('LAYOUT', F, T)\n"
            "G, D, I = 256, 2048, 512\n"
            "assert column_block(D, I) == 512 and row_block(4096, D) == 1024\n"
            "for tiles, M in ((192, 128), (1, 128)):\n"
            "    text = swiglu_tiles_fused.lower(\n"
            "        bf(tiles * M, D), S(jnp.float32, tiles * M),\n"
            "        S(jnp.int32, tiles), S(jnp.int32), bf(G, D, I),\n"
            "        bf(G, D, I), bf(G, I, D)).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (tiles, M)\n"
            "    text = add_rows_fused.lower(\n"
            "        S(jnp.float32, tiles * M, D), S(jnp.int32, tiles, M),\n"
            "        S(jnp.int32), S(jnp.float32, 4096, D)\n"
            "        ).compile().as_text()\n"
            "    assert 'tpu_custom_call' in text, (tiles, M)\n"
            "    print('RUN', tiles, M)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=600,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        assert proc.stdout.count("LAYOUT") == 5
        assert proc.stdout.count("RUN") == 2

    @pytest.fixture(scope="class")
    def hy4_step_compiled(self):
        """The same step over the fifth flow model's layers
        (``models/hy4_moe.py``) as the benchmark's cell
        ``hy4-preview-ep16.flows64x64-6k`` runs it: the published widths
        (hidden 6,144, 64 heads of latent attention, an indexer of 32 x
        128, four streams, 16 of 256 experts held beside a shared one, an
        eighth of the vocabulary), 5 layers with two kinds of state (5
        latent caches ``[128, 6144, 576]``, 2 index-key arrays ``[128,
        128, 6144]``), 64 flows x 64 events, the attention a TPU gets. The
        child prints the program's bytes, its kernels by name, every
        instruction that makes an array of a layer's state's, the
        embedding's or the head's size, and the parts each instruction
        under a layer's scope lies in."""
        code = (
            "import json, re, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models import latent_moe as lm\n"
            "from linkerd_tpu.models import hy4_moe as hy\n"
            "from linkerd_tpu.ops.cache_append import best_append\n"
            "from linkerd_tpu.ops.expert_product import (\n"
            "    best_expert_product)\n"
            "from linkerd_tpu.ops.flow_attention import best_attention\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "with open('chipbench/configs/hy4-preview-ep16.json') as f:\n"
            "    cfg = hy.Hy4MoEConfig.from_config(json.load(f))\n"
            "assert cfg == hy.Hy4MoEConfig()\n"
            "held = cfg.experts_held[1] - cfg.experts_held[0]\n"
            "params = {'layers': [{} for _ in range(cfg.layers)]}\n"
            "for name, (shape, _, _, each) in cfg.tensors().items():\n"
            "    a = S(jnp.bfloat16, *((held,) if each else ()), *shape)\n"
            "    p = name.split('.')\n"
            "    if p[0] == 'layers': params['layers'][int(p[1])][p[2]] = a\n"
            "    else: params[name] = a\n"
            "place = lambda t: jax.tree_util.tree_map(\n"
            "    lambda a: S(a.dtype, *a.shape), t)\n"
            "state = place(jax.eval_shape(\n"
            "    lambda: lm.init_state(cfg)))[:3] + (\n"
            "    place(lm.start_shapes(cfg)),)\n"
            "step = jax.jit(lm.flow_step, donate_argnums=(1, 2),\n"
            "               static_argnames=('cfg', 'F', 'T', 'attend',\n"
            "                                'experts', 'append'))\n"
            "c = step.lower(params, state, S(jnp.int32, 4096, 3),\n"
            "               S(jnp.int32), cfg=cfg, F=64, T=64,\n"
            "               attend=best_attention('tpu', sparse=True),\n"
            "               experts=best_expert_product('tpu'),\n"
            "               append=best_append('tpu')).compile()\n"
            "m = c.memory_analysis()\n"
            "print('BYTES', m.argument_size_in_bytes, m.temp_size_in_bytes,\n"
            "      m.alias_size_in_bytes)\n"
            "text = c.as_text()\n"
            "print('KERNELS', text.count(\n"
            "    'custom_call_target=\"tpu_custom_call\"'))\n"
            + APPEND_NAMED +
            "print('NAMED sparse', len(re.findall(\n"
            "    r'^\\s*%sparse_latent_attention_fused[\\w.]* = ', text,\n"
            "    re.M)))\n"
            + EXPERTS_PATTERNS +
            "S_, P, E, V = (cfg.slots, cfg.positions, cfg.entry_width,\n"
            "               cfg.vocab_slice)\n"
            "DH = cfg.index_head_dim\n"
            "# a layer's latent or index keys, or a part of every slot's\n"
            "# keys (XLA split the copy a gather of slots made in three)\n"
            "whole = re.compile(rf'bf16\\[{S_},(\\d+),{E}\\]|'\n"
            "                   rf'bf16\\[{S_},(\\d+),{P}\\]|'\n"
            "                   rf'bf16\\[{S_},{DH},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[{V},(\\d+)\\]|'\n"
            "                   rf'(?:bf16|f32)\\[(\\d+),{V}\\]')\n"
            "for line in text.splitlines():\n"
            "    name, eq, rest = line.strip().partition(' = ')\n"
            "    if not eq or name.startswith('//'): continue\n"
            "    if rest.startswith('('):\n"
            "        depth = 0\n"
            "        for i, ch in enumerate(rest):\n"
            "            depth += (ch == '(') - (ch == ')')\n"
            "            if depth == 0: break\n"
            "        typ, rest = rest[:i + 1], rest[i + 2:]\n"
            "    else:\n"
            "        typ, _, rest = rest.partition(' ')\n"
            "    if any(int(next(x for x in g if x)) >= 128\n"
            "           for g in whole.findall(typ)):\n"
            "        op = rest.split('(', 1)[0]\n"
            "        if op == 'fusion' and 'calls=%bitcast_fusion' in rest:\n"
            "            op = 'bitcast'     # a fusion of a bitcast alone\n"
            "        print('WHOLE', op, name, typ[:80])\n"
            + EXPERTS_SEEN +
            "from linkerd_tpu.telemetry import phases\n"
            "with open('chipbench/metrics/flow_step.unattributed_pct.json'\n"
            "          ) as f:\n"
            "    parts = json.load(f)['scopes'] + ['index', 'hyper']\n"
            "for name, path in phases.instruction_scopes(text).items():\n"
            "    if re.search(r'(^|/)layer\\d+\\.', path):\n"
            "        found = {p for c in path.split('/') for p in parts\n"
            "                 if c == p or c.endswith('.' + p)}\n"
            "        inner = [c for c in path.split('/')\n"
            "                 if any(c == p or c.endswith('.' + p)\n"
            "                        for p in parts)]\n"
            "        print('PART', len(found), inner[-1] if inner else '-',\n"
            "              name, path)\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=900,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        return proc.stdout

    def test_hy4_step_compiles_for_v5e_at_the_published_widths(
            self, hy4_step_compiled):
        """It fits one v5e with all 5 layers' weights and both kinds of
        state as arguments, under 15 GiB with its temporaries, the state
        updated in place (aliased); every layer attends by the selection's
        kernel, and appends its latent, and a ``full`` layer its index
        keys, by the append's kernel: 5 + 7 + 4 x 2 experts' kernels."""
        args, temp, alias = (int(v) for v in next(
            line for line in hy4_step_compiled.splitlines()
            if line.startswith("BYTES")).split()[1:])
        # weights 8.90 GB; 5 latent caches of 0.91 GB, 2 index-key arrays
        # of 0.20 GB
        assert 13.7e9 < args < 13.9e9 and alias > 4.9e9
        # the four streams are arrays of their own: with one array of
        # [F, T, 4, hidden] the compiler relaid it for every
        # hyper-connection and held 2.76 GiB of temporaries
        assert temp < 1.75 * 2 ** 30 and args + temp < 15 * 2 ** 30
        assert "KERNELS 20" in hy4_step_compiled
        assert "NAMED sparse 5" in hy4_step_compiled
        assert "NAMED append 7" in hy4_step_compiled

    def test_hy4_step_puts_each_layer_instruction_in_one_part(
            self, hy4_step_compiled):
        """Every instruction of the optimised step made under a layer's
        scope lies in exactly one of the parts the benchmark reads the
        step's device time by: the flow cells' (``project``, ``append``,
        ``attend``, ``out``; ``route``, ``dense``, ``expert_tiles``) and
        this model's ``index`` (the indexer's projections, scores and
        selection) and ``hyper`` (the hyper-connections); both are
        read."""
        seen = [line.split(None, 4)[1:]
                for line in hy4_step_compiled.splitlines()
                if line.startswith("PART")]
        assert len(seen) > 500
        assert not [s for s in seen if s[0] != "1"], [
            s for s in seen if s[0] != "1"][:10]
        inner = {s[1] for s in seen}
        assert {"index", "hyper", "attend", "append", "project",
                "out"} <= inner
        layers = {re.search(r"layer\d+\.\w+", path).group(0)
                  for *_, path in seen}
        assert layers == {f"layer{l}.{part}" for l in range(5)
                          for part in ("attention", "ffn")}
        index = {re.search(r"layer\d+", path).group(0)
                 for _, part, _, path in seen if part == "index"}
        assert index == {"layer0", "layer1"}       # the full layers alone

    def test_hy4_step_makes_no_copy_of_a_layers_state(
            self, hy4_step_compiled):
        """A latent cache and an index-key array lie as the model keeps
        them and the optimised program names them only to pass them on
        (the selection's kernel reads a slot where it lies; the indexer's
        product reads the flows' keys a few slots at a time); no array of
        the embedding's or the head's size is made but the head widened to
        float32, which its product reads (``head_fp32``). Each of the seven
        arrays is appended by one call of the kernel, in place."""
        seen = [line.split()[1:] for line in hy4_step_compiled.splitlines()
                if line.startswith("WHOLE")]
        passes_on = {"parameter", "get-tuple-element", "bitcast", "tuple",
                     "while", "custom-call"}
        made = [s for s in seen if s[0] not in passes_on]
        assert len(made) <= 1 and all("f32[6144,15104]" in s[2]
                                      for s in made), made[:10]
        appended_by_the_kernel(hy4_step_compiled, 7)

    def test_hy4_kernels_compile_for_v5e_at_every_kind_of_layout(self):
        """The selection's kernel alone at the published sizes (64 heads
        over a latent of 512 + 64, slots of 6,144, index scores ``[F, T,
        6144]``) in the layouts ``FlowTable`` makes: a tile of 16 events
        (1,024 rows, 24 MiB of scores in VMEM) where a chunk has 16 or
        more, the chunk whole where fewer; and the grouped product with
        the SwiGLU's clamp at this model's widths."""
        code = (
            "import functools, jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "try:\n"
            "    topo = topologies.get_topology_desc(\n"
            "        topology_name='v5e:2x2', platform='tpu')\n"
            "except Exception as e:\n"
            "    print('NO_TOPOLOGY', repr(e)); raise SystemExit(0)\n"
            "from linkerd_tpu.models.hy4_moe import Selection\n"
            "from linkerd_tpu.ops.expert_product import swiglu_tiles_fused\n"
            "from linkerd_tpu.ops.flow_attention import (\n"
            "    sparse_latent_attention_fused)\n"
            "sh = SingleDeviceSharding(topo.devices[0])\n"
            "S = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sh)\n"
            "bf = functools.partial(S, jnp.bfloat16)\n"
            "f32 = functools.partial(S, jnp.float32)\n"
            "fn = jax.jit(functools.partial(sparse_latent_attention_fused,\n"
            "                               scale=256 ** -0.5))\n"
            "P = 6144\n"
            "for F, T in ((64, 64), (64, 1), (1, 64), (2, 8), (8, 128)):\n"
            "    sel = Selection(f32(F, T, P), f32(F, T),\n"
            "                    S(jnp.int32, F, T))\n"
            "    text = fn.lower(bf(F, T, 64, 512), bf(F, T, 64, 64),\n"
            "                    bf(128, P, 576), S(jnp.int32, F),\n"
            "                    S(jnp.int32, F), selection=sel,\n"
            "                    sink=f32(64)).compile().as_text()\n"
            "    assert '%sparse_latent_attention_fused' in text, (F, T)\n"
            "    print('LAYOUT', F, T)\n"
            "G, D, I = 16, 6144, 2048\n"
            "text = swiglu_tiles_fused.lower(\n"
            "    bf(4 * 128, D), S(jnp.float32, 4 * 128), S(jnp.int32, 4),\n"
            "    S(jnp.int32), bf(G, D, I), bf(G, D, I), bf(G, I, D),\n"
            "    limit=10.0).compile().as_text()\n"
            "assert 'tpu_custom_call' in text\n"
            "print('COMPILED', topo.devices[0].device_kind)\n")
        proc = _run([sys.executable, "-c", code], timeout=600,
                    env=_clean_env(
                        JAX_PLATFORMS="cpu",
                        TPU_ACCELERATOR_TYPE="v5litepod-4",
                        TPU_WORKER_HOSTNAMES="localhost", PYTHONPATH=REPO))
        if "NO_TOPOLOGY" in proc.stdout:
            pytest.skip("no compile-only TPU client in this installation")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "COMPILED TPU v5" in proc.stdout
        assert proc.stdout.count("LAYOUT") == 5


class TestFailLoud:
    def test_inprocess_primary_that_cannot_build_fails_at_start(
            self, monkeypatch):
        from linkerd_tpu.telemetry.anomaly import (
            JaxAnomalyConfig, JaxAnomalyTelemeter,
        )
        from linkerd_tpu.telemetry.metrics import MetricsTree

        class NoDevice(Exception):
            pass

        def no_device(self):
            raise NoDevice("chip held by another process")

        monkeypatch.setattr(JaxAnomalyTelemeter, "_mk_inprocess", no_device)
        tele = JaxAnomalyTelemeter(
            JaxAnomalyConfig(sidecarAddress="127.0.0.1:1"), MetricsTree())
        with pytest.raises(NoDevice):
            tele._ensure_scorer()  # no quiet demotion to the sidecar

    def test_device_block_reports_what_ran(self):
        import asyncio

        from linkerd_tpu.telemetry.anomaly import InProcessScorer

        scorer = InProcessScorer(devices=[jax.devices()[0]])
        try:
            asyncio.run(scorer.warmup())
            state = scorer.device_state()
        finally:
            scorer.close()
        assert state["platform"] == "cpu"
        assert state["score_path"] == "xla" and state["mesh"] is None
        assert state["count"] == len(jax.devices())
        assert state["score_batches"] == {"4": 2}
        assert state["fit_batches"] == {"4": 1}

    def test_bench_phase_child_that_raised_exits_nonzero(
            self, monkeypatch, capsys):
        import bench
        import linkerd_tpu.compile_cache as cc

        def boom():
            raise RuntimeError("phase blew up")

        monkeypatch.setattr(cc, "place_compile_cache", lambda: "unused")
        monkeypatch.setattr(bench, "static_analysis_bench", boom)
        monkeypatch.setattr(sys, "argv",
                            ["bench.py", "--phase", "static_analysis"])
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code == 1
        frag = bench._last_phase_fragment(capsys.readouterr().out)
        assert "phase blew up" in frag["static_analysis_error"]


class TestCompileCachePlacement:
    CODE = ("from linkerd_tpu.compile_cache import place_compile_cache\n"
            "import os\n"
            "a = place_compile_cache(); b = place_compile_cache()\n"
            "assert a == b == os.environ['JAX_COMPILATION_CACHE_DIR']\n"
            "print(a)\n"
            "print(os.environ['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'])")

    def test_environment_variable_is_honoured(self, tmp_path):
        proc = _run([sys.executable, "-c", self.CODE], env=_clean_env(
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "elsewhere")))
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [str(tmp_path / "elsewhere"), "0"]

    def test_default_is_one_fixed_path_in_the_checkout(self, tmp_path):
        outs = [_run([sys.executable, "-c", self.CODE],
                     env=_clean_env(PYTHONPATH=REPO), cwd=cwd).stdout.split()
                for cwd in (REPO, str(tmp_path))]  # cwd must not matter
        assert outs[0] == outs[1] == [os.path.join(REPO, ".jax_cache"), "0"]

    def test_placement_after_jax_import_is_an_error(self):
        from linkerd_tpu.compile_cache import place_compile_cache
        with pytest.raises(RuntimeError, match="before the first"):
            place_compile_cache()

    def test_no_other_cache_dir_setting_in_the_tree(self):
        hits = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") and d != "chiprun_out"]
            for name in files:
                path = os.path.join(root, name)
                if name.endswith(".py"):
                    with open(path, "r", encoding="utf-8") as f:
                        if "compilation_cache_dir" in f.read().lower():
                            hits.append(os.path.relpath(path, REPO))
        assert sorted(hits) == ["linkerd_tpu/compile_cache.py",
                                "tests/test_chip_bringup.py"]


class TestChipSmoke:
    def test_without_a_chip_it_fails_and_names_the_platform(self):
        proc = _run([sys.executable, SMOKE],
                    env=_clean_env(JAX_PLATFORMS="cpu"), timeout=170)
        assert proc.returncode != 0
        assert "platform is 'cpu'" in proc.stderr
        assert not [ln for ln in proc.stdout.splitlines()
                    if ln.startswith("{")]  # no result line

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        import shutil
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        proc = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
                    env=_clean_env(), timeout=60)
        assert proc.returncode != 0
        assert not proc.stdout.strip()

    def test_parent_imports_no_jax_and_no_telemetry(self):
        with open(SMOKE, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())

        def module_level(nodes):
            for node in nodes:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                    continue  # runs only when called (the child legs)
                yield node
                yield from module_level(ast.iter_child_nodes(node))

        names = []
        for node in module_level(tree.body):
            if isinstance(node, ast.Import):
                names += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names.append(node.module or "")
        assert names, "expected stdlib imports at module level"
        for name in names:
            assert name.split(".")[0] not in ("jax", "jaxlib", "numpy")
            assert not name.startswith("linkerd_tpu.telemetry")


class TestNativeStaleness:
    def test_so_older_than_a_source_is_stale(self, tmp_path, monkeypatch):
        from linkerd_tpu import native

        so = tmp_path / "libl5d_native.so"
        so.write_bytes(b"")
        newest = max(
            os.path.getmtime(os.path.join(native._SRC_DIR, n))
            for n in os.listdir(native._SRC_DIR)
            if n.endswith((".cpp", ".h")) and n not in native._GENERATED)
        monkeypatch.setattr(native, "_SO_PATH", str(so))
        os.utime(so, (newest - 10, newest - 10))
        assert native._stale()
        os.utime(so, (newest + 10, newest + 10))
        assert not native._stale()
        monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "missing"))
        assert not native._stale()  # absent is "not built", not "stale"

    def test_compiler_failure_is_logged_at_warning(self, monkeypatch,
                                                   caplog):
        from linkerd_tpu import native

        def refuse(*a, **k):
            raise subprocess.CalledProcessError(
                1, a[0], stderr=b"fastpath.cpp:1: error: expected ';'")

        monkeypatch.setattr(native.subprocess, "run", refuse)
        with caplog.at_level("WARNING", logger=native.log.name):
            assert native._build() is False
        assert "expected ';'" in caplog.text
