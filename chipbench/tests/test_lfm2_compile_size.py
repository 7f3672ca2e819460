"""The cell ``lfm2-24b-a2b.flows64x64-fullvocab``'s program, compiled with
the TPU's own compiler and no chip (``test_compile_size.py``'s guard, for
the cell PR 32 added): the flow step over the configuration's 9 layers at
the cell's 64 flows x 64 events has to compile for a described v5e and to
need the argument and temporary bytes PERF.md section 4 gives for it,
within a tenth. Nothing runs, so this says nothing about times."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests.test_compile_size import (  # noqa: F401 - fixtures
    GIB, _bytes, _shapes, one_chip, quiet_cache,
)

STEP_GIB = 12.03    # GiB; compile-only, v5e:2x2, PR 32: 11.67 of arguments


def test_flow_step_at_the_cells_layout(one_chip, quiet_cache):  # noqa: F811
    from linkerd_tpu.models import latent_moe as lm
    from linkerd_tpu.models.lfm2_moe import Lfm2MoEConfig
    from linkerd_tpu.ops.flow_attention import best_attention
    config = harness.load_json("configs", "lfm2-24b-a2b.json")
    mix = harness.load_json("traffic", "flows64x64-fullvocab.json")
    cfg = Lfm2MoEConfig.from_config(config)
    held = cfg.experts_held[1] - cfg.experts_held[0]
    params = {"layers": [{} for _ in range(cfg.layers)]}
    for name, (shape, _, _, each) in cfg.tensors().items():
        a = jax.ShapeDtypeStruct(((held,) if each else ()) + shape,
                                 jnp.bfloat16, sharding=one_chip)
        parts = name.split(".")
        if parts[0] == "layers":
            params["layers"][int(parts[1])][parts[2]] = a
        else:
            params[name] = a
    state = _shapes(jax.eval_shape(lambda: lm.init_state(cfg))[:3]
                    + (lm.start_shapes(cfg),), one_chip)
    rows = jax.ShapeDtypeStruct((mix["rows_per_call"], 3), jnp.int32,
                                sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lm.flow_step, donate_argnums=(1, 2),
        static_argnames=("cfg", "F", "T", "attend")).lower(
            params, state, rows, n, cfg=cfg, F=mix["flows_per_call"],
            T=mix["chunk"], attend=best_attention("tpu", True)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert _bytes(compiled) == pytest.approx(STEP_GIB, rel=0.1)
    assert _bytes(compiled) * GIB < 14.5 * GIB
