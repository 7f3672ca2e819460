"""The cell's programs, compiled with the TPU's own compiler and no chip:
the score program (with the fused kernel) and the train step at the
cell's 2,097,152 rows a call, for a described v5e, have to compile and to
need the argument and temporary bytes that PERF.md section 4 gives for
them, within a tenth: the cell's size, guarded at no chip time. Nothing
runs, so this says nothing about times."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import harness

GIB = 2 ** 30
SCORE_GIB, TRAIN_GIB = 2.31, 4.27    # GiB; compile-only, v5e:2x2, ISSUE 25


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes) / GIB


def test_score_program_at_the_cells_rows(one_chip, quiet_cache):
    from linkerd_tpu.models.anomaly import AnomalyModelConfig, init_params
    from linkerd_tpu.ops.scoring import best_scorer
    cfg = harness.load_json("configs", "mlp36-online.json")
    rows = harness.load_json("traffic", "drain32.json")["rows_per_call"]
    assert rows == cfg["assumed"]["routers"] * cfg["telemeter"][
        "ringCapacity"] == harness.bucket(rows)
    mcfg = AnomalyModelConfig(recon_weight=cfg["telemeter"]["reconWeight"])
    params = _shapes(jax.eval_shape(
        lambda: init_params(jax.random.key(0), mcfg)), one_chip)
    x = jax.ShapeDtypeStruct((rows, mcfg.in_dim), jnp.float32,
                             sharding=one_chip)
    stat = jax.ShapeDtypeStruct((mcfg.in_dim,), jnp.float32,
                                sharding=one_chip)
    compiled = best_scorer(mcfg, "tpu", donate=True).lower(
        params, x, stat, stat).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) == pytest.approx(SCORE_GIB, rel=0.1)


def test_train_step_at_the_cells_rows(one_chip, quiet_cache):
    import optax

    from linkerd_tpu.models.anomaly import (
        AnomalyModelConfig, init_params, loss_fn, normalize_features)
    cfg = harness.load_json("configs", "mlp36-online.json")
    rows = harness.load_json("traffic", "drain32.json")["rows_per_call"]
    tel = cfg["telemeter"]
    mcfg = AnomalyModelConfig(recon_weight=tel["reconWeight"])
    opt = optax.adam(tel["learningRate"])

    # InProcessScorer._mk_train_step, written out: the method needs a
    # scorer, and a scorer needs a device to put its parameters on
    @jax.jit
    def step(params, opt_state, x, labels, mask, mu, var):
        x = normalize_features(x, mu, var)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x, labels, mask, mcfg, None)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    p = jax.eval_shape(lambda: init_params(jax.random.key(0), mcfg))
    params = _shapes(p, one_chip)
    opt_state = _shapes(jax.eval_shape(opt.init, p), one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    compiled = step.lower(params, opt_state, f32(rows, mcfg.in_dim),
                          f32(rows), f32(rows), f32(mcfg.in_dim),
                          f32(mcfg.in_dim)).compile()
    assert _bytes(compiled) == pytest.approx(TRAIN_GIB, rel=0.1)
