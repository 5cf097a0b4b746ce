"""Compile the FL path's kernels and the fused FedSR block for a described
TPU v5e, without a chip: what the chip's compiler refuses fails here.

The topology is described inside a fixture, so importing this file never
loads the TPU compiler; the persistent compilation cache is off around
these compiles (their entries cannot be read back without a chip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import FLConfig
from repro.core.local import LocalTrainer
from repro.kernels.fused_sgd.ops import fused_sgd_update
from repro.models.small import init_small_model

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no libtpu, or it cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _param_shapes(model):
    cfg = get_config(model)
    return cfg, jax.eval_shape(
        lambda: init_small_model(jax.random.PRNGKey(0), cfg))


def _raveled_width(model) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(_param_shapes(model)[1]))


@pytest.mark.parametrize("model", ["fedsr-mlp", "fedsr-cnn"])
@pytest.mark.parametrize("lanes", [0, 25])
def test_fused_sgd_compiles_for_v5e(model, lanes, one_chip):
    """The raveled update at the model's width, on one vector and vmapped
    over 25 lanes (one Table IV edge ring per lane), lowers to the Pallas
    kernel rather than being refused or replaced."""
    shape = (lanes, _raveled_width(model)) if lanes else (
        _raveled_width(model),)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def upd(p, g, m):
        return fused_sgd_update(p, g, m, lr=jnp.float32(0.01), momentum=0.5,
                                interpret=False)

    fn = jax.vmap(upd) if lanes else upd
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_fedsr_block_compiles_for_v5e(one_chip):
    """One eval block of the fused FedSR schedule for fedsr-cnn at the
    Table IV deployment (K=100, 10 rings of 4 at participation 0.4, R=5,
    batch 32, 2 rounds) fits one v5e chip."""
    cfg, params = _param_shapes("fedsr-cnn")
    trainer = LocalTrainer(cfg, FLConfig(algorithm="fedsr", engine="fused"))
    block = trainer._make_schedule("plain", False)

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, H, C, S, B, N, K = 2, 20, 10, 1, 32, 2000, 100
    xs = {"rows": on_chip((n, H, C), jnp.int32),
          "plans": on_chip((n, H, C, S, B), jnp.int32),
          "valid": on_chip((n, H, C, S), jnp.bool_),
          "lr": on_chip((n,)), "aggv": on_chip((n, C))}
    compiled = block.lower(
        jax.tree.map(lambda s: on_chip(s.shape, s.dtype), params), {},
        on_chip((N, cfg.image_size * cfg.image_size * cfg.image_channels)),
        on_chip((N,), jnp.int32), on_chip((K,), jnp.int32), xs).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM, mem
    assert mem.argument_size_in_bytes >= N * 32 * 32 * 3 * 4

