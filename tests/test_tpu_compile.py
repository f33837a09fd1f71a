"""Compiles of the simulator's device programs for a described TPU v5e
chip.  Nothing runs: the TPU compiler, which is installed alongside
jaxlib, compiles for a v5e:2x2 topology that is described, not attached,
and raises what the chip's compiler would raise (scoped VMEM overflow,
misaligned blocks, programs that do not fit HBM).

The topology is described inside a fixture, never while a module is
imported, so every test worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fl import cnn
from repro.fl.client import StackedClients
from repro.fl.divergence import pairwise_divergence_values
from repro.kernels.alpha_combine.kernel import alpha_combine_flat

#: flattened parameter count of the paper's CNN (10/20 maps, 128 hidden)
CNN_PARAMS = 48_158
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (S, T): mesh-1 pools at N=64/256/1024 and the per-shard slab of a
# 4-chip mesh at N=1024 (all N sources against N/4 local targets)
@pytest.mark.parametrize("s,t", [(64, 64), (256, 256), (1024, 1024),
                                 (1024, 256)])
def test_alpha_combine_compiles_for_v5e(one_chip, s, t):
    theta = jax.ShapeDtypeStruct((s, CNN_PARAMS), jnp.float32,
                                 sharding=one_chip)
    alpha = jax.ShapeDtypeStruct((s, t), jnp.float32, sharding=one_chip)
    compiled = alpha_combine_flat.lower(theta, alpha,
                                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pair_divergence_chunk_fits_v5e_hbm(one_chip):
    """One 256-lane chunk of Algorithm-1 pair estimation over a 256-device
    pool at the default data shape (100 samples of 28x28x3)."""
    n, n_max, lanes = 256, 100, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    h0 = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: cnn.cnn_init(jax.random.PRNGKey(0),
                                            num_classes=2)))
    clients = StackedClients(
        x=sds((n, n_max, 28, 28, 3), jnp.float32),
        y=sds((n, n_max), jnp.int32), labeled=sds((n, n_max), jnp.bool_),
        valid=sds((n, n_max), jnp.bool_), true_y=sds((n, n_max), jnp.int32),
        counts=sds((n,), jnp.int32))
    pi = sds((lanes,), jnp.int32)
    keys = sds((lanes, 2), jnp.uint32)
    compiled = pairwise_divergence_values.lower(
        h0, clients, pi, pi, keys, tau=1, T=8, batch=10, lr=0.01).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
