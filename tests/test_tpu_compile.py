"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler (libtpu) compiles for a chip that is
described, not attached, and raises what the chip's compiler would
raise — Mosaic's block-tiling rules, VMEM limits, device memory, its
own crashes. The kernel is called with interpret=False directly,
because `ops` would pick the interpreter on a CPU host. Shapes are the
paper cell's: N=20 clients, M=100 models, P=100 (survival pool 2P=200),
V=656 validation rows padded to 768, C=100 classes, 32x32x3 images.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_store import _flush
from repro.kernels.ensemble_fitness.kernel import (ensemble_fitness,
                                                   ensemble_fitness_batched)

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-chip compile can be written to the persistent cache
    but not read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn, static_argnames=("interpret",)).lower(
        *args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not XLA
    return compiled


@pytest.mark.parametrize("N,P,M", [(20, 100, 100), (20, 200, 100),
                                   (2, 100, 100), (2, 200, 100),
                                   (20, 200, 128)])
def test_batched_fitness_kernel_compiles(one_chip, N, P, M):
    """Every power-of-two client batch the engine sends and both GA
    population sizes; M=128 fills the (M, M) S block and a (BLOCK_P, M)
    chromosome tile to whole lanes."""
    _compile_kernel(ensemble_fitness_batched, _spec(one_chip, N, P, M),
                    _spec(one_chip, N, M), _spec(one_chip, N, M, M))


@pytest.mark.parametrize("P", [100, 200])
def test_single_client_fitness_kernel_compiles(one_chip, P):
    M = 100
    _compile_kernel(ensemble_fitness, _spec(one_chip, P, M),
                    _spec(one_chip, M), _spec(one_chip, M, M))


@pytest.mark.parametrize("family", ["cnn4", "vgg", "resnet", "densenet",
                                    "inception"])
def test_multi_model_forward_compiles(one_chip, family):
    """One family's 20 stacked models on a 256-image 32x32x3 chunk (the
    store-building forward). As a vmap, cnn4's grouped convolutions
    crashed this compiler with a stack overflow."""
    from repro.fl.client import _multi_predict_fn
    from repro.models.cnn import CNNConfig, init_model
    cfg = CNNConfig(n_classes=100, width=16, in_channels=3)
    params = jax.eval_shape(
        lambda: init_model(family, jax.random.PRNGKey(0), cfg))
    stacked = jax.tree.map(
        lambda a: _spec(one_chip, 20, *a.shape, dtype=a.dtype), params)
    _multi_predict_fn(family, cfg).lower(
        stacked, _spec(one_chip, 256, 32, 32, 3)).compile()


def test_flush_compiles_at_paper_cell(one_chip):
    """The donated dirty-row scatter + stats update over the resident
    (20, 100, 768, 100) store, for two clients with two dirty slots."""
    N, M, V, C, K, R = 20, 100, 768, 100, 2, 2
    f, i = (lambda *s: _spec(one_chip, *s)), \
        (lambda *s: _spec(one_chip, *s, dtype=jnp.int32))
    compiled = _flush.lower(
        f(N, M, V, C), f(N, M, V, C), f(N, M), f(N, M), f(N, M, M),
        i(N, V), f(N), f(K * R, V, C), f(K * R), i(K), i(K, R),
        all_clients=False).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
