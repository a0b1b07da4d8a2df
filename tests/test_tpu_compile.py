"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a *described* chip: these tests lower each kernel at real
OPT widths for one v5e core and assert that Mosaic accepted it (a
``tpu_custom_call`` in the compiled HLO).  They catch what interpret
mode cannot — casts Mosaic refuses, block shapes that break the (8, 128)
tiling, kernels that overflow VMEM — at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers all import this file.  Nothing runs here; a compile that
passes is not a chip run.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.fused import matmul as fused_matmul
from repro.kernels import zo_axpy


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler is not installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles land in the persistent cache but can
    # never be read back without the chip: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_zo_axpy_stacked_ffn_leaf(one_chip):
    """OPT-1.3B's stacked FFN leaf: 24 layers of 2048 x 8192 bf16."""
    def f(theta, mask, seed, scale, decay):
        return zo_axpy.zo_axpy_2d(theta, mask, seed, scale, decay,
                                  interpret=False)

    _assert_mosaic(f, _spec((24, 2048 * 8192), jnp.bfloat16, one_chip),
                   _spec((24,), jnp.bool_, one_chip),
                   _spec((), jnp.uint32, one_chip),
                   _spec((), jnp.float32, one_chip),
                   _spec((), jnp.float32, one_chip))


def test_pmatmul_stack_opt13b_ffn(one_chip):
    """The paired +-eps probe over OPT-13B's up-projection."""
    P, M, K, N = 2, 2048, 5120, 20480

    def f(x, w, seeds, scales):
        return fused_matmul.pmatmul_stack(x, w, seeds, scales,
                                          interpret=False, shared_seed=True)

    _assert_mosaic(f, _spec((P, M, K), jnp.bfloat16, one_chip),
                   _spec((K, N), jnp.bfloat16, one_chip),
                   _spec((P,), jnp.uint32, one_chip),
                   _spec((P,), jnp.float32, one_chip))


def test_pmatmul_tied_head(one_chip):
    """The tied LM head: hidden (K=5120) against embed/tok read through
    a transpose, N = vocab 50272 (padded to the lane tile)."""
    M, K, N = 2048, 5120, 50272

    def f(x, w_t, seed, scale):
        return fused_matmul.pmatmul(x, w_t, seed, scale, trans=True, ld=K,
                                    interpret=False)

    _assert_mosaic(f, _spec((M, K), jnp.bfloat16, one_chip),
                   _spec((K, N), jnp.bfloat16, one_chip),
                   _spec((), jnp.uint32, one_chip),
                   _spec((), jnp.float32, one_chip))
