"""Kernel backends vs the pure-jnp oracle: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.kernels import ops, ref
from repro.kernels.zo_axpy import zo_axpy_2d


@pytest.mark.parametrize("L,shape", [(1, (7,)), (3, (16,)), (4, (8, 8)),
                                     (6, (5, 3, 4)), (2, (1000,)),
                                     (5, (129,)), (2, (257, 3))])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("backend", ["scan", "gather", "pallas"])
def test_backend_matches_dense(L, shape, dtype, backend):
    k = jax.random.PRNGKey(0)
    theta = jax.random.normal(k, (L,) + shape, jnp.dtype(dtype))
    mask = jnp.asarray(np.random.default_rng(L).random(L) > 0.4)
    if not bool(mask.any()):
        mask = mask.at[0].set(True)
    aidx = jnp.nonzero(mask)[0].astype(jnp.int32)
    want = ops.zo_axpy(theta, path="w", seed=jnp.uint32(3), scale=0.05,
                       decay=0.99, mask=mask, backend="dense")
    got = ops.zo_axpy(theta, path="w", seed=jnp.uint32(3), scale=0.05,
                      decay=0.99, mask=mask, active_idx=aidx, backend=backend)
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    # dropped rows untouched in every backend
    drop = ~np.asarray(mask)
    assert np.array_equal(np.asarray(got)[drop], np.asarray(theta)[drop])


_SHAPES = [(1, (7,)), (3, (16,)), (4, (8, 8)), (6, (5, 3, 4)), (2, (1000,)),
           (5, (129,)), (2, (257, 3))]
# active sets of the in-place scan: one row, every row (MeZO, k = L),
# every other row (non-contiguous from L = 3 on)
_ACTIVE = {"one": lambda L: [L - 1], "all": lambda L: list(range(L)),
           "gaps": lambda L: list(range(0, L, 2))}


@pytest.mark.parametrize("L,shape", _SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("active", sorted(_ACTIVE))
def test_scan_active_rows_in_place_match_dense(L, shape, dtype, active):
    """With active_idx the scan backend updates only the listed rows,
    in place: the same z and floats as dense, dropped rows bit-identical,
    also with the leaf donated to a jitted sweep."""
    theta = jax.random.normal(jax.random.PRNGKey(1), (L,) + shape,
                              jnp.dtype(dtype))
    aidx = np.asarray(_ACTIVE[active](L), np.int32)
    mask = np.zeros(L, bool)
    mask[aidx] = True
    want = ops.zo_axpy(theta, path="w", seed=jnp.uint32(5), scale=0.05,
                       decay=0.99, mask=jnp.asarray(mask), backend="dense")
    sweep = jax.jit(lambda t, a: ops.zo_axpy(
        t, path="w", seed=jnp.uint32(5), scale=0.05, decay=0.99,
        active_idx=a, backend="scan"), donate_argnums=0)
    before = np.asarray(theta)
    got = np.asarray(sweep(jnp.array(theta), jnp.asarray(aidx)))
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(got.astype(np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    assert np.array_equal(got[~mask], before[~mask])


@pytest.mark.parametrize("L,shape", _SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_masked_path_matches_dense(L, shape, dtype):
    """Without active_idx (the uniform policy) scan keeps its masked
    lax.scan and still matches dense."""
    theta = jax.random.normal(jax.random.PRNGKey(2), (L,) + shape,
                              jnp.dtype(dtype))
    mask = jnp.asarray(np.random.default_rng(L + 7).random(L) > 0.5)
    want = ops.zo_axpy(theta, path="w", seed=jnp.uint32(9), scale=-0.1,
                       mask=mask, backend="dense")
    got = ops.zo_axpy(theta, path="w", seed=jnp.uint32(9), scale=-0.1,
                      mask=mask, backend="scan")
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    drop = ~np.asarray(mask)
    assert np.array_equal(np.asarray(got)[drop], np.asarray(theta)[drop])


@pytest.mark.parametrize("n", [64, 100, 65536, 65537])
def test_pallas_tile_boundaries(n):
    theta = jnp.arange(2 * n, dtype=jnp.float32).reshape(2, n)
    mask = jnp.asarray([True, False])
    got = zo_axpy_2d(theta, mask, jnp.uint32(1), jnp.float32(0.1),
                     jnp.float32(1.0))
    want = ref.zo_axpy_2d(theta, mask, jnp.uint32(1), 0.1, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_unstacked_leaf():
    theta = jnp.ones((13, 7))
    out = ops.zo_axpy(theta, path="embed", seed=jnp.uint32(2), scale=0.1)
    assert out.shape == theta.shape
    assert not np.allclose(np.asarray(out), 1.0)


@given(st.integers(0, 2**31), st.floats(-0.1, 0.1), st.floats(0.9, 1.0))
@settings(max_examples=25, deadline=None)
def test_axpy_linear_property(seed, scale, decay):
    """out == decay*theta + scale*z exactly (oracle linearity)."""
    theta = jnp.ones((3, 50))
    mask = jnp.asarray([True, True, False])
    out = np.asarray(ref.zo_axpy_2d(theta, mask, jnp.uint32(seed), scale,
                                    decay))
    z = np.asarray(ref.leaf_normal(jnp.uint32(seed), 3, 50))
    want = decay * 1.0 + scale * z
    want[2] = 1.0
    np.testing.assert_allclose(out, want.astype(np.float32), atol=1e-6)
