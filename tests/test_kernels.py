"""The ZO parameter sweep vs the pure-jnp oracle: shape/dtype sweeps +
properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import rng, zo
from repro.kernels import ops, ref


def _oracle(theta, mask, seed, scale, decay=1.0, path="w"):
    """ref.zo_axpy_nd under ops.zo_axpy's keying of the leaf's stream."""
    leaf_seed = rng.fold(jnp.uint32(seed), jnp.uint32(rng.leaf_uid(path)))
    return ref.zo_axpy_nd(theta, None if mask is None else jnp.asarray(mask),
                          leaf_seed, scale, decay)


# The slot forms a selection policy hands the sweep: a static vector
# (the OPT cell's stratified slots), and a (vector, enabled count) pair
# with one slot disabled or with all enabled (the MoE cell's layer-0
# group, whose drop count varies by step).
_SLOTS = {
    "static": lambda a, L: jnp.asarray(a),
    "pair_one_off": lambda a, L: (jnp.asarray(np.append(a, L), jnp.int32),
                                  jnp.int32(len(a))),
    "pair_all_on": lambda a, L: (jnp.asarray(a), jnp.int32(len(a))),
}


@pytest.mark.parametrize("L,shape", [(1, (7,)), (3, (16,)), (4, (8, 8)),
                                     (6, (5, 3, 4)), (2, (1000,)),
                                     (5, (129,)), (2, (257, 3))])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slots", sorted(_SLOTS))
def test_backend_matches_dense(L, shape, dtype, slots):
    """The row sweep over each slot form matches the dense oracle, and
    leaves dropped rows untouched."""
    k = jax.random.PRNGKey(0)
    theta = jax.random.normal(k, (L,) + shape, jnp.dtype(dtype))
    mask = np.random.default_rng(L).random(L) > 0.4
    if not mask.any():
        mask[0] = True
    aidx = np.nonzero(mask)[0].astype(np.int32)
    want = _oracle(theta, mask, 3, 0.05, 0.99)
    got = ops.zo_axpy(theta, path="w", seed=jnp.uint32(3), scale=0.05,
                      decay=0.99, active_idx=_SLOTS[slots](aidx, L))
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    drop = ~mask
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
    """The sweep updates only the listed rows, in place: the same z and
    floats as the dense oracle, dropped rows bit-identical, also with the
    leaf donated to a jitted sweep."""
    theta = jax.random.normal(jax.random.PRNGKey(1), (L,) + shape,
                              jnp.dtype(dtype))
    aidx = np.asarray(_ACTIVE[active](L), np.int32)
    mask = np.zeros(L, bool)
    mask[aidx] = True
    want = _oracle(theta, mask, 5, 0.05, 0.99)
    sweep = jax.jit(lambda t, a: ops.zo_axpy(
        t, path="w", seed=jnp.uint32(5), scale=0.05, decay=0.99,
        active_idx=a), donate_argnums=0)
    before = np.asarray(theta)
    got = np.asarray(sweep(jnp.array(theta), jnp.asarray(aidx)))
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(got.astype(np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    assert np.array_equal(got[~mask], before[~mask])


@pytest.mark.parametrize("L,shape", _SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_masked_path_matches_dense(L, shape, dtype):
    """The uniform policy's slots (its active layers padded with
    disabled slots, a traced count) sweep the rows its mask selects and
    match the dense oracle."""
    theta = jax.random.normal(jax.random.PRNGKey(2), (L,) + shape,
                              jnp.dtype(dtype))
    spec = zo.build_spec({"w": theta}, lambda p: "g")
    masks, idxs, _ = zo.uniform_select(spec, jnp.uint32(L + 7), L // 2)
    mask = masks["g"]
    assert int(idxs["g"][1]) == int(mask.sum()) == L - L // 2
    want = _oracle(theta, mask, 9, -0.1)
    got = ops.zo_axpy(theta, path="w", seed=jnp.uint32(9), scale=-0.1,
                      active_idx=idxs["g"])
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    drop = ~np.asarray(mask)
    assert np.array_equal(np.asarray(got)[drop], np.asarray(theta)[drop])


@pytest.mark.parametrize("n", [64, 100, 65536, 65537])
def test_sweep_row_lengths(n):
    """Rows around the 64Ki-element tile of the TPU kernel the sweep
    replaced: counters stay flat element indices at every length."""
    theta = jnp.arange(2 * n, dtype=jnp.float32).reshape(2, n)
    got = ops.zo_axpy(theta, path="w", seed=jnp.uint32(1), scale=0.1,
                      active_idx=jnp.asarray([0], jnp.int32))
    want = _oracle(theta, [True, False], 1, 0.1)
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
    out = np.asarray(ref.zo_axpy_nd(theta, mask, jnp.uint32(seed), scale,
                                    decay))
    z = np.asarray(ref.leaf_normal_nd(jnp.uint32(seed), (3, 50)))
    want = decay * 1.0 + scale * z
    want[2] = 1.0
    np.testing.assert_allclose(out, want.astype(np.float32), atol=1e-6)
