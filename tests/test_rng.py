"""Counter-RNG statistical and determinism properties."""
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import rng
from repro.kernels import ops, ref


def test_moments():
    z = np.asarray(ref.leaf_normal_nd(jnp.uint32(7), (4, 200_000)))
    assert abs(z.mean()) < 0.01
    assert abs(z.std() - 1.0) < 0.01
    # higher moments of N(0,1): skew ~ 0, kurtosis ~ 3
    assert abs(((z - z.mean()) ** 3).mean()) < 0.02
    assert abs(((z - z.mean()) ** 4).mean() - 3.0) < 0.05


def test_rows_decorrelated():
    z = np.asarray(ref.leaf_normal_nd(jnp.uint32(3), (8, 50_000)))
    for i in range(7):
        c = np.corrcoef(z[i], z[i + 1])[0, 1]
        assert abs(c) < 0.02


def test_seed_changes_stream():
    a = np.asarray(ref.leaf_normal_nd(jnp.uint32(1), (2, 1000)))
    b = np.asarray(ref.leaf_normal_nd(jnp.uint32(2), (2, 1000)))
    assert np.abs(a - b).min() > 0  # no element coincides


def test_deterministic():
    a = ref.leaf_normal_nd(jnp.uint32(9), (3, 512))
    b = ref.leaf_normal_nd(jnp.uint32(9), (3, 512))
    assert np.array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
@settings(max_examples=50, deadline=None)
def test_fold_py_matches_jnp(seed, data):
    assert rng.fold_py(seed, data) == int(rng.fold(jnp.uint32(seed),
                                                   jnp.uint32(data)))


def test_nd_matches_2d():
    """Natural-shape generation == flattened-2d generation."""
    z2 = ref.leaf_normal_nd(jnp.uint32(5), (3, 24))
    znd = ref.leaf_normal_nd(jnp.uint32(5), (3, 4, 6))
    assert np.array_equal(np.asarray(z2), np.asarray(znd).reshape(3, 24))


def test_layer_ids_subset():
    """The row sweep's z for a subset of rows is the full stack's z at
    those global layer ids; the other rows are not written."""
    full = ref.leaf_normal_nd(jnp.uint32(5), (8, 10))
    ids = jnp.asarray([1, 4, 6], jnp.int32)
    swept = ops._rows_axpy(jnp.zeros((8, 10)), ids, 3, jnp.uint32(5),
                           1.0, 0.0)
    rows = np.zeros(8, bool)
    rows[np.asarray(ids)] = True
    assert np.array_equal(np.asarray(swept)[rows], np.asarray(full)[rows])
    assert not np.asarray(swept)[~rows].any()
