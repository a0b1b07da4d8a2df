"""Golden regression pins for the ZO parameter sweep.

``kernels/ops.zo_axpy`` folds the leaf's path into the step seed and
sweeps the active rows of a stacked leaf with ``ops._rows_axpy``; these
pins drive that sweep at a given leaf seed.  They freeze its
*semantics* — exact output values for f32 and bf16, swept and dropped
rows, rows that are not a multiple of 128 elements — so any change to
the RNG stream, the accumulate dtype (f32 math, cast on store), the
counter indexing, or the row selection is caught as a value diff, not
discovered as a silently-diverged training run.

The expected arrays were pinned from the TPU axpy kernel this sweep
replaced and cross-checked bit-exact against the pure-jnp oracle
(kernels/ref.py); both are asserted below so sweep and oracle cannot
drift apart either.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

# Shared inputs: (3, 20) ramp, row 1 dropped, leaf seed=7, scale=0.125,
# decay=0.75.
SEED, SCALE, DECAY = 7, 0.125, 0.75
MASK = (True, False, True)
ACTIVE = (0, 2)

GOLDEN_F32 = [
    [-2.328188419342041, -2.136239767074585, -1.8744629621505737,
     -1.5842370986938477, -1.658737063407898, -1.4480239152908325,
     -0.8561497330665588, -0.9114561676979065, -0.7327646017074585,
     -0.6654055714607239, -0.48222506046295166, -0.16871586441993713,
     0.15204283595085144, 0.2187245488166809, 0.4803268313407898,
     0.7026308178901672, 0.7442966103553772, 1.2369083166122437,
     1.2519561052322388, 1.231925368309021],
    [2.0, 2.25, 2.5, 2.75, 3.0, 3.25, 3.5, 3.75, 4.0, 4.25,
     4.5, 4.75, 5.0, 5.25, 5.5, 5.75, 6.0, 6.25, 6.5, 6.75],
    [5.089682579040527, 5.395804405212402, 5.470516204833984,
     5.6442108154296875, 5.852479934692383, 6.322897911071777,
     6.222381114959717, 6.4823760986328125, 6.535152912139893,
     7.022654056549072, 6.9342498779296875, 7.212704658508301,
     7.444188117980957, 7.642969131469727, 7.786533355712891,
     8.02572250366211, 8.018147468566895, 8.38583755493164,
     8.474709510803223, 8.863702774047852]]

GOLDEN_BF16 = [
    [-2.328125, -2.140625, -1.875, -1.5859375, -1.65625, -1.4453125,
     -0.85546875, -0.91015625, -0.734375, -0.6640625, -0.482421875,
     -0.1689453125, 0.15234375, 0.21875, 0.48046875, 0.703125,
     0.74609375, 1.234375, 1.25, 1.234375],
    [2.0, 2.25, 2.5, 2.75, 3.0, 3.25, 3.5, 3.75, 4.0, 4.25,
     4.5, 4.75, 5.0, 5.25, 5.5, 5.75, 6.0, 6.25, 6.5, 6.75],
    [5.09375, 5.40625, 5.46875, 5.65625, 5.84375, 6.3125, 6.21875,
     6.46875, 6.53125, 7.03125, 6.9375, 7.21875, 7.4375, 7.65625,
     7.78125, 8.0, 8.0, 8.375, 8.5, 8.875]]

# (2, 256) ramp, both rows active, seed=123, scale=0.5: value and
# magnitude checksums in f64 — a cheap wide-coverage pin.
CHECKSUM_N = 256
CHECKSUM_SUM = 1307.369512297213
CHECKSUM_ABS = 1319.640700943768


def _theta(dtype):
    t = jnp.arange(3 * 20, dtype=jnp.float32).reshape(3, 20) * 0.25 - 3.0
    return t.astype(dtype)


def _sweep(theta, active=ACTIVE, seed=SEED, scale=SCALE, decay=DECAY):
    return ops._rows_axpy(theta, jnp.asarray(active, jnp.int32),
                          len(active), jnp.uint32(seed), jnp.float32(scale),
                          jnp.float32(decay))


def _oracle(theta):
    return ref.zo_axpy_nd(theta, jnp.asarray(MASK), jnp.uint32(SEED),
                          SCALE, DECAY)


@pytest.mark.parametrize("dtype,golden", [("float32", GOLDEN_F32),
                                          ("bfloat16", GOLDEN_BF16)])
def test_golden_values_pinned(dtype, golden):
    theta = _theta(dtype)
    got = _sweep(theta)
    assert got.dtype == theta.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(golden, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_bitexact_vs_oracle(dtype):
    """Sweep and jnp oracle must agree to the bit here (DESIGN.md §2), so
    the golden arrays pin both implementations at once."""
    theta = _theta(dtype)
    np.testing.assert_array_equal(np.asarray(_sweep(theta), np.float32),
                                  np.asarray(_oracle(theta), np.float32))


def test_masked_row_bitwise_untouched():
    theta = _theta("float32")
    got = _sweep(theta)
    assert np.array_equal(np.asarray(got)[1], np.asarray(theta)[1])


def test_checksum_full_tiles():
    theta = (jnp.arange(2 * CHECKSUM_N, dtype=jnp.float32)
             .reshape(2, CHECKSUM_N) * 0.01)
    got = _sweep(theta, active=(0, 1), seed=123, scale=0.5, decay=1.0)
    arr = np.asarray(got, np.float64)
    np.testing.assert_allclose(arr.sum(), CHECKSUM_SUM, rtol=1e-12)
    np.testing.assert_allclose(np.abs(arr).sum(), CHECKSUM_ABS, rtol=1e-12)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ragged_row_block_bitexact_vs_oracle(dtype):
    """Rows of 40 x 1024 elements (the kernel's cut-short last block):
    the counters must still be flat element indices, so the swept z
    equals the oracle's to the bit.  The axpy itself may be contracted
    into an FMA inside the sweep's loop where the oracle rounds the
    product first: a last-bit f32 difference, within the 1e-6 that
    tests/test_kernels.py allows the sweep."""
    n = 40 * 1024
    theta = (jnp.arange(3 * n, dtype=jnp.float32).reshape(3, n)
             * 1e-4).astype(dtype)
    z = ref.leaf_normal_nd(jnp.uint32(SEED), theta.shape).astype(dtype)
    got_z = _sweep(theta, scale=1.0, decay=0.0)
    np.testing.assert_array_equal(np.asarray(got_z, np.float32)[::2],
                                  np.asarray(z, np.float32)[::2])
    tol = 1e-6 if dtype == "float32" else 0.0
    np.testing.assert_allclose(np.asarray(_sweep(theta), np.float32),
                               np.asarray(_oracle(theta), np.float32),
                               atol=tol, rtol=tol)
