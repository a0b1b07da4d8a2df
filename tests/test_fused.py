"""Virtual-perturbation fused runtime (repro.fused, DESIGN.md §10).

Load-bearing claims:

  * z-consistency: the virtual weight views regenerate exactly the z the
    axpy sweeps (kernels/ops.py) draw — bit-for-bit, per leaf, per layer,
    including the tied head's transposed counter window and embedding
    row gathers.
  * kernel == oracle: the Pallas pmatmul (interpret mode) matches the
    pure-JAX oracle over dtypes, ragged tiles, trans layouts, offsets
    and mask patterns.
  * the step contract: a two_point step with forward_backend="virtual"
    performs exactly ONE parameter axpy (the update) — no perturb, no
    restore — while matching the materialized dense step's projected
    gradient and parameters.
  * the pairing contract: the stacked ±εz forward (ProbePair) is
    bit-identical to the two sequential virtual probe forwards it
    replaces — per kernel call, per lm_loss, per estimator step — while
    loading every W tile and regenerating every z tile exactly once for
    the pair (structural counters).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import estimators, fused
from repro.configs import opt
from repro.core import rng, zo
from repro.estimators import costs
from repro.fused import matmul as fused_matmul
from repro.fused import ref as fref
from repro.kernels import ops as kops
from repro.models import lm

# ---------------------------------------------------------------- helpers


def _tiny_cfg(layers=2, d_model=64, vocab=256):
    return opt.opt_tiny(layers=layers, d_model=d_model, vocab=vocab)


def _batch(vocab, B=4, S=32, seed=0):
    r = np.random.default_rng(seed)
    toks = jnp.asarray(r.integers(0, vocab, (B, S)), jnp.int32)
    return {"tokens": toks, "labels": toks,
            "loss_mask": jnp.ones((B, S), jnp.float32)}


def _loss_fn(mcfg):
    return lambda p, b, perturb=None: lm.lm_loss(mcfg, p, b, perturb=perturb)


# ---------------------------------------------------- kernel vs oracle
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(16, 32, 48), (5, 7, 13), (33, 129, 65)])
@pytest.mark.parametrize("trans", [False, True])
def test_pmatmul_matches_ref(shape, dtype, trans):
    """Pallas kernel (interpret) == oracle: aligned and ragged tiles,
    both counter layouts, active and skipped layers."""
    M, K, N = shape
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, K), dt)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), dt)
    seed = jnp.uint32(1234)
    tol = 1e-6 if dtype == "float32" else 5e-2
    for active in (True, False):
        a = fref.pmatmul(x, w, seed, 1e-3, jnp.bool_(active), trans=trans)
        b = fused_matmul.pmatmul(x, w, seed, 1e-3, jnp.bool_(active),
                                 trans=trans, interpret=True)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


def test_pmatmul_batched_input_and_block_invariance():
    """3-D activations flatten correctly and the result is invariant to
    the (static) tile sizes."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 5, 40))
    w = jax.random.normal(jax.random.fold_in(key, 1), (40, 24))
    seed = jnp.uint32(9)
    want = fref.pmatmul(x, w, seed, 1e-2)
    for bm, bn, bk in ((128, 128, 128), (8, 128, 128)):
        got = fused_matmul.pmatmul(x, w, seed, 1e-2, block_m=bm, block_n=bn,
                                   block_k=bk, interpret=True)
        assert got.shape == (2, 5, 24)
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   atol=1e-5)


def test_pmatmul_counter_offsets_match_slices():
    """Shard invariance: computing a (row/col)-slice with the matching
    counter offset reproduces the slice of the full result — the property
    fused/sharded.py's per-shard invocation is built on."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (6, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48))
    seed = jnp.uint32(5)
    full = fused_matmul.pmatmul(x, w, seed, 1e-3, interpret=True)
    colslice = fused_matmul.pmatmul(x, w[:, 16:40], seed, 1e-3, col_off=16,
                                    ld=48, interpret=True)
    np.testing.assert_allclose(np.asarray(full[:, 16:40]),
                               np.asarray(colslice), atol=1e-6)
    # row shards produce partial sums: sum of shard products == full
    parts = [fused_matmul.pmatmul(x[:, a:b], w[a:b], seed, 1e-3, row_off=a,
                                  ld=48, interpret=True)
             for a, b in ((0, 16), (16, 32))]
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(parts[0] + parts[1]), atol=1e-5)


def test_sharded_wrappers_match_dense():
    """shard_map wrappers on a 1-device mesh reproduce the unsharded
    kernel (the offsets path is covered for >1 shards above)."""
    from jax.sharding import Mesh

    from repro.fused import sharded

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("model",))
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (4, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    seed, scale, active = jnp.uint32(2), 1e-3, True
    want = fused_matmul.pmatmul(x, w, seed, scale, interpret=True)
    got_c = sharded.pmatmul_col_sharded(mesh, x, w, seed, scale, active)
    got_r = sharded.pmatmul_row_sharded(mesh, x, w, seed, scale, active)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got_c),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got_r),
                               atol=1e-6)


# ------------------------------------------------- z-consistency contract
def test_virtual_weight_matches_axpy_unstacked_and_stacked():
    """fref views draw the exact z the axpy sweeps add: unstacked leaf,
    stacked per-layer leaf under a mask, and vector leaves."""
    key = jax.random.PRNGKey(0)
    step_seed = jnp.uint32(77)
    w = jax.random.normal(key, (24, 40))
    wm = kops.zo_axpy(w, path="head/w", seed=step_seed, scale=1e-3)
    weff = fref.pvec(w, fref.layer_seed(step_seed, "head/w", 0), 1e-3)
    assert np.array_equal(np.asarray(wm), np.asarray(weff))

    ws = jax.random.normal(key, (6, 24, 40))
    mask = jnp.asarray([1, 0, 1, 1, 0, 1], bool)
    wm = kops.zo_axpy(ws, path="stages/s0/b0/mix/wq", seed=step_seed,
                      scale=1e-3, mask=mask)
    for l in range(6):
        weff = fref.pvec(ws[l],
                         fref.layer_seed(step_seed, "stages/s0/b0/mix/wq", l),
                         1e-3, active=mask[l])
        assert np.array_equal(np.asarray(wm[l]), np.asarray(weff)), l


def test_virtual_tied_head_and_embedding_match_axpy():
    """Tied head reads embed/tok.T through trans counters; embedding
    lookups gather the perturbed rows — both exactly the axpy's z."""
    key = jax.random.PRNGKey(1)
    step_seed = jnp.uint32(31)
    tok = jax.random.normal(key, (40, 24))
    tokp = kops.zo_axpy(tok, path="embed/tok", seed=step_seed, scale=1e-3)
    h = jax.random.normal(jax.random.fold_in(key, 1), (4, 24))
    lseed = fref.layer_seed(step_seed, "embed/tok", 0)
    got = fref.pmatmul(h, tok.T, lseed, 1e-3, trans=True, ld=24)
    assert np.array_equal(np.asarray(h @ tokp.T), np.asarray(got))

    toks = jnp.asarray([[1, 5, 2], [0, 3, 39]], jnp.int32)
    ge = fref.pembed(tok, toks, lseed, 1e-3)
    assert np.array_equal(np.asarray(tokp[toks]), np.asarray(ge))

    pos = kops.zo_axpy(tok, path="embed/pos", seed=step_seed, scale=1e-3)
    pp = fref.ppos(tok, 8, 16, fref.layer_seed(step_seed, "embed/pos", 0),
                   1e-3)
    assert np.array_equal(np.asarray(pos[8:24]), np.asarray(pp))


@pytest.mark.parametrize("n_drop", [0, 2])
def test_virtual_loss_equals_materialized(n_drop):
    """lm_loss(params, perturb=ctx) equals lm_loss(materialized perturbed
    params) across mask patterns and both probe signs — embeddings,
    positions, norms, projections, tied head.  The z streams themselves
    are bit-identical (tested above); the losses agree to XLA fusion
    tolerance (the two graphs fuse the same float ops differently)."""
    mcfg = _tiny_cfg(layers=4)
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab)
    for t in range(3):
        seed = rng.fold(jnp.uint32(9), jnp.uint32(t))
        masks, _, _ = zo.stratified_select(spec, seed, n_drop)
        for sign in (1.0, -1.0):
            pmat = zo.tree_axpy(params, spec, seed, sign * 1e-3, masks)
            want = float(lm.lm_loss(mcfg, pmat, batch))
            ctx = fused.make_ctx(seed, sign * 1e-3, masks, "virtual_ref")
            got = float(lm.lm_loss(mcfg, params, batch, perturb=ctx))
            np.testing.assert_allclose(want, got, rtol=1e-6,
                                       err_msg=f"t={t} sign={sign}")


def test_virtual_pallas_loss_close_to_materialized():
    """The kernel path agrees with the materialized loss to float32
    accumulation tolerance."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    seed = jnp.uint32(11)
    masks, _, _ = zo.stratified_select(spec, seed, 1)
    pmat = zo.tree_axpy(params, spec, seed, 1e-3, masks)
    want = float(lm.lm_loss(mcfg, pmat, batch))
    ctx = fused.make_ctx(seed, 1e-3, masks, "virtual")
    got = float(lm.lm_loss(mcfg, params, batch, perturb=ctx))
    np.testing.assert_allclose(want, got, rtol=1e-5)


# -------------------------------------------------------- step contract
@pytest.mark.parametrize("fb", ["virtual_ref", "virtual"])
def test_two_point_virtual_matches_materialized_dense(fb):
    """Acceptance gate: the virtual two_point step matches the dense
    materialized step's projected gradient to <=1e-5 rel and its updated
    parameters to float tolerance on the tiny OPT config."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    loss_fn = _loss_fn(mcfg)
    outs = {}
    for backend in ("materialized", fb):
        ecfg = estimators.EstimatorConfig(
            name="two_point", n_drop=1, lr=1e-4, eps=1e-3,
            weight_decay=0.01, forward_backend=backend)
        step, init = estimators.make_step(loss_fn, spec, ecfg)
        outs[backend] = jax.jit(step)(params, init(), batch, jnp.int32(3),
                                      jnp.uint32(9))
    _, _, m_mat = outs["materialized"]
    p_vir, _, m_vir = outs[fb]
    np.testing.assert_allclose(float(m_mat["projected_grad"]),
                               float(m_vir["projected_grad"]), rtol=1e-5)
    np.testing.assert_allclose(float(m_mat["loss"]), float(m_vir["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs["materialized"][0]),
                    jax.tree.leaves(p_vir)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("name,q", [("one_sided", 3), ("averaged", 2),
                                    ("importance", 1)])
def test_estimators_virtual_matches_materialized(name, q):
    """Every estimator produces the same step under virtual_ref probes as
    under materialized dense probes (identical z, identical floats)."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    loss_fn = _loss_fn(mcfg)
    outs = []
    for fb in ("materialized", "virtual_ref"):
        ecfg = estimators.EstimatorConfig(name=name, q=q, n_drop=1, lr=1e-4,
                                          eps=1e-3, forward_backend=fb)
        step, init = estimators.make_step(loss_fn, spec, ecfg)
        p, _, m = jax.jit(step)(params, init(), batch, jnp.int32(1),
                                jnp.uint32(5))
        outs.append((p, m))
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(float(outs[0][1]["projected_grad"]),
                               float(outs[1][1]["projected_grad"]),
                               rtol=1e-4)


def test_virtual_step_performs_single_axpy(monkeypatch):
    """Zero perturb/restore parameter writes: tracing the virtual step
    invokes the axpy machinery exactly once (the update); materialized
    invokes it three times (perturb, perturb, fused restore+update)."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    loss_fn = _loss_fn(mcfg)
    calls = []
    orig = zo.tree_axpy
    monkeypatch.setattr(zo, "tree_axpy",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    for fb, want in (("materialized", 3), ("virtual_ref", 1)):
        calls.clear()
        ecfg = estimators.EstimatorConfig(name="two_point", n_drop=1,
                                          forward_backend=fb)
        step, init = estimators.make_step(loss_fn, spec, ecfg)
        jax.eval_shape(step, params, init(), batch, jnp.int32(0),
                       jnp.uint32(1))
        assert len(calls) == want, fb


def test_virtual_jaxpr_has_single_param_write():
    """The jaxpr-level version of the write contract: with buffer
    donation, only one donated input can alias each parameter output —
    count scatter/dynamic-update-free full-leaf writes by checking that
    dropping the update scale freezes the params exactly."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    loss_fn = _loss_fn(mcfg)
    # lr=0, weight_decay=0: the lone update axpy has scale -lr*g == 0 and
    # decay 1, so if it is truly the only θ write the step is an exact
    # no-op on parameters.  Any residual perturb/restore write would
    # leave a +-eps*z trace.
    ecfg = estimators.EstimatorConfig(name="two_point", n_drop=1, lr=0.0,
                                      eps=1e-3, forward_backend="virtual_ref")
    step, init = estimators.make_step(loss_fn, spec, ecfg)
    p, _, _ = jax.jit(step)(params, init(), batch, jnp.int32(2),
                            jnp.uint32(7))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_cost_model_virtual_entries():
    assert costs.step_counts("two_point")["axpy_sweeps"] == 3
    for fb in ("virtual", "virtual_ref"):
        assert costs.step_counts("two_point", forward_backend=fb) == {
            "forwards": 2, "axpy_sweeps": 1, "state_scalars": 0}
        assert costs.step_counts("one_sided", q=8, forward_backend=fb) == {
            "forwards": 9, "axpy_sweeps": 8, "state_scalars": 0}
        assert costs.step_counts("averaged", q=4, forward_backend=fb) == {
            "forwards": 8, "axpy_sweeps": 4, "state_scalars": 0}
        imp = costs.step_counts("importance", num_layers=12,
                                forward_backend=fb)
        assert imp["axpy_sweeps"] == 1 and imp["state_scalars"] == 12
    with pytest.raises(ValueError):
        costs.step_counts("two_point", forward_backend="nope")


def test_estimator_step_cost_prices_virtual_sweeps():
    from repro.launch import analysis

    terms = {"compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.0}
    pb = 819e9 / 4                        # 0.5 s per sweep at default bw
    mat = analysis.estimator_step_cost(terms, "two_point", param_bytes=pb)
    vir = analysis.estimator_step_cost(terms, "two_point", param_bytes=pb,
                                       forward_backend="virtual")
    assert mat["axpy_sweeps"] == 3 and vir["axpy_sweeps"] == 1
    # fwd_mem = 2.0 - 3*0.5 = 0.5 -> mat: 0.5 + 1.5 = 2.0, vir: 0.5 + 0.5
    np.testing.assert_allclose(mat["memory_s"], 2.0)
    np.testing.assert_allclose(vir["memory_s"], 1.0)


# ------------------------------------------------- paired ±εz probes
@pytest.mark.parametrize("shape,trans", [((8, 128, 128), False),
                                         ((16, 200, 96), False),
                                         ((6, 40, 24), True)])
def test_pmatmul_stack_bitwise_matches_pmatmul(shape, trans):
    """One stacked kernel pass == P separate pmatmul calls, bitwise:
    aligned and ragged (non-128-multiple) tiles, the tied-head trans
    layout, shared-seed ±εz pairs and per-probe LeZO predicates."""
    M, K, N = shape
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, M, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N))
    seed = jnp.uint32(21)
    seeds = jnp.stack([seed, seed])
    scales = jnp.asarray([1e-3, -1e-3], jnp.float32)
    for active in (None, jnp.asarray([True, False])):
        got = fused_matmul.pmatmul_stack(x, w, seeds, scales, active,
                                         trans=trans, interpret=True,
                                         shared_seed=True)
        ref = fref.pmatmul_stack(x, w, seeds, fref._stack_scales(
            scales, active), trans=trans)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        for p in range(2):
            a = None if active is None else active[p]
            want = fused_matmul.pmatmul(x[p], w, seed, scales[p], a,
                                        trans=trans, interpret=True)
            assert np.array_equal(np.asarray(got[p]), np.asarray(want)), p


def test_paired_z_streams_match_axpy():
    """RNG contract of the pair (satellite): each sign's z stream is
    bit-identical to the materialized ``kernels/ops.zo_axpy`` stream —
    stacked per-layer leaves, the tied head's transposed counter window,
    and vector leaves with per-seed (unshared) streams."""
    key = jax.random.PRNGKey(2)
    step_seed = jnp.uint32(77)
    # stacked per-layer leaf under a LeZO mask: the paired view's
    # effective weight must equal the axpy result for both signs
    ws = jax.random.normal(key, (4, 24, 40))
    mask = jnp.asarray([1, 0, 1, 1], bool)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 6, 24))
    path = "stages/s0/b0/mix/wq"
    for l in range(4):
        lseed = fref.layer_seed(step_seed, path, l)
        got = fref.pmatmul_stack(
            x, ws[l], jnp.stack([lseed, lseed]),
            jnp.asarray([1e-3, -1e-3], jnp.float32),
            jnp.broadcast_to(mask[l], (2,)))
        for p, sign in enumerate((1.0, -1.0)):
            wm = kops.zo_axpy(ws, path=path, seed=step_seed,
                              scale=sign * 1e-3, mask=mask)
            assert np.array_equal(np.asarray(got[p]),
                                  np.asarray(x[p] @ wm[l])), (l, p)
    # tied head: trans counters over embed/tok.T
    tok = jax.random.normal(jax.random.fold_in(key, 1), (40, 24))
    h = jax.random.normal(jax.random.fold_in(key, 2), (2, 4, 24))
    lseed = fref.layer_seed(step_seed, "embed/tok")
    got = fref.pmatmul_stack(h, tok.T, jnp.stack([lseed, lseed]),
                             jnp.asarray([1e-3, -1e-3], jnp.float32),
                             trans=True, ld=24)
    for p, sign in enumerate((1.0, -1.0)):
        tokp = kops.zo_axpy(tok, path="embed/tok", seed=step_seed,
                            scale=sign * 1e-3)
        assert np.array_equal(np.asarray(got[p]),
                              np.asarray(h[p] @ tokp.T)), p
    # unshared per-seed streams (one_sided's stacked q probes)
    w = jax.random.normal(jax.random.fold_in(key, 4), (24, 40))
    seeds = jnp.stack([rng.fold(step_seed, jnp.uint32(c)) for c in (1, 2)])
    got = fref.pvec_stack(w, fref.layer_seed(seeds, "head/w"),
                          jnp.asarray([1e-3, 1e-3], jnp.float32))
    for p in range(2):
        wm = kops.zo_axpy(w, path="head/w", seed=seeds[p], scale=1e-3)
        assert np.array_equal(np.asarray(got[p]), np.asarray(wm)), p


@pytest.mark.parametrize("fb", ["virtual_ref", "virtual"])
def test_paired_loss_bitwise_matches_two_forwards(fb):
    """lm_loss under the paired ctx returns [l+, l-] bit-identical to
    the two sequential single-probe virtual forwards it replaces."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    seed = jnp.uint32(13)
    masks, _, _ = zo.stratified_select(spec, seed, 1)
    pair = lm.lm_loss_pair(mcfg, params, batch,
                           perturb=fused.make_pair_ctx(seed, 1e-3, masks,
                                                       fb))
    assert pair.shape == (2,)
    for i, sign in enumerate((1.0, -1.0)):
        ctx = fused.make_ctx(seed, sign * 1e-3, masks, fb)
        want = lm.lm_loss(mcfg, params, batch, perturb=ctx)
        assert np.array_equal(np.asarray(want), np.asarray(pair[i])), sign


def test_stacked_probes_bitwise_match_sequential():
    """make_stack_ctx (one_sided's q probes, unshared seeds) returns a
    (P,) loss vector bit-identical to P single-probe forwards."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    base = jnp.uint32(23)
    seeds = jnp.stack([rng.fold(base, jnp.uint32(c)) for c in range(3)])
    per = [zo.stratified_select(spec, s, 1)[0] for s in seeds]
    stacked = {g: jnp.stack([m[g] for m in per]) for g in per[0]}
    got = lm.lm_loss(mcfg, params, batch,
                     perturb=fused.make_stack_ctx(seeds, 1e-3, stacked,
                                                  "virtual_ref"))
    assert got.shape == (3,)
    for p in range(3):
        ctx = fused.make_ctx(seeds[p], 1e-3, per[p], "virtual_ref")
        want = lm.lm_loss(mcfg, params, batch, perturb=ctx)
        assert np.array_equal(np.asarray(want), np.asarray(got[p])), p


@pytest.mark.parametrize("name,q", [("two_point", 1), ("one_sided", 3),
                                    ("averaged", 2)])
def test_paired_step_bitwise_matches_unpaired(name, q):
    """The estimator acceptance gate: paired_probes=True produces the
    bit-identical step (params AND loss) to paired_probes=False on the
    virtual path — the pairing is a pure execution-schedule change."""
    mcfg = _tiny_cfg()
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=2, S=16)
    loss_fn = _loss_fn(mcfg)
    outs = {}
    for paired in (True, False):
        ecfg = estimators.EstimatorConfig(
            name=name, q=q, n_drop=1, lr=1e-4, eps=1e-3,
            forward_backend="virtual_ref", paired_probes=paired)
        step, init = estimators.make_step(loss_fn, spec, ecfg)
        outs[paired] = jax.jit(step)(params, init(), batch, jnp.int32(2),
                                     jnp.uint32(9))
    for a, b in zip(jax.tree.leaves(outs[True][0]),
                    jax.tree.leaves(outs[False][0])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(outs[True][2]["loss"]),
                          np.asarray(outs[False][2]["loss"]))


def test_paired_step_emits_forward_pair_span():
    """The eager staged step emits ONE forward_pair span (and no ±εz
    forward spans) when paired; the unpaired virtual step still emits
    the two forward spans — and the two schedules produce bit-identical
    steps (the fast-tier representative of the pairing gate; the jitted
    per-estimator matrix is tier-2)."""
    from repro import obs
    mcfg = _tiny_cfg(layers=1, d_model=32, vocab=64)
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    batch = _batch(mcfg.vocab, B=1, S=8)
    loss_fn = _loss_fn(mcfg)
    outs = {}
    for paired, want_pair in ((True, True), (False, False)):
        ring = obs.RingSink(64)
        tr = obs.Tracer(sinks=[ring])
        ecfg = estimators.EstimatorConfig(
            name="two_point", n_drop=0, forward_backend="virtual_ref",
            paired_probes=paired)
        step, init = estimators.make_step(loss_fn, spec, ecfg)
        with obs.use(tr):
            outs[paired] = jax.block_until_ready(
                step(params, init(), batch, jnp.int32(0), jnp.uint32(1)))
        names = {r.name for r in ring.records()}
        if want_pair:
            assert obs.FWD_PAIR in names
            assert obs.FWD_PLUS not in names and obs.FWD_MINUS not in names
        else:
            assert obs.FWD_PAIR not in names
            assert obs.FWD_PLUS in names and obs.FWD_MINUS in names
    for a, b in zip(jax.tree.leaves(outs[True][0]),
                    jax.tree.leaves(outs[False][0])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(outs[True][2]["loss"]),
                          np.asarray(outs[False][2]["loss"]))


def test_probe_accessor_and_pair_validation():
    """ProbePair plumbing: probe(i) peels one unpaired probe out of a
    paired ctx; lm_loss_pair insists on a paired ctx; probe() on an
    unpaired ctx is an error."""
    masks = {"g": jnp.asarray([True, False])}
    ctx = fused.make_pair_ctx(7, 1e-3, masks, "virtual_ref")
    for i, sign in enumerate((1.0, -1.0)):
        p = ctx.probe(i)
        assert p.pair is None
        np.testing.assert_allclose(float(p.scale), sign * 1e-3)
        assert p.masks["g"].shape == (2,)
    with pytest.raises(ValueError):
        fused.make_ctx(7, 1e-3, None, "virtual_ref").probe(0)
    mcfg = _tiny_cfg(layers=1, d_model=32, vocab=64)
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    batch = _batch(mcfg.vocab, B=1, S=8)
    with pytest.raises(ValueError):
        lm.lm_loss_pair(mcfg, params, batch,
                        perturb=fused.make_ctx(7, 1e-3, None, "virtual_ref"))
    with pytest.raises(ValueError):
        lm.lm_loss_pair(mcfg, params, batch, perturb=None)


def test_paired_structural_counters_halve():
    """The bench tripwire's claim at unit scope: counting the eager
    forward's grid cells (jax.disable_jit turns the layer scan into a
    Python loop so the lens counters actually fire), ONE paired forward
    loads half the W tiles and regenerates half the z tiles of the two
    probe forwards it replaces."""
    from repro import obs
    mcfg = _tiny_cfg(layers=2, d_model=32, vocab=64)
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    toks = _batch(mcfg.vocab, B=1, S=8)["tokens"]
    seed = jnp.uint32(5)

    def count(ctxs):
        tr = obs.Tracer()
        with obs.use(tr), jax.disable_jit():
            for ctx in ctxs:
                lm.forward(mcfg, params, toks, perturb=ctx)
        return (tr.counters[obs.CTR_WLOAD], tr.counters[obs.CTR_ZREGEN])

    pw, pz = count([fused.make_pair_ctx(seed, 1e-3, None, "virtual_ref")])
    uw, uz = count([fused.make_ctx(seed, 1e-3, None, "virtual_ref"),
                    fused.make_ctx(seed, -1e-3, None, "virtual_ref")])
    assert pw > 0 and 2 * pw == uw
    assert pz > 0 and 2 * pz == uz


def test_interpret_autodetects_platform():
    """interpret=None resolves per-platform: emulator off TPU, compiled
    on it — no kernel entry point defaults to interpret=True."""
    import inspect
    from repro import kernels
    from repro.kernels import flash_attn, zo_axpy
    assert kernels.default_interpret() == (jax.default_backend() != "tpu")
    assert kernels.resolve_interpret(None) == kernels.default_interpret()
    assert kernels.resolve_interpret(False) is False
    for fn in (fused_matmul.pmatmul, fused_matmul.pmatmul_stack,
               zo_axpy.zo_axpy_2d, flash_attn.flash_attention_pallas):
        sig = inspect.signature(inspect.unwrap(fn))
        assert sig.parameters["interpret"].default is None, fn


# -------------------------------------------------- trainer integration
def test_trainer_virtual_backend_trains():
    from repro.data import synthetic
    from repro.train.trainer import Trainer, TrainConfig

    mcfg = _tiny_cfg(d_model=32, vocab=128)
    task = synthetic.TaskConfig(vocab=128, seq_len=32, n_classes=2,
                                signal_rate=0.35)
    tr = Trainer(mcfg, task,
                 TrainConfig(steps=8, batch_size=4, eval_every=0,
                             log_every=2, forward_backend="virtual_ref"),
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1))
    assert tr.est_cfg.forward_backend == "virtual_ref"
    h = tr.train()
    assert np.isfinite(h["loss"]).all()


def test_trainer_virtual_guards():
    from repro.data import synthetic
    from repro.train.trainer import Trainer, TrainConfig

    mcfg = _tiny_cfg(d_model=32, vocab=128)
    task = synthetic.TaskConfig(vocab=128, seq_len=32, n_classes=2)
    with pytest.raises(ValueError, match="PEFT"):
        Trainer(mcfg, task, TrainConfig(peft="lora",
                                        forward_backend="virtual_ref"))
    with pytest.raises(ValueError, match="mode"):
        Trainer(mcfg, task, TrainConfig(mode="fo",
                                        forward_backend="virtual_ref"))
    moe_cfg = dataclasses.replace(
        mcfg, stages=(dataclasses.replace(
            mcfg.stages[0],
            pattern=(dataclasses.replace(mcfg.stages[0].pattern[0],
                                         ffn="moe"),)),),
        n_experts=4, top_k=2, moe_d_ff=64)
    with pytest.raises(ValueError, match="attn"):
        Trainer(moe_cfg, task, TrainConfig(forward_backend="virtual_ref"))
