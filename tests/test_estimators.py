"""Estimator subsystem: legacy equivalence, bias, invariants, backends.

The load-bearing test is bit-identity of ``two_point`` (through
``zo.make_zo_step``, now a shim over the subsystem) against an inline
copy of the pre-refactor step — the refactor must not move a single ulp.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import estimators
from repro.core import rng, zo
from repro.kernels import ref as kref


def _params():
    k = jax.random.PRNGKey(0)
    return {"embed": jax.random.normal(k, (40, 8)),
            "blocks": {"w": jax.random.normal(jax.random.fold_in(k, 1),
                                              (6, 16, 8)),
                       "b": jax.random.normal(jax.random.fold_in(k, 2),
                                              (6, 8))}}


def _spec(params):
    return zo.build_spec(params, lambda p: "blk" if p.startswith("blocks")
                         else None)


def _loss(p, batch):
    return 1e-3 * sum(jnp.sum(x ** 2) for x in jax.tree.leaves(p))


def _legacy_zo_step(loss_fn, spec, cfg):
    """Verbatim copy of the pre-refactor core/zo.py::make_zo_step body."""
    def step(params, batch, step_idx, base_seed):
        seed = rng.fold(jnp.asarray(base_seed, jnp.uint32),
                        jnp.asarray(step_idx, jnp.uint32))
        if cfg.policy == "stratified":
            masks, idxs, n_active = zo.stratified_select(spec, seed,
                                                         cfg.n_drop)
        else:
            masks, idxs, n_active = zo.uniform_select(spec, seed, cfg.n_drop)
        ax = lambda p, s, d=1.0: zo.tree_axpy(
            p, spec, seed, s, masks, idxs, decay=d,
            backend=cfg.backend)

        p = ax(params, cfg.eps)
        l_plus = loss_fn(p, batch)
        p = ax(p, -2.0 * cfg.eps)
        l_minus = loss_fn(p, batch)
        g = (l_plus - l_minus) / (2.0 * cfg.eps)
        lr = cfg.lr
        decay = 1.0 - lr * cfg.weight_decay
        if cfg.fused_update:
            p = ax(p, cfg.eps - lr * g, decay)
        else:
            p = ax(p, cfg.eps)
            p = ax(p, -lr * g, decay)
        metrics = {"loss": 0.5 * (l_plus + l_minus), "projected_grad": g,
                   "lr": lr, "active_layers": jnp.asarray(n_active,
                                                          jnp.int32)}
        return p, metrics

    return step


# ----------------------------------------------------- legacy equivalence
@pytest.mark.parametrize("backend", ["dense", "scan", "gather"])
@pytest.mark.parametrize("fused", [True, False])
def test_two_point_bit_identical_to_legacy(backend, fused):
    params = _params()
    spec = _spec(params)
    cfg = zo.ZOConfig(n_drop=2, lr=1e-3, weight_decay=0.1, backend=backend,
                      fused_update=fused)
    old = jax.jit(_legacy_zo_step(_loss, spec, cfg))
    new = jax.jit(zo.make_zo_step(_loss, spec, cfg))
    p_old, m_old = old(params, None, jnp.int32(3), jnp.uint32(9))
    p_new, m_new = new(params, None, jnp.int32(3), jnp.uint32(9))
    for a, b in zip(jax.tree.leaves(p_old), jax.tree.leaves(p_new)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for k in m_old:
        assert np.array_equal(np.asarray(m_old[k]), np.asarray(m_new[k])), k


def test_averaged_q1_matches_two_point():
    params = _params()
    spec = _spec(params)
    outs = []
    for name in ("two_point", "averaged"):
        ecfg = estimators.EstimatorConfig(name=name, q=1, n_drop=2, lr=1e-3,
                                          eps=1e-3)
        step, init = estimators.make_step(_loss, spec, ecfg)
        p, _, m = jax.jit(step)(params, init(), None, jnp.int32(2),
                                jnp.uint32(11))
        outs.append((p, m))
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    np.testing.assert_allclose(float(outs[0][1]["projected_grad"]),
                               float(outs[1][1]["projected_grad"]),
                               rtol=1e-5)


# ------------------------------------------------------- one-sided probes
def test_one_sided_bias_quadratic():
    """E[sum_i c_i z_i] over many steps ~= the true gradient of a
    quadratic (the one-sided Hessian term has zero odd moment)."""
    w = jnp.linspace(0.5, 1.5, 16)
    params = {"w": w}
    spec = zo.build_spec(params, lambda s: None)
    loss = lambda p, b: 0.5 * jnp.sum(p["w"] ** 2)   # grad = w
    q = 8
    ecfg = estimators.EstimatorConfig(name="one_sided", q=q, eps=1e-3,
                                      n_drop=0)
    est = estimators.build_estimator(spec, ecfg)
    uid = jnp.uint32(rng.leaf_uid("w"))

    @jax.jit
    def ghat(step_seed):
        _, dirs, _ = est.estimate(loss, params, None, step_seed, {})
        acc = jnp.zeros_like(w)
        for i in range(q):
            lseed = rng.fold(dirs.seeds[i], uid)
            z = kref.leaf_normal_nd(lseed, (1, 16))[0]
            acc = acc + dirs.coeffs[i] * z
        return acc

    total = np.zeros(16)
    steps = 250
    for t in range(steps):
        total += np.asarray(ghat(rng.fold(jnp.uint32(123), jnp.uint32(t))))
    mean = total / steps
    grad = np.asarray(w)
    cos = mean @ grad / (np.linalg.norm(mean) * np.linalg.norm(grad))
    assert cos > 0.97
    np.testing.assert_allclose(mean, grad, atol=0.2)


def test_one_sided_q_chunk_equivalent():
    """Chunked probe evaluation (bounded working set) is numerically the
    single-widened-forward path, same seeds and coefficients."""
    params = _params()
    spec = _spec(params)
    outs = []
    for chunk in (0, 2):
        ecfg = estimators.EstimatorConfig(name="one_sided", q=4,
                                          q_chunk=chunk, n_drop=2, lr=1e-3)
        step, init = estimators.make_step(_loss, spec, ecfg)
        p, _, m = jax.jit(step)(params, init(), None, jnp.int32(1),
                                jnp.uint32(6))
        outs.append(p)
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_one_sided_converges_quadratic():
    params = {"w": jnp.full((32,), 2.0)}
    spec = zo.build_spec(params, lambda s: None)
    loss = lambda p, b: 0.5 * jnp.sum(p["w"] ** 2)
    ecfg = estimators.EstimatorConfig(name="one_sided", q=8, eps=1e-3,
                                      lr=1e-2, n_drop=0)
    step, init = estimators.make_step(loss, spec, ecfg)
    step = jax.jit(step)
    p, st = params, init()
    l0 = float(loss(p, None))
    for t in range(200):
        p, st, m = step(p, st, None, jnp.int32(t), jnp.uint32(3))
    assert float(loss(p, None)) < 0.3 * l0


# ------------------------------------------------- importance / selection
def test_weighted_select_quota_invariants():
    params = _params()
    spec = _spec(params)
    quotas = spec.quotas(4)
    for t in range(25):
        wts = jax.random.uniform(jax.random.PRNGKey(t), (spec.num_layers,),
                                 minval=0.01, maxval=5.0)
        masks, idxs, n_active = zo.stratified_select_weighted(
            spec, jnp.uint32(t), 4, wts)
        assert n_active == spec.num_layers - 4
        for g, (start, L) in spec.slices.items():
            k = L - quotas[g]
            m = np.asarray(masks[g])
            ix = np.asarray(idxs[g])
            assert m.sum() == k == len(ix)
            assert np.array_equal(np.sort(ix), ix)          # ascending
            assert m[ix].all()                              # idxs <-> mask


def test_weighted_select_prefers_heavy_layers():
    params = _params()
    spec = _spec(params)
    wts = jnp.asarray([10.0, 10.0, 0.01, 0.01, 0.01, 0.01])
    counts = np.zeros(6)
    for t in range(200):
        masks, _, _ = zo.stratified_select_weighted(spec, jnp.uint32(t), 4,
                                                    wts)
        counts += np.asarray(masks["blk"])
    assert counts[:2].mean() > counts[2:].mean() * 2


def test_importance_state_adapts_and_stays_small():
    params = _params()
    spec = _spec(params)
    ecfg = estimators.EstimatorConfig(name="importance", inner="two_point",
                                      n_drop=2, lr=1e-3, eps=1e-3,
                                      importance_decay=0.5)
    step, init = estimators.make_step(_loss, spec, ecfg)
    step = jax.jit(step)
    p, st = params, init()
    for t in range(12):
        p, st, m = step(p, st, None, jnp.int32(t), jnp.uint32(4))
    imp = np.asarray(st["imp"])
    assert imp.shape == (spec.num_layers,)
    assert np.isfinite(imp).all()
    assert not np.allclose(imp, 1.0)        # EMA moved off the init
    # memory invariant: estimator state is O(num_layers) floats, never
    # anything parameter-shaped
    assert sum(x.size for x in jax.tree.leaves(st)) <= spec.num_layers + 8


@pytest.mark.parametrize("name,q", [("two_point", 1), ("one_sided", 4),
                                    ("averaged", 3), ("importance", 1)])
def test_state_is_o_q_scalars(name, q):
    params = _params()
    spec = _spec(params)
    ecfg = estimators.EstimatorConfig(name=name, q=q, n_drop=2)
    _, init = estimators.make_step(_loss, spec, ecfg)
    n = sum(x.size for x in jax.tree.leaves(init()))
    assert n <= spec.num_layers + q + 8
    # and the analytic cost table agrees with the implementation's claim
    est = estimators.build_estimator(spec, ecfg)
    counts = est.step_counts()
    assert counts == estimators.costs.step_counts(
        name, q=q, fused_update=True, inner="two_point",
        num_layers=spec.num_layers)


# ------------------------------------------------- cross-backend property
@pytest.mark.parametrize("backend", ["scan", "gather", "pallas"])
@pytest.mark.parametrize("name,q", [("two_point", 1), ("one_sided", 4),
                                    ("averaged", 2), ("importance", 1)])
def test_backend_matches_dense_per_estimator(name, q, backend):
    params = _params()
    spec = _spec(params)
    want = got = None
    for be in ("dense", backend):
        ecfg = estimators.EstimatorConfig(name=name, q=q, n_drop=2, lr=1e-3,
                                          eps=1e-3, backend=be)
        step, init = estimators.make_step(_loss, spec, ecfg)
        p, _, _ = jax.jit(step)(params, init(), None, jnp.int32(1),
                                jnp.uint32(5))
        if be == "dense":
            want = p
        else:
            got = p
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_dropped_layers_untouched_under_estimators():
    """No estimator may move a dropped layer (q=1 so exactly one subset)."""
    params = _params()
    spec = _spec(params)
    for name in ("two_point", "averaged", "one_sided"):
        ecfg = estimators.EstimatorConfig(name=name, q=1, n_drop=4, lr=1e-2)
        step, init = estimators.make_step(_loss, spec, ecfg)
        p, _, _ = jax.jit(step)(params, init(), None, jnp.int32(0),
                                jnp.uint32(5))
        seed = rng.fold(jnp.uint32(5), jnp.uint32(0))
        masks, _, _ = zo.stratified_select(spec, seed, 4)
        m = np.asarray(masks["blk"])
        w_moved = np.asarray(jnp.any(p["blocks"]["w"] != params["blocks"]["w"],
                                     axis=(1, 2)))
        assert np.array_equal(w_moved, m), name


# ----------------------------------------------------- cost-model bridge
def test_estimator_step_cost_projection():
    from repro.launch import analysis

    terms = {"compute_s": 1.0, "memory_s": 1.0, "collective_s": 0.5}
    same = analysis.estimator_step_cost(terms, "two_point")
    assert same["compute_s"] == 1.0 and same["memory_s"] == 1.0

    proj = analysis.estimator_step_cost(terms, "one_sided", q=16)
    assert proj["forwards"] == 17 and proj["axpy_sweeps"] == 32
    np.testing.assert_allclose(proj["compute_s"], 17 / 2)
    np.testing.assert_allclose(proj["collective_s"], 0.5 * 17 / 2)

    # with param_bytes, axpy sweeps are priced exactly: more sweeps =>
    # strictly more memory time than the pure forward-scaled projection
    # of a sweep-free graph
    pb = 819e9 / 4                      # 0.5 s per sweep at default bw
    withpb = analysis.estimator_step_cost(
        {"compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.0},
        "averaged", q=4, param_bytes=pb)
    # fwd_mem = 2.0 - 3*0.5 = 0.5 -> 0.5*(8/2) + 16*0.5 = 10.0
    np.testing.assert_allclose(withpb["memory_s"], 10.0)


# --------------------------------------------------- trainer integration
def test_trainer_selects_estimators():
    from repro.configs import opt
    from repro.data import synthetic
    from repro.train.trainer import Trainer, TrainConfig

    mcfg = opt.opt_tiny(layers=2, d_model=64, vocab=256)
    task = synthetic.TaskConfig(vocab=256, seq_len=32, n_classes=2,
                                signal_rate=0.35)
    for name, q in [("one_sided", 4), ("importance", 1)]:
        tr = Trainer(mcfg, task,
                     TrainConfig(steps=30, batch_size=8, eval_every=0,
                                 log_every=29, estimator=name, est_q=q),
                     zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1))
        h = tr.train()
        assert np.isfinite(h["loss"]).all(), name
        assert tr.est_cfg.name == name
