"""ZO optimizer invariants: restore identity, fused==unfused, determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import rng, selection, zo


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


def test_perturb_restore_identity():
    params = _params()
    spec = _spec(params)
    seed = jnp.uint32(11)
    _, idxs, _ = zo.stratified_select(spec, seed, 3)
    p = zo.tree_axpy(params, spec, seed, 1e-3, idxs)
    p = zo.tree_axpy(p, spec, seed, -2e-3, idxs)
    p = zo.tree_axpy(p, spec, seed, 1e-3, idxs)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("policy", ["stratified", "uniform", "mezo"])
def test_fused_equals_unfused(policy):
    params = _params()
    spec = _spec(params)
    kw = {"n_drop": 0} if policy == "mezo" else {"n_drop": 2,
                                                 "policy": policy}
    outs = []
    for fused in (True, False):
        cfg = zo.ZOConfig(lr=1e-3, fused_update=fused, **kw)
        step = jax.jit(zo.make_zo_step(_loss, spec, cfg))
        p, _ = step(params, None, jnp.int32(0), jnp.uint32(7))
        outs.append(p)
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_mezo_is_lezo_zero_drop():
    params = _params()
    spec = _spec(params)
    s0 = jax.jit(zo.make_zo_step(_loss, spec, zo.ZOConfig(n_drop=0)))
    p0, m0 = s0(params, None, jnp.int32(1), jnp.uint32(3))
    assert int(m0["active_layers"]) == spec.num_layers
    # every layer moved
    moved = np.asarray(jnp.any(p0["blocks"]["w"] != params["blocks"]["w"],
                               axis=(1, 2)))
    assert moved.all()


def test_dropped_layers_untouched():
    params = _params()
    spec = _spec(params)
    seed = jnp.uint32(5)
    masks, idxs, _ = zo.stratified_select(spec, rng.fold(seed, jnp.uint32(0)),
                                          4)
    cfg = zo.ZOConfig(n_drop=4, lr=1e-2)
    step = jax.jit(zo.make_zo_step(_loss, spec, cfg))
    p, _ = step(params, None, jnp.int32(0), seed)
    m = np.asarray(masks["blk"])
    w_moved = np.asarray(jnp.any(p["blocks"]["w"] != params["blocks"]["w"],
                                 axis=(1, 2)))
    assert np.array_equal(w_moved, m)
    # embed is always-on
    assert bool(jnp.any(p["embed"] != params["embed"]))


def test_step_deterministic_replay():
    params = _params()
    spec = _spec(params)
    cfg = zo.ZOConfig(n_drop=2, lr=1e-3)
    step = jax.jit(zo.make_zo_step(_loss, spec, cfg))
    a, _ = step(params, None, jnp.int32(4), jnp.uint32(9))
    b, _ = step(params, None, jnp.int32(4), jnp.uint32(9))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@given(st.integers(1, 23), st.integers(0, 2**31))
@settings(max_examples=30, deadline=None)
def test_uniform_selection_count(n_drop, seed):
    active = selection.uniform_active(jnp.uint32(seed), 24, n_drop)
    assert int(active.sum()) == 24 - n_drop


@given(st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_selection_coverage(seed):
    """over many steps every layer is active sometimes (full-param tuning)."""
    hits = np.zeros(12, bool)
    for t in range(60):
        s = rng.fold(jnp.uint32(seed), jnp.uint32(t))
        hits |= np.asarray(selection.uniform_active(s, 12, 9))
    assert hits.all()


def test_quota_apportionment():
    params = {"a": {"w": jnp.ones((21, 2))}, "b": {"w": jnp.ones((3, 2))}}
    spec = zo.build_spec(params, lambda p: p.split("/")[0])
    # 18 drops over 21 + 3 layers: 15.75 and 2.25 a step, the leftover
    # one placed by the step index
    qs = [spec.quotas(18, step=t) for t in range(24)]
    for q in qs:
        assert sum(q.values()) == 18
        assert q["a"] in (15, 16) and q["b"] in (2, 3)
    assert sum(q["b"] for q in qs) == 24 * 2.25


def test_round_robin_policy():
    act0 = selection.round_robin_active(0, 8, 6)
    act1 = selection.round_robin_active(1, 8, 6)
    assert int(act0.sum()) == 2 and int(act1.sum()) == 2
    assert not np.array_equal(np.asarray(act0), np.asarray(act1))


def test_weighted_policy_prefers_heavy():
    w = jnp.asarray([10.0] * 4 + [0.01] * 12)
    counts = np.zeros(16)
    for t in range(200):
        act = selection.weighted_active(jnp.uint32(t), w, 12)
        counts += np.asarray(act)
    assert counts[:4].mean() > counts[4:].mean() * 2
