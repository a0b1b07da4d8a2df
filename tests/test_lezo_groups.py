"""LeZO selection over layer groups of unequal size (a dense first layer
before a stack of MoE layers): every layer active at (N - n_drop) / N,
each group's count even over every window of steps, and single-group
specs unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _oracle
from repro import obs
from repro.core import zo
from repro.kernels import ops


def _spec(sizes):
    params = {"embed": jnp.zeros((4, 2)),
              "stages": {g: {"w": jnp.zeros((L, 3, 2)), "b": jnp.zeros((L, 2))}
                         for g, L in sizes.items()}}
    return params, zo.build_spec(params, lambda p: p.split("/")[1]
                                 if p.startswith("stages/") else None)


@pytest.mark.parametrize("sizes,n_drop", [({"s0": 1, "s1": 6}, 5),
                                          ({"s0": 1, "s1": 26}, 20)])
def test_every_layer_active_at_the_lezo_rate(sizes, n_drop):
    """Over N steps each group takes exactly its share of the drops, and
    over many seeds each layer of a group is active equally often: every
    layer's rate is (N - n_drop) / N, the leading layer's too."""
    _, spec = _spec(sizes)
    N = spec.num_layers
    sel = jax.jit(lambda seed, t: zo.stratified_select(spec, seed, n_drop,
                                                       step=t)[:2])
    hits = {g: np.zeros(L) for g, L in sizes.items()}
    steps = 4 * N
    for t in range(steps):
        for s in range(25):
            masks, idxs = sel(jnp.uint32(1000 * s + t), jnp.int32(t))
            for g in sizes:
                hits[g] += np.asarray(masks[g])
                idx, n = idxs[g]
                assert int(n) == int(np.asarray(masks[g]).sum())
                assert (np.asarray(idx)[int(n):] == sizes[g]).all()
    rate = (N - n_drop) / N
    for g, L in sizes.items():
        # the group's total is exact; the layers share it evenly
        assert hits[g].sum() == pytest.approx(25 * steps * L * rate)
        np.testing.assert_allclose(hits[g] / (25 * steps), rate, atol=0.06)
    assert hits["s0"].sum() > 0          # the dense first layer is trained


@pytest.mark.parametrize("sizes,n_drop", [({"s0": 1, "s1": 6}, 5),
                                          ({"s0": 1, "s1": 26}, 20),
                                          ({"s0": 3, "s1": 5, "s2": 9}, 11)])
def test_windows_hold_each_groups_expected_count(sizes, n_drop):
    """Every window of W steps holds each group's drops within +-1 of
    W * n_drop * L_g / N with two groups (+-2 with three), each group's
    share is exact over N steps, the step's total is always n_drop, and
    no group drops more layers than it has."""
    _, spec = _spec(sizes)
    N = spec.num_layers
    T = 3 * N
    d = {g: np.asarray([spec.quotas(n_drop, step=t)[g] for t in range(T)])
         for g in sizes}
    assert (sum(d.values()) == n_drop).all()
    slack = 1 if len(sizes) == 2 else 2
    for g, L in sizes.items():
        share = n_drop * L / N
        assert (d[g] <= L).all() and (d[g] >= 0).all()
        c = np.concatenate([[0], np.cumsum(d[g])])
        assert c[N] == pytest.approx(N * share)
        for W in range(1, T):
            win = c[W:] - c[:-W]
            assert (np.abs(win - W * share) <= slack + 1e-9).all(), (g, W)
    # the traced schedule is the same as the Python one
    traced = jax.jit(lambda t: spec.quotas(n_drop, step=t))
    for t in range(T):
        got = traced(jnp.int32(t))
        assert {g: int(v) for g, v in got.items()} == {
            g: int(d[g][t]) for g in sizes}


def test_disabled_slots_sweep_nothing():
    """Eager in-place sweeps touch only the enabled slots of each group
    (axpy_rows_swept), and leave dropped layers bit-identical."""
    params, spec = _spec({"s0": 1, "s1": 6})
    seed = jnp.uint32(9)
    tr = obs.Tracer()
    for t in range(7):
        masks, idxs, n_active = zo.stratified_select(spec, seed, 5,
                                                     step=jnp.int32(t))
        with obs.use(tr):
            tr.counters.clear()
            out = zo.tree_axpy(params, spec, seed, 1.0, idxs)
        # two stacked leaves a group, each its enabled rows
        assert tr.counters[obs.CTR_AXPY_ROWS] == 2 * n_active
        for g in ("s0", "s1"):
            moved = np.asarray(jnp.any(out["stages"][g]["w"] != 0, (1, 2)))
            assert np.array_equal(moved, np.asarray(masks[g]))


@pytest.mark.parametrize("n_drop", [0, 3, 6])
def test_uniform_select_slots_match_its_masks(n_drop):
    """The uniform policy's slots per group: its active layers ascending,
    padded to L_g with L_g, and the enabled count; the counts add up to
    the step's active layers."""
    _, spec = _spec({"s0": 1, "s1": 6})
    for seed in range(20):
        masks, idxs, n_active = zo.uniform_select(spec, jnp.uint32(seed),
                                                  n_drop)
        total = 0
        for g, (_, L) in spec.slices.items():
            vec, n = (np.asarray(x) for x in idxs[g])
            m = np.asarray(masks[g])
            assert vec.shape == (L,) and int(n) == m.sum()
            assert vec[:n].tolist() == np.flatnonzero(m).tolist()
            assert (vec[n:] == L).all()
            total += int(n)
        assert total == n_active == spec.num_layers - n_drop


_SELECT = {
    "stratified": lambda spec, s: zo.stratified_select(spec, s, 5,
                                                       step=jnp.int32(4)),
    "weighted": lambda spec, s: zo.stratified_select_weighted(
        spec, s, 5, jnp.arange(1.0, 8.0), step=jnp.int32(4)),
    "uniform": lambda spec, s: zo.uniform_select(spec, s, 5),
    "mezo": lambda spec, s: zo.stratified_select(spec, s, 0),
}


@pytest.mark.parametrize("policy", sorted(_SELECT))
def test_backends_agree_on_uneven_groups(policy):
    """The jitted row sweep over each policy's slots for a 1 + 6 layer
    stack matches the dense oracle."""
    params, spec = _spec({"s0": 1, "s1": 6})
    params = jax.tree.map(lambda x: x + 1.0, params)
    _, idxs, _ = _SELECT[policy](spec, jnp.uint32(3))
    want = _oracle.tree_axpy(params, spec, jnp.uint32(3), 0.5, idxs)
    got = jax.jit(lambda p, i: zo.tree_axpy(p, spec, jnp.uint32(3), 0.5,
                                            i))(params, idxs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# golden: the single-group selection before the uneven-group schedule
_GOLDEN = {0: ([0, 1, 2], [2, 3, 5]), 7: ([1, 3, 4], [3, 5, 9]),
           123456789: ([4, 8, 9], [1, 5, 7]),
           2 ** 31 + 5: ([1, 4, 8], [2, 3, 9])}


@pytest.mark.parametrize("seed", sorted(_GOLDEN))
def test_single_group_selection_is_unchanged(seed):
    """One group, 10 layers, 7 dropped: the masks and active indices of
    the uniform and weighted policies are the same bits as before, at
    every step."""
    _, spec = _spec({"s0": 10})
    w = jnp.asarray([0.5, 2.0, 1.0, 3.0, 0.1, 1.5, 0.7, 2.5, 0.2, 1.1])
    for step in (None, jnp.int32(0), jnp.int32(11)):
        m, i, n = zo.stratified_select(spec, jnp.uint32(seed), 7, step=step)
        mw, iw, nw = zo.stratified_select_weighted(spec, jnp.uint32(seed), 7,
                                                   w, step=step)
        assert np.asarray(i["s0"]).tolist() == _GOLDEN[seed][0]
        assert np.asarray(iw["s0"]).tolist() == _GOLDEN[seed][1]
        assert np.asarray(m["s0"]).sum() == 3 == n == nw
        assert np.asarray(mw["s0"])[_GOLDEN[seed][1]].all()


def test_rows_sweep_stops_at_the_enabled_count():
    theta = jnp.ones((4, 3), jnp.float32)
    idx = jnp.asarray([0, 2, 4], jnp.int32)          # slot 2 disabled
    out = ops.zo_axpy(theta, path="w", seed=jnp.uint32(1), scale=1.0,
                      active_idx=(idx, jnp.int32(2)))
    moved = np.asarray(jnp.any(out != 1.0, axis=1))
    assert moved.tolist() == [True, False, True, False]
