"""MoE routing invariants and drop-free dispatch vs a dense-expert oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe
from repro.models.config import BlockCfg, ModelConfig, StageCfg


def _cfg(E=4, k=2, **kw):
    return ModelConfig(
        name="m", d_model=16, n_heads=2, n_kv_heads=2, d_ff=0, vocab=32,
        stages=(StageCfg(1, (BlockCfg("attn", "moe"),)),), n_experts=E,
        top_k=k, moe_d_ff=8, dtype="float32", max_seq=32, **kw)


def _dense_oracle(cfg, p, x):
    """Compute every expert for every token, combine with router weights."""
    from repro.models import layers
    B, S, D = x.shape
    h = layers.apply_norm(cfg, p["norm"], x)
    logits = h.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    w, e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    g = jnp.einsum("bsd,edf->bsef", h, p["we_g"])
    u = jnp.einsum("bsd,edf->bsef", h, p["we_u"])
    o = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, p["we_d"])
    full_w = jnp.zeros(probs.shape).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], e].set(w)
    return jnp.einsum("bse,bsed->bsd", full_w, o)


def test_dispatch_matches_dense_oracle():
    cfg = _cfg()
    p = moe.moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    got, _ = moe.moe_fwd(cfg, p, x)
    want = _dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_capacity_drop_bounded():
    """No assignment drops under skew: a router that sends every token
    to expert 0 (its load is every token, far past any capacity) still
    matches the oracle, and the balance loss reads the skew."""
    cfg = _cfg()
    p = moe.moe_params(cfg, jax.random.PRNGKey(0))
    p["router"] = p["router"].at[:, 0].add(100.0)
    # positive inputs: every token's expert-0 logit gains 100 * sum(h)
    x = 1.0 + jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16)))
    got, aux = moe.moe_fwd(cfg, p, x)
    want = _dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    assert float(aux) > 1.5          # balanced routing reads about 1


def test_single_token_never_drops():
    """Decode groups (S=1) route every assignment."""
    cfg = _cfg()
    p = moe.moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 16))
    got, _ = moe.moe_fwd(cfg, p, x)
    want = _dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_shared_experts_added():
    cfg = _cfg().with_(n_shared_experts=1)
    p = moe.moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    with_shared, _ = moe.moe_fwd(cfg, p, x)
    p2 = {k: v for k, v in p.items() if not k.startswith("ws_")}
    cfg2 = cfg.with_(n_shared_experts=0)
    without, _ = moe.moe_fwd(cfg2, p2, x)
    assert float(jnp.abs(with_shared - without).max()) > 1e-6


def test_unnormalized_topk_weights():
    """norm_topk_prob=False (DeepSeek-V2-Lite) weights each expert by its
    plain softmax probability; the oracle follows the same field."""
    cfg = _cfg(k=3, norm_topk_prob=False)
    p = moe.moe_params(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 16))
    got, _ = moe.moe_fwd(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_oracle(cfg, p, x)),
                               atol=1e-4, rtol=1e-4)
    renorm, _ = moe.moe_fwd(cfg.with_(norm_topk_prob=True), p, x)
    assert float(jnp.abs(renorm - got).max()) > 1e-3


def test_routed_counter_counts_every_assignment():
    from repro import obs
    cfg = _cfg()
    p = moe.moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    tr = obs.Tracer()
    with obs.use(tr):
        moe.moe_fwd(cfg, p, x)
        jax.jit(lambda a: moe.moe_fwd(cfg, p, a))(x)   # traced: no count
    assert tr.counters[obs.CTR_MOE_ROUTED] == 2 * 8 * cfg.top_k


def test_dispatch_stays_on_each_data_shard():
    """With the batch sharded over 'data' (four host devices, a 2 x 2
    mesh), each shard routes its own rows: the output and the balance
    loss are the whole-batch dispatch's, and nothing under the MoE
    scopes gathers across shards."""
    import subprocess
    import sys
    code = r"""
import os, re
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed import ctx
from repro.launch.mesh import make_mesh
from repro.models import moe
from repro.models.config import BlockCfg, ModelConfig, StageCfg
cfg = ModelConfig(name='m', d_model=16, n_heads=2, n_kv_heads=2, d_ff=0,
                  vocab=32, stages=(StageCfg(1, (BlockCfg('attn', 'moe'),)),),
                  n_experts=4, top_k=2, moe_d_ff=8, dtype='float32', max_seq=32)
p = moe.moe_params(cfg, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
f = jax.jit(lambda p, x: moe.moe_fwd(cfg, p, x))
want = f(p, x)
mesh = make_mesh((2, 2), ('data', 'model'))
ctx.set_mesh(mesh)
xs = jax.device_put(x, NamedSharding(mesh, P('data')))
g = jax.jit(lambda p, x: moe.moe_fwd(cfg, p, x))
got = g(p, xs)
for a, b in zip(got, want):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
txt = g.lower(p, xs).compile().as_text()
ag = [ln for ln in txt.splitlines()
      if re.search(r'= [^ ]+ all-gather(-start)?[(]', ln)
      and re.search(r'moe_(dispatch|experts|combine)', ln)]
assert not ag, ag[:2]
print('OK')
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]


# -- the grouped matmul: megablox gmm (interpreted) against lax.ragged_dot

_GMM_CASES = {
    # name: (m, k, n, group sizes, (tm, tk, tn), dtype)
    "empty_groups": (256, 128, 128, (0, 100, 0, 156, 0), (128, 128, 128),
                     "float32"),
    "one_group": (256, 128, 128, (0, 0, 256, 0), (128, 128, 128), "float32"),
    "bounds_on_tiles": (384, 128, 256, (128, 0, 256, 0), (128, 128, 128),
                        "float32"),
    "bounds_off_tiles": (384, 128, 256, (37, 90, 129, 128), (128, 128, 256),
                         "float32"),
    "irregular_k_n": (256, 384, 384, (60, 120, 76), (128, 256, 256),
                      "float32"),
    "bf16_in_f32_out": (256, 256, 128, (17, 0, 200, 39), (128, 128, 128),
                        "bfloat16"),
}


def _gmm_case(case):
    m, k, n, sizes, t, dt = _GMM_CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    lhs = jax.random.normal(ka, (m, k)).astype(dt)
    rhs = (jax.random.normal(kb, (len(sizes), k, n)) * k ** -0.5).astype(dt)
    return lhs, rhs, sizes, t


@pytest.mark.parametrize("case", sorted(_GMM_CASES))
def test_grouped_matmul_kernel_matches_ragged_dot(case, monkeypatch):
    """With each case's tiling given by the rule, ``grouped_matmul``
    runs the kernel; its f32 output equals ``lax.ragged_dot``'s, and
    the eager counter reads the rows the kernel computes (megablox's
    own count of visited m-tiles x tm)."""
    import importlib
    from repro import obs
    from repro.kernels import gmm
    lhs, rhs, sizes, t = _gmm_case(case)
    m, n = lhs.shape[0], rhs.shape[2]
    monkeypatch.setattr(gmm, "tiling", lambda *shape: t)
    tr = obs.Tracer()
    with obs.use(tr):
        got = gmm.grouped_matmul(lhs, rhs, sizes, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    _, tiles = mb.make_group_metadata(
        group_sizes=sizes, m=m, tm=t[0], start_group=jnp.int32(0),
        num_nonzero_groups=len(sizes), visit_empty_groups=False)
    assert tr.counters[obs.CTR_MOE_GMM_ROWS] == int(tiles) * t[0]
    assert gmm.rows_computed(sizes, t[0]) == int(tiles) * t[0]


@pytest.mark.parametrize("case", ["bounds_off_tiles", "bf16_in_f32_out"])
def test_grouped_matmul_kernel_gradient_is_ragged_dots(case, monkeypatch):
    """A first-order gradient through the kernel (interpreted) equals
    ``lax.ragged_dot``'s, in the inputs' dtypes: the backward is
    ragged_dot's VJP, not megablox's, whose tgmm reuses the forward's
    tiling."""
    from repro.kernels import gmm
    lhs, rhs, sizes, t = _gmm_case(case)
    monkeypatch.setattr(gmm, "tiling", lambda *shape: t)
    cot = jax.random.normal(jax.random.PRNGKey(3),
                            (lhs.shape[0], rhs.shape[2]))

    def loss(mm):
        return lambda a, w: jnp.sum(mm(a, w) * cot)

    got = jax.grad(loss(lambda a, w: gmm.grouped_matmul(
        a, w, sizes, interpret=True)), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, w: jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32)),
        argnums=(0, 1))(lhs, rhs)
    for g, w, x in zip(got, want, (lhs, rhs)):
        assert g.dtype == x.dtype and g.shape == x.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=1e-4, rtol=1e-4)


def test_grouped_matmul_falls_back_where_no_tiling_fits():
    """Shapes no tiling fits (a width off the 128-lane tile, as the tiny
    configs' moe_d_ff 48; rows no m-tile divides; an expert block over
    the scoped VMEM, as jamba's 4096 x 14336) and the default off a TPU
    run ``lax.ragged_dot`` itself, and count nothing; the cell's shapes
    and granite's keep each expert's whole block; a mesh that leaves
    the call to the partitioner keeps the kernel off."""
    from jax.sharding import AbstractMesh
    from repro import obs
    from repro.distributed import ctx
    from repro.kernels import gmm
    assert gmm.tiling(256, 64, 48) is None
    assert gmm.tiling(100, 128, 128) is None
    assert gmm.tiling(16384, 4096, 14336) is None
    assert gmm.tiling(49152, 2048, 1408, 4) is None        # f32 block
    assert gmm.tiling(49152, 2048, 1408) == (128, 2048, 1408)
    assert gmm.tiling(49152, 1408, 2048) == (128, 1408, 2048)
    assert gmm.tiling(6144, 1024, 512) == (128, 1024, 512)
    sizes = jnp.asarray([3, 0, 2], jnp.int32)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 48))
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    tr = obs.Tracer()
    with obs.use(tr):
        for interpret in (True, None):
            got = gmm.grouped_matmul(lhs, rhs, sizes, interpret=interpret)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert obs.CTR_MOE_GMM_ROWS not in tr.counters
    try:
        assert ctx.device_local()
        ctx.set_mesh(AbstractMesh((1, 1), ("data", "model")))
        assert ctx.device_local()
        ctx.set_mesh(AbstractMesh((4, 1), ("data", "model")))
        assert not ctx.device_local()        # outside per_batch_shard
    finally:
        ctx.set_mesh(None)
