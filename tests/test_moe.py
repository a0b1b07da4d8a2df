"""MoE routing invariants and drop-free dispatch vs a dense-expert oracle."""
import jax
import jax.numpy as jnp
import numpy as np

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
