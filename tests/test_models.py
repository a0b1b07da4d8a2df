"""Model substrate correctness: attention oracle, cache consistency,
chunked-vs-sequential recurrences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers, lm, ssm
from repro.models.config import BlockCfg, ModelConfig, StageCfg, dense_lm

F32 = jnp.float32


def naive_attention(q, k, v, causal=True):
    B, S, KV, G, dh = q.shape
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * dh ** -0.5
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


@pytest.mark.parametrize("S,qc,kc", [(64, 16, 16), (64, 64, 8), (96, 32, 32)])
def test_flash_matches_naive(S, qc, kc):
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (2, S, 2, 3, 8), F32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, S, 2, 8), F32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, S, 2, 8), F32)
    got = layers.flash_attention(q, kk, v, q_chunk=qc, k_chunk=kc)
    want = naive_attention(q, kk, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_key_padding_with_prefix_offset():
    """k_offset<0 (prefix tokens) + non-divisible Sk exercises padding."""
    k = jax.random.PRNGKey(1)
    S, P = 32, 5
    q = jax.random.normal(k, (1, S, 1, 2, 8), F32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (1, S + P, 1, 8), F32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (1, S + P, 1, 8), F32)
    got = layers.flash_attention(q, kk, v, k_offset=-P, q_chunk=16, k_chunk=16)
    # oracle: prefix rows always visible
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kk) * 8 ** -0.5
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S + P)[None, :] - P
    mask = qpos >= kpos
    s = jnp.where(mask[None, None, None], s, -1e30)
    want = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _decode_check(cfg, S=16, tol=5e-5):
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, S)), jnp.int32)
    h, _, _ = lm.forward(cfg, params, toks, mode="train")
    want = lm.logits_fn(cfg, params, h[:, -1])
    _, caches = lm.prefill(cfg, params, toks[:, :S - 1], max_seq=S + 2)
    got, _ = lm.serve_step(cfg, params, caches, toks[:, S - 1:S],
                           jnp.int32(S - 1))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol * max(scale, 1.0))


def test_decode_consistency_dense():
    _decode_check(dense_lm("d", 2, 64, 4, 2, 128, 256, qk_norm=True,
                           dtype="float32", max_seq=64))


def test_decode_consistency_mla():
    _decode_check(ModelConfig(
        name="m", d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
        stages=(StageCfg(2, (BlockCfg("mla", "dense"),)),), kv_lora=32,
        rope_head_dim=8, d_head=16, dtype="float32", max_seq=64))


def test_decode_consistency_mla_yarn():
    """Decode through the latent cache agrees with the train forward
    under DeepSeek-V2-Lite's YaRN rope, softmax scale and norm eps."""
    from repro.configs.deepseek_v2_lite import YARN
    _decode_check(ModelConfig(
        name="m", d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
        stages=(StageCfg(2, (BlockCfg("mla", "dense"),)),), kv_lora=32,
        rope_head_dim=8, d_head=16, rope_scaling=YARN, norm_eps=1e-6,
        dtype="float32", max_seq=64))


def test_yarn_frequencies_follow_the_published_formula():
    """DeepSeek-V2-Lite's 64 rope dims, written out from the YaRN
    definition: dims 0-9 keep base^(-2i/d), dims 23-31 take it over 40,
    a linear ramp between; cos/sin factor 1, softmax factor mscale^2."""
    import math
    from repro.configs.deepseek_v2_lite import YARN
    d, base = 64, 10000.0
    extra = base ** (-np.arange(0, d, 2) / d)
    # correction dims: d ln(4096 / (2 pi rot)) / (2 ln base), rot 32 and 1
    lo = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(base)))
    hi = math.ceil(d * math.log(4096 / (2 * math.pi)) / (2 * math.log(base)))
    assert (lo, hi) == (10, 23)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(layers.rope_freqs(d, base, YARN)),
                               want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert layers.yarn_mscale(40.0, 0.707) ** 2 == pytest.approx(1.5896,
                                                                 abs=1e-4)
    # equal mscale and mscale_all_dim: the rotation keeps its length
    x = jnp.ones((1, 3, 1, d))
    y = layers.rope(x, jnp.arange(3), base, YARN)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(y * y, -1)), np.full((1, 3, 1), d), rtol=1e-5)


def test_norm_eps_is_the_configs():
    cfg = dense_lm("d", 1, 8, 2, 2, 16, 32, dtype="float32", norm_eps=1e-2)
    x = jnp.full((1, 1, 8), 0.1)
    y = layers.apply_norm(cfg, {"scale": jnp.ones((8,))}, x)
    np.testing.assert_allclose(np.asarray(y), 0.1 / np.sqrt(0.01 + 0.01),
                               rtol=1e-5)


def test_decode_consistency_mamba():
    _decode_check(ModelConfig(
        name="mm", d_model=64, n_heads=4, n_kv_heads=4, d_ff=0, vocab=256,
        stages=(StageCfg(2, (BlockCfg("mamba", "none"),)),),
        dtype="float32", max_seq=64))


def test_decode_consistency_xlstm():
    _decode_check(ModelConfig(
        name="x", d_model=64, n_heads=4, n_kv_heads=4, d_ff=0, vocab=256,
        stages=(StageCfg(2, (BlockCfg("mlstm", "none"),
                             BlockCfg("slstm", "none"))),),
        dtype="float32", max_seq=64))


def test_decode_consistency_moe_dropless():
    # MoE dispatch never drops an assignment, so decode matches train
    _decode_check(ModelConfig(
        name="moe", d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
        stages=(StageCfg(2, (BlockCfg("attn", "moe"),)),), n_experts=4,
        top_k=2, moe_d_ff=32, dtype="float32", max_seq=64))


def test_multi_step_decode_matches_train():
    cfg = dense_lm("d", 2, 64, 4, 2, 128, 256, dtype="float32", max_seq=64)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    S = 12
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, S)),
                       jnp.int32)
    h, _, _ = lm.forward(cfg, params, toks, mode="train")
    _, caches = lm.prefill(cfg, params, toks[:, :4], max_seq=S + 2)
    for t in range(4, S):
        got, caches = lm.serve_step(cfg, params, caches, toks[:, t:t + 1],
                                    jnp.int32(t))
        want = lm.logits_fn(cfg, params, h[:, t])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_mamba_chunk_invariance():
    """Chunked scan result independent of chunk size."""
    B, S, Di, St = 2, 64, 8, 4
    k = jax.random.PRNGKey(0)
    dA = jax.nn.sigmoid(jax.random.normal(k, (B, S, Di, St)))
    dBx = jax.random.normal(jax.random.fold_in(k, 1), (B, S, Di, St))
    C = jax.random.normal(jax.random.fold_in(k, 2), (B, S, St))
    h0 = jnp.zeros((B, Di, St))
    outs = []
    for chunk in (8, 64):
        ssm.CHUNK, old = chunk, ssm.CHUNK
        y, hf = ssm._ssm_chunk_scan(dA, dBx, C, h0)
        ssm.CHUNK = old
        outs.append((y, hf))
    np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(outs[1][0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[0][1]), np.asarray(outs[1][1]),
                               atol=1e-5)


def test_mlstm_chunk_invariance():
    """Chunkwise mLSTM == step-by-step recurrence (chunk=1 vs chunk=32)."""
    B, S, H, dh = 1, 64, 2, 8
    k = jax.random.PRNGKey(3)
    q = jax.random.normal(k, (B, S, H, dh))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, dh))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, H, dh))
    li = jax.random.normal(jax.random.fold_in(k, 3), (B, S, H))
    lf = jax.nn.log_sigmoid(jax.random.normal(jax.random.fold_in(k, 4),
                                              (B, S, H)) + 2.0)
    state = (jnp.zeros((B, H, dh, dh)), jnp.zeros((B, H, dh)),
             jnp.full((B, H), -jnp.inf))

    def run(chunk):
        st = state
        hs = []
        for i in range(0, S, chunk):
            h, st = ssm._mlstm_chunk(q[:, i:i + chunk], kk[:, i:i + chunk],
                                     v[:, i:i + chunk], li[:, i:i + chunk],
                                     lf[:, i:i + chunk], st)
            hs.append(h)
        return jnp.concatenate(hs, 1), st

    h1, st1 = run(1)
    h32, st32 = run(32)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h32), atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1[0]), np.asarray(st32[0]),
                               rtol=2e-4, atol=2e-4)


def test_chunked_ce_matches_dense():
    cfg = dense_lm("d", 1, 32, 2, 2, 64, 128, dtype="float32", max_seq=64)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 64
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, 32))
    y = jnp.asarray(np.random.default_rng(0).integers(0, 128, (B, S)))
    m = jnp.asarray(np.random.default_rng(1).random((B, S)) > 0.5, F32)
    got = lm.chunked_ce(cfg, params, h, y, m)
    lg = lm.logits_fn(cfg, params, h)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, y[..., None], -1)[..., 0]
    want = jnp.sum((lse - gold) * m) / jnp.sum(m)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def _ce_case(seed=0):
    cfg = dense_lm("d", 1, 32, 2, 2, 64, 128, dtype="float32", max_seq=64)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    B, S = 2, 64
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, 32))
    y = jnp.asarray(np.random.default_rng(seed).integers(0, 128, (B, S)))
    return cfg, params, h, y


def _dense_ce(cfg, params, h, y, m):
    """The head over every position, then masked: the loss head's oracle."""
    lg = lm.logits_fn(cfg, params, h)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, y[..., None], -1)[..., 0]
    return jnp.sum((lse - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)


def _tail_mask(kind, B=2, S=64):
    m = np.zeros((B, S), np.float32)
    if kind == "last":
        m[:, -1] = 1
    elif kind == "span8":
        m[:, -8:] = 1
    elif kind == "row_varying":
        m[0, -3:] = 1
        m[1, -13:-2] = 1
    elif kind == "early":            # one entry outside the last S/2
        m[:, -1] = 1
        m[1, 5] = 1
    return jnp.asarray(m)


@pytest.mark.parametrize("kind,width", [("last", 8), ("span8", 8),
                                        ("row_varying", 16), ("early", 64),
                                        ("zero", 8)])
def test_windowed_ce_matches_dense(kind, width):
    """The head runs over the narrowest trailing window holding the mask
    (an early entry falls to the whole sequence), with the dense loss."""
    from repro import obs
    cfg, params, h, y = _ce_case()
    m = _tail_mask(kind)
    assert lm.head_widths(64) == (8, 16, 32, 64)
    tr = obs.Tracer()
    with obs.use(tr):
        got = lm.chunked_ce(cfg, params, h, y, m)
    assert tr.counters[obs.CTR_LOSS_HEAD_ROWS] == 2 * width
    jitted = jax.jit(lambda h, y, m: lm.chunked_ce(cfg, params, h, y, m))
    for loss in (got, jitted(h, y, m)):
        np.testing.assert_allclose(float(loss),
                                   float(_dense_ce(cfg, params, h, y, m)),
                                   rtol=1e-5, atol=1e-7)
    if kind == "early":              # the top rung is the whole sequence
        top = len(lm.head_widths(64)) - 1
        assert int(lm.head_rung(m)) == top
        assert np.array_equal(
            np.asarray(got),
            np.asarray(lm.chunked_ce(cfg, params, h, y, m, rung=top)))


def test_windowed_ce_grad_matches_dense():
    """``jax.grad`` through the switch: the windowed head's gradient in
    the head matrix and the hidden rows equals the dense head's, and the
    differentiated program keeps its conditional."""
    cfg, params, h, y = _ce_case(3)
    m = _tail_mask("span8")
    f = lambda p, h: lm.chunked_ce(cfg, p, h, y, m)
    got = jax.grad(f, argnums=(0, 1))(params, h)
    want = jax.grad(lambda p, h: _dense_ce(cfg, p, h, y, m),
                    argnums=(0, 1))(params, h)
    assert float(jnp.max(jnp.abs(got[1][:, :-8]))) == 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert "cond[" in str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(
        params, h))
