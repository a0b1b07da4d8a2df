"""Per-assigned-architecture smoke tests: reduced config, one forward +
one LeZO train step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import zo
from repro.data import synthetic
from repro.models import frontends, lm


@pytest.mark.parametrize("arch", configs.list_archs())
def test_arch_smoke(arch):
    cfg = configs.get(arch, "smoke")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"labels": toks, "loss_mask": jnp.ones((B, S))}
    if frontends.uses_embeds(cfg):
        batch["embeds"] = frontends.stub_embeddings(cfg, B, S)
    else:
        batch["tokens"] = toks

    # forward shapes + finiteness
    hidden, _, aux = lm.forward(cfg, params, batch.get("tokens"),
                                embeds=batch.get("embeds"), mode="train")
    assert hidden.shape == (B, S, cfg.d_model)
    assert bool(jnp.isfinite(hidden).all())
    logits = lm.logits_fn(cfg, params, hidden[:, -1])
    assert logits.shape == (B, cfg.vocab)

    # one LeZO train step
    spec = zo.build_spec(params, lm.zo_group_fn)
    n_drop = max(1, int(0.5 * spec.num_layers))
    step = jax.jit(zo.make_zo_step(
        lambda p, b: lm.lm_loss(cfg, p, b), spec,
        zo.ZOConfig(n_drop=n_drop, lr=1e-4)))
    p2, metrics = step(params, batch, jnp.int32(0), jnp.uint32(1))
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["active_layers"]) == spec.num_layers - n_drop
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)):
        assert bool(jnp.isfinite(a).all())


@pytest.mark.parametrize("arch", ["xlstm-350m", "jamba-v0.1-52b"])
def test_subquadratic_flag(arch):
    assert configs.get(arch).subquadratic


def test_full_configs_match_assignment():
    """Spot-check the exact assigned hyperparameters."""
    c = configs.get("deepseek-coder-33b")
    assert (c.num_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (62, 7168, 56, 8, 19200, 32256)
    c = configs.get("qwen3-14b")
    assert c.qk_norm and c.head_dim == 128 and c.vocab == 151936
    c = configs.get("deepseek-v2-lite-16b")
    assert c.kv_lora == 512 and c.top_k == 6 and c.n_shared_experts == 2
    c = configs.get("jamba-v0.1-52b")
    kinds = [b.kind for b in c.stages[0].pattern]
    assert kinds.count("attn") == 1 and kinds.count("mamba") == 7
    ffns = [b.ffn for b in c.stages[0].pattern]
    assert ffns.count("moe") == 4
    c = configs.get("granite-moe-1b-a400m")
    assert c.n_experts == 32 and c.top_k == 8 and c.moe_d_ff == 512
