"""Operations a DeepSeek-V2-style (MLA + MoE) training step requires,
from the shapes alone, as ``cost.py`` counts them for OPT: what the
algorithm needs, not what the program happens to execute.

Per token and layer: the MLA projections (q, the latent down-projection,
the latent's key and value up-projections, the output); causal attention
over (nope + rope)-wide scores and v-wide values, query p seeing p + 1
keys; then a dense SwiGLU in the leading dense layers, or in a MoE layer
the router, ``num_experts_per_tok`` routed experts and the shared
experts.  The untied head runs only at the positions whose loss is
taken.  Norms, rope, softmax and the routing's sort are not FLOPs here.

``cfg`` is a configuration file of ``chipbench/configs`` as a dict.
"""
from __future__ import annotations

from typing import Dict


def mla_params(cfg: Dict) -> int:
    """Matmul weights of one MLA block."""
    D, H, R = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (D * H * (dn + dr) + D * (R + dr) + R * H * dn + R * H * dv
            + H * dv * D)


def swiglu_params(cfg: Dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def expert_token_params(cfg: Dict) -> int:
    """Routed-expert weights one token's assignments read."""
    return cfg["num_experts_per_tok"] * swiglu_params(
        cfg, cfg["moe_intermediate_size"])


def moe_token_params(cfg: Dict) -> int:
    """Matmul weights one token reads in a MoE layer's FFN: the router,
    its routed experts and the shared experts."""
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + expert_token_params(cfg)
            + swiglu_params(cfg, cfg["n_shared_experts"]
                            * cfg["moe_intermediate_size"]))


def layers(cfg: Dict):
    """(dense layers, MoE layers) of the configuration."""
    n_dense = cfg["first_k_dense_replace"]
    return n_dense, cfg["num_hidden_layers"] - n_dense


def forward_flops(cfg: Dict, batch: int, seq: int, head_positions: int) -> float:
    """One training forward over (batch, seq)."""
    n_dense, n_moe = layers(cfg)
    L = n_dense + n_moe
    per_token = (L * mla_params(cfg)
                 + n_dense * swiglu_params(cfg, cfg["intermediate_size"])
                 + n_moe * moe_token_params(cfg))
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 2.0 * batch * L * H * (qk + cfg["v_head_dim"]) * seq * (seq + 1) / 2
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_positions
    return 2.0 * batch * seq * per_token + attn + head


def zo_step_flops(cfg: Dict, batch: int, seq: int, head_positions: int,
                  probes: int = 2) -> float:
    """A ZO step needs one forward per probe (two for two_point)."""
    return probes * forward_flops(cfg, batch, seq, head_positions)


def expert_matmul_flops(cfg: Dict, batch: int, seq: int,
                        probes: int = 2) -> float:
    """The routed experts' grouped matmuls of one step: every token's
    assignments in every MoE layer of every probe."""
    return 2.0 * probes * layers(cfg)[1] * batch * seq * expert_token_params(cfg)
