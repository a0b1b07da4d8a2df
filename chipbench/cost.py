"""Operations and bytes the work requires, from the shapes alone.

Every count here is what the algorithm needs, not what the program
happens to execute: attention counts its causal half, the loss head only
the positions whose loss is taken, a prompt's logits only where a token
is produced.  Work a program does beyond this (a head over every
position, a whole page table gathered for a short context, an arena
copied per call) is waste, and shows as a lower share of the peak.

``cfg`` is a configuration file of ``chipbench/configs`` as a dict.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2
F32 = 4


def dims(cfg: Dict) -> Dict[str, int]:
    return {"D": cfg["hidden_size"], "F": cfg["ffn_dim"],
            "H": cfg["num_attention_heads"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "P": cfg["max_position_embeddings"]}


def layer_matmul_params(cfg: Dict) -> int:
    """q, k, v, o and the two MLP projections of one layer."""
    d = dims(cfg)
    return 4 * d["D"] * d["D"] + 2 * d["D"] * d["F"]


def layer_params(cfg: Dict) -> int:
    """One layer: projections plus two LayerNorms (scale and bias)."""
    return layer_matmul_params(cfg) + 4 * dims(cfg)["D"]


def shared_params(cfg: Dict) -> int:
    """Token table (tied to the head), positions and the final norm."""
    d = dims(cfg)
    return d["V"] * d["D"] + d["P"] * d["D"] + 2 * d["D"]


def n_params(cfg: Dict) -> int:
    return dims(cfg)["L"] * layer_params(cfg) + shared_params(cfg)


def weight_bytes(cfg: Dict) -> int:
    """Bytes a forward reads once: bf16 matrices and tables, f32 norms."""
    d = dims(cfg)
    mats = d["L"] * layer_matmul_params(cfg) + d["V"] * d["D"]
    norms = (d["L"] * 4 + 2) * d["D"]
    return mats * BF16 + norms * F32


# ------------------------------------------------------------- training
def forward_flops(cfg: Dict, batch: int, seq: int, head_positions: int) -> float:
    """One training forward over (batch, seq): the layers' projections at
    every position, causal attention (query p sees p + 1 keys: QK^T and
    PV are 2*D*(p+1) FLOPs each per layer), and the tied head only at
    the ``head_positions`` whose loss is taken."""
    d = dims(cfg)
    proj = 2.0 * batch * seq * d["L"] * layer_matmul_params(cfg)
    attn = 2.0 * batch * d["L"] * d["D"] * seq * (seq + 1)
    head = 2.0 * d["D"] * d["V"] * head_positions
    return proj + attn + head


def zo_step_flops(cfg: Dict, batch: int, seq: int, head_positions: int,
                  probes: int = 2) -> float:
    """A ZO step needs one forward per probe (two for two_point)."""
    return probes * forward_flops(cfg, batch, seq, head_positions)


# -------------------------------------------------------------- serving
def token_flops(cfg: Dict, position: int, head: bool) -> float:
    """One token at 0-based ``position``: projections, attention over
    position + 1 keys, and the head where a token is produced."""
    d = dims(cfg)
    return (2.0 * d["L"] * layer_matmul_params(cfg)
            + 4.0 * d["L"] * d["D"] * (position + 1)
            + (2.0 * d["D"] * d["V"] if head else 0.0))


def prefill_flops(cfg: Dict, start: int, n: int, final: bool) -> float:
    """Prompt tokens start .. start + n - 1 of one lane; the head runs
    only for the last prompt token, which produces the first output."""
    return sum(token_flops(cfg, p, final and p == start + n - 1)
               for p in range(start, start + n))


def decode_flops(cfg: Dict, positions: Iterable[int]) -> float:
    """One decode step: one token for each live lane at ``positions``."""
    return sum(token_flops(cfg, p, True) for p in positions)


def kv_bytes_per_position(cfg: Dict) -> int:
    d = dims(cfg)
    return 2 * d["L"] * d["D"] * BF16


def decode_bytes(cfg: Dict, positions: Iterable[int]) -> float:
    """One decode step: the weights once, and for each live lane the K/V
    of its real context (positions 0 .. p - 1) read and its new K/V
    written.  A whole page table gathered, or an arena copied, is
    beyond this."""
    kv = kv_bytes_per_position(cfg)
    return weight_bytes(cfg) + sum((p + 1) * kv for p in positions)
