"""Pallas flash-attention forward kernel (TPU target).

The forward hot spot of every assigned dense arch.  Grid =
(batch*kv_heads*q_groups, Sq/BQ); each program owns one (BQ, dh) query
tile and scans the key/value sequence in (BK, dh) tiles held in VMEM,
maintaining the usual running (m, l, acc) in f32.  Causal masking skips
fully-masked key tiles via ``pl.when`` — real predication, matching the
lax.cond skip of the jnp reference (models.layers.flash_attention, which
remains the production path under pjit; this kernel is the single-core
TPU tile schedule for it).

Layout choices: q/k/v arrive flattened to (BH, S, dh) with BH =
B*KV*G; dh padded to a multiple of 128 by the wrapper (ops-level
contract) so the MXU matmul dims are hardware-aligned.

Kernels of the ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, nk, causal, kv_repeat):
    qi = pl.program_id(1)
    q = q_ref[0].astype(F32)                       # (BQ, dh)
    dh = q.shape[-1]
    scale = dh ** -0.5
    m = jnp.full((bq,), NEG_INF, F32)
    l = jnp.zeros((bq,), F32)
    acc = jnp.zeros((bq, dh), F32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * bk, bk), :].astype(F32)   # (BK, dh)
        v = v_ref[0, pl.ds(ki * bk, bk), :].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        if causal:
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk),
                                                       1)[0]
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        return m_new, l_new, acc_new

    if causal:
        # only key tiles up to the diagonal contribute
        nk_needed = jnp.minimum(nk, (qi + 1) * bq // bk + 1)
    else:
        nk_needed = nk
    m, l, acc = jax.lax.fori_loop(0, nk_needed, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, bq=128, bk=128,
                           interpret=None):
    """q: (BH, Sq, dh), k/v: (BH, Sk, dh) with q already GQA-expanded
    (BH = B*KV*G and k/v repeated per group by the caller/ops wrapper).
    dh should be a multiple of 128 for MXU alignment (any value works in
    interpret mode).  ``interpret=None`` follows the platform."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nk = Sk // bk
    grid = (BH, Sq // bq)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          kv_repeat=1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def flash_attention_ref(q, k, v, *, causal=True):
    """Pure-jnp oracle in the kernel's (BH, S, dh) layout."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(F32), k.astype(F32))
    s = s * (q.shape[-1] ** -0.5)
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        msk = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(msk[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(F32)).astype(q.dtype)
