"""Compile rehearsals of the main path for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a *described* chip: these tests lower each Pallas kernel
at real OPT widths (and the MoE experts' grouped matmul at
DeepSeek-V2-Lite's) for one v5e core and assert that Mosaic accepted it
(a ``tpu_custom_call`` in the compiled HLO).  They catch what interpret
mode cannot — casts Mosaic refuses, block shapes that break the (8, 128)
tiling, kernels that overflow VMEM — at no chip time.  The parameter
sweeps are checked the same way for what the CPU cannot show:
that the TPU compiler updates the active rows of a donated stacked leaf
in place, with no fill or copy of the whole stack.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers all import this file.  Nothing runs here; a compile that
passes is not a chip run.
"""
import os
import re
from collections import Counter

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import estimators
from repro.core import zo
from repro.fused import matmul as fused_matmul
from repro.kernels import ops
from repro.models import lm
from repro.models.config import dense_lm


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e host of four chips (no chip is attached)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler is not installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles land in the persistent cache but can
    # never be read back without the chip: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pmatmul_stack_opt13b_ffn(one_chip):
    """The paired +-eps probe over OPT-13B's up-projection."""
    P, M, K, N = 2, 2048, 5120, 20480

    def f(x, w, seeds, scales):
        return fused_matmul.pmatmul_stack(x, w, seeds, scales,
                                          interpret=False, shared_seed=True)

    _assert_mosaic(f, _spec((P, M, K), jnp.bfloat16, one_chip),
                   _spec((K, N), jnp.bfloat16, one_chip),
                   _spec((P,), jnp.uint32, one_chip),
                   _spec((P,), jnp.float32, one_chip))


def test_pmatmul_tied_head(one_chip):
    """The tied LM head: hidden (K=5120) against embed/tok read through
    a transpose, N = vocab 50272 (padded to the lane tile)."""
    M, K, N = 2048, 5120, 50272

    def f(x, w_t, seed, scale):
        return fused_matmul.pmatmul(x, w_t, seed, scale, trans=True, ld=K,
                                    interpret=False)

    _assert_mosaic(f, _spec((M, K), jnp.bfloat16, one_chip),
                   _spec((K, N), jnp.bfloat16, one_chip),
                   _spec((), jnp.uint32, one_chip),
                   _spec((), jnp.float32, one_chip))


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_dsv2lite_experts(one_chip, k, n):
    """The MoE cell's grouped matmuls (16 x 512 tokens x top-6 = 49152
    rows over DeepSeek-V2-Lite's 64 experts) with the swept tiling:
    Mosaic accepts the blocks and they fit the scoped VMEM."""
    from repro.kernels import gmm
    m, E = 49152, 64

    def f(lhs, rhs, sizes):
        return gmm.grouped_matmul(lhs, rhs, sizes, interpret=False)

    _assert_mosaic(f, _spec((m, k), jnp.bfloat16, one_chip),
                   _spec((E, k, n), jnp.bfloat16, one_chip),
                   _spec((E,), jnp.int32, one_chip))


def _kernel_calls(hlo):
    """The compiled HLO's Pallas custom calls (the grouped matmul's
    ``op_name`` ends ``jit(gmm)/pallas_call``, ``jvp(jit(gmm))/...``
    under a VJP); XLA's own ragged dot lowers to ``ragged-dot-*``."""
    return [ln for ln in hlo.splitlines()
            if "custom-call(" in ln and re.search(r'op_name="[^"]*pallas_call"', ln)]


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_dsv2lite_experts_vjp(one_chip, k, n):
    """A first-order gradient through the cell's grouped matmuls
    compiles for one v5e: the forward is the kernel and the backward
    ``lax.ragged_dot``'s (megablox's own backward runs tgmm with the
    forward's whole-block tiling, which overflows the scoped VMEM)."""
    from repro.kernels import gmm
    m, E = 49152, 64

    def f(lhs, rhs, sizes, g):
        out, vjp = jax.vjp(lambda a, w: gmm.grouped_matmul(
            a, w, sizes, interpret=False), lhs, rhs)
        return (out,) + vjp(g)

    hlo = jax.jit(f).lower(_spec((m, k), jnp.bfloat16, one_chip),
                           _spec((E, k, n), jnp.bfloat16, one_chip),
                           _spec((E,), jnp.int32, one_chip),
                           _spec((m, n), jnp.float32, one_chip)
                           ).compile().as_text()
    assert len(_kernel_calls(hlo)) == 1
    assert "ragged-dot" in hlo


@pytest.mark.parametrize("shape,axes,kernels", [
    ((4,), ("data",), 3),
    ((4, 1), ("data", "model"), 3),
    ((2, 2), ("data", "model"), 0),
], ids=["data4", "data4_model1", "data2_model2"])
def test_moe_grouped_matmul_on_data_shards(v5e_2x2, monkeypatch, shape,
                                           axes, kernels):
    """The MoE FFN on a described four-chip host, batch over 'data' and
    the weights replicated: where every axis beside 'data' has size 1,
    each data shard runs the three grouped matmuls as the kernel inside
    ``per_batch_shard``; where 'model' splits the experts, XLA's ragged
    dot.  Nothing under the MoE scopes gathers across devices."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import kernels as kernel_mod
    from repro.distributed import ctx
    from repro.models import moe
    from repro.models.config import BlockCfg, ModelConfig, StageCfg
    monkeypatch.setattr(kernel_mod, "default_interpret", lambda: False)
    cfg = ModelConfig(name="m", d_model=256, n_heads=2, n_kv_heads=2,
                      d_ff=0, vocab=32,
                      stages=(StageCfg(1, (BlockCfg("attn", "moe"),)),),
                      n_experts=8, top_k=2, moe_d_ff=256, dtype="bfloat16",
                      max_seq=64)
    params = jax.eval_shape(lambda: moe.moe_params(cfg,
                                                   jax.random.PRNGKey(0)))
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(shape), axes)
    rep = NamedSharding(mesh, P())
    ps = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), params)
    xs = jax.ShapeDtypeStruct((8, 64, 256), jnp.bfloat16,
                              sharding=NamedSharding(mesh, P("data")))
    ctx.set_mesh(mesh)
    try:
        hlo = jax.jit(lambda p, x: moe.moe_fwd(cfg, p, x)).lower(
            ps, xs).compile().as_text()
    finally:
        ctx.set_mesh(None)
    assert len(_kernel_calls(hlo)) == kernels
    assert ("ragged-dot" in hlo) == (kernels == 0)
    ag = [ln for ln in hlo.splitlines()
          if re.search(r"= [^ ]+ all-gather(-start)?[(]", ln)
          and re.search(r"moe_(dispatch|experts|combine)", ln)]
    assert not ag, ag[:2]


def _ops_of_shapes(hlo, shapes):
    """Count the compiled HLO's instructions by opcode whose result is
    (or, for a tuple, holds) one of ``shapes``, e.g. ``bf16[10,5120,20480]``."""
    found = Counter()
    for m in re.finditer(r"^\s*(?:ROOT )?%\S+ = (.+?) ([a-z][a-z0-9-]*)\(",
                         hlo, re.M):
        if any(sh + "{" in m.group(1) for sh in shapes):
            found[m.group(2)] += 1
    return found


_WHOLE_LEAF = ("broadcast", "copy", "copy-start", "copy-done")
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32"}


def _hlo_shape(x):
    return (f"{_HLO_DTYPE[jnp.dtype(x.dtype).name]}"
            f"[{','.join(map(str, x.shape))}]")


def test_scan_sweep_updates_active_rows_in_place(one_chip):
    """One LeZO sweep over OPT-13B's stacked up-projection, 10 layers of
    5120 x 20480 bf16, k = 3 active rows, the leaf donated: no fill or
    copy of the stack, and less scratch than one layer row."""
    L, k, shape = 10, 3, (5120, 20480)

    def sweep(theta, aidx, seed, scale):
        return ops.zo_axpy(theta, path="w", seed=seed, scale=scale,
                           active_idx=aidx)

    leaf = _spec((L,) + shape, jnp.bfloat16, one_chip)
    compiled = jax.jit(sweep, donate_argnums=0).lower(
        leaf, _spec((k,), jnp.int32, one_chip),
        _spec((), jnp.uint32, one_chip),
        _spec((), jnp.float32, one_chip)).compile()
    found = _ops_of_shapes(compiled.as_text(), [_hlo_shape(leaf)])
    assert found["dynamic-update-slice"] >= 1       # the in-place row write
    assert not any(found[op] for op in _WHOLE_LEAF), found
    row_bytes = 5120 * 20480 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < row_bytes


def _aot_step_ops(one_chip, **ecfg):
    """Compile a whole materialized LeZO step of a 10-layer bf16 model
    with the params donated; -> (ops on stacked-leaf shapes, number of
    stacked leaves)."""
    mcfg = dense_lm("opt-aot", 10, 256, 4, 4, 1024, 512, act="relu",
                    norm="ln", pos_emb="learned", tie_embeddings=True,
                    max_seq=128)
    params = jax.eval_shape(lambda: lm.init_params(mcfg,
                                                   jax.random.PRNGKey(0)))
    spec = zo.build_spec(params, lm.zo_group_fn)
    step, init = estimators.make_step(
        lambda p, b, perturb=None: lm.lm_loss(mcfg, p, b, perturb=perturb),
        spec, estimators.EstimatorConfig(name="two_point", n_drop=7,
                                         **ecfg))
    on_chip = lambda x: _spec(x.shape, x.dtype, one_chip)
    B, S = 2, 16
    batch = {"tokens": _spec((B, S), jnp.int32, one_chip),
             "labels": _spec((B, S), jnp.int32, one_chip),
             "loss_mask": _spec((B, S), jnp.float32, one_chip)}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        jax.tree.map(on_chip, params),
        jax.tree.map(on_chip, jax.eval_shape(init)), batch,
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.uint32, one_chip)).compile()
    stacked = [leaf for leaf, g in zip(jax.tree.leaves(params), spec.groups)
               if g is not None]
    return (_ops_of_shapes(compiled.as_text(),
                           {_hlo_shape(leaf) for leaf in stacked}),
            len(stacked))


def test_two_point_step_sweeps_in_place(one_chip):
    """A whole materialized LeZO step (perturb, probe, -2eps sweep,
    probe, update), params donated: each sweep writes the buffer the
    probe before it read, with no copy of any stacked leaf between them
    and no fresh stacked buffer."""
    found, n_stacked = _aot_step_ops(one_chip)
    # three sweeps, each one in-place row write per stacked leaf
    assert found["dynamic-update-slice"] == 3 * n_stacked, found
    assert not any(found[op] for op in _WHOLE_LEAF), found


def test_uniform_step_sweeps_in_place(one_chip):
    """The same under the paper's uniform policy, whose slots carry a
    traced count: the sweeps stay in place."""
    found, n_stacked = _aot_step_ops(one_chip, policy="uniform")
    assert found["dynamic-update-slice"] == 3 * n_stacked, found
    assert not any(found[op] for op in _WHOLE_LEAF), found


def test_dsv2lite_stage_step_sweeps_experts_in_place(one_chip):
    """The ``lezo-dsv2lite`` step at the benchmark stage's widths
    (DeepSeek-V2-Lite's dense layer 0 and six MoE layers, batch 16 x 512
    tokens, params donated): every expert slab (6 x 64 x 2048 x 1408
    bf16) is updated in place, one row write per sweep, with no copy or
    fill of its stacked shape, and the step fits one v5e."""
    from repro.configs import deepseek_v2_lite
    from repro.models.config import StageCfg
    full = deepseek_v2_lite.full()
    mcfg = full.with_(stages=(full.stages[0],
                              StageCfg(6, full.stages[1].pattern)))
    params = jax.eval_shape(lambda: lm.init_params(mcfg,
                                                   jax.random.PRNGKey(0)))
    spec = zo.build_spec(params, lm.zo_group_fn)
    step, init = estimators.make_step(
        lambda p, b, perturb=None: lm.lm_loss(mcfg, p, b, perturb=perturb),
        spec, estimators.EstimatorConfig(name="two_point", n_drop=5))
    on_chip = lambda x: _spec(x.shape, x.dtype, one_chip)
    B, S = 16, 512
    batch = {"tokens": _spec((B, S), jnp.int32, one_chip),
             "labels": _spec((B, S), jnp.int32, one_chip),
             "loss_mask": _spec((B, S), jnp.float32, one_chip)}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        jax.tree.map(on_chip, params),
        jax.tree.map(on_chip, jax.eval_shape(init)), batch,
        _spec((), jnp.int32, one_chip),
        _spec((), jnp.uint32, one_chip)).compile()
    moe = params["stages"]["s1"]["b0"]["ffn"]
    experts = {_hlo_shape(moe[k]) for k in ("we_g", "we_u", "we_d")}
    assert len(experts) == 2          # we_g and we_u share a shape
    found = _ops_of_shapes(compiled.as_text(), experts)
    assert found["dynamic-update-slice"] == 3 * 3, found
    assert not any(found[op] for op in _WHOLE_LEAF), found
    mem = compiled.memory_analysis()
    # AOT: arguments 8,020,831,232 B, temp 5,310,749,696 B
    assert mem.argument_size_in_bytes == 8_020_831_232
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
