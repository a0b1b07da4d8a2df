"""The DeepSeek-V2-Lite cell's own tests, on the CPU: the configuration
against the program's parameter tree and the published widths, the
operation counts, the plain reference against the program on seeded
weights, and the ``train_moe`` driver end to end at a tiny size, sound,
with its timed path broken, and with the reference's controls in the
program's place.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_moe_cell.py
"""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import cost_moe  # noqa: E402
import run  # noqa: E402
from drivers import train_moe  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402
from traffic import generate  # noqa: E402

CELL = "ft-dsv2lite-squad-lezo"
CFG = json.loads((HERE / "configs" / "deepseek-v2-lite-stage7.json").read_text())
# every width cut to CPU size; three layers keep the dense-then-MoE
# split, and eight experts with two a token keep the routing sparse
TINY_CFG = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
            "intermediate_size": 96, "moe_intermediate_size": 48,
            "n_routed_experts": 8, "num_experts_per_tok": 2,
            "n_shared_experts": 2, "num_hidden_layers": 3, "vocab_size": 512,
            "max_position_embeddings": 128,
            "assumed": {**CFG["assumed"], "dtype": "float32"}}
TINY = {"config": TINY_CFG,
        "traffic": {"batch": 4, "seq_len": 32, "prompt_tokens": [8, 24],
                    "answer_tokens": 4},
        "workload": {"limits": {"loss_gap_mean": 3e-3, "grad_gap": 3e-3,
                                "change_gap": 3e-3}},
        "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
SOUND, FAULT = 1e-4, 5e-3


def run_tiny(seed=2**35 + 1, **hooks):
    return run.run(["--workload", CELL, "--seed", str(seed), "--seconds", "2",
                    "--trace", "0"], stand_in=TINY, **hooks)


def tiny(**kw):
    return {**CFG, **TINY_CFG, **kw}


# ------------------------------------------------------------ the files
def test_config_resolves_to_published_widths():
    import jax
    from repro.configs import deepseek_v2_lite
    from repro.models import lm
    mcfg = train_moe.model_config(CFG)
    pub = deepseek_v2_lite.full()
    for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
              "kv_lora", "rope_head_dim", "rope_theta", "rope_scaling",
              "norm_eps", "n_experts", "n_shared_experts", "top_k",
              "moe_d_ff", "norm_topk_prob", "tie_embeddings", "dtype"):
        assert getattr(mcfg, k) == getattr(pub, k), k
    assert [(s.repeat, s.pattern) for s in mcfg.stages] == [
        (1, pub.stages[0].pattern), (6, pub.stages[1].pattern)]
    tree = jax.eval_shape(lambda: lm.init_params(mcfg, jax.random.PRNGKey(0)))
    train_moe.check_layout(CFG, tree)
    assert lm.count_params(tree) == 4_009_526_784


def test_cost_counts():
    # 13.76M MLA, 51.9M routed + 17.3M shared + 0.13M router a MoE token,
    # 67.2M in the dense layer's SwiGLU
    assert cost_moe.mla_params(CFG) == 13_762_560
    assert cost_moe.moe_token_params(CFG) == 69_337_088
    f = cost_moe.forward_flops(CFG, 16, 512, 128)
    assert f == pytest.approx(9.69e12, rel=0.01)
    assert cost_moe.expert_matmul_flops(CFG, 16, 512) == (
        2 * 2 * 6 * 8192 * 6 * 3 * 2048 * 1408)
    toy = {"hidden_size": 2, "num_attention_heads": 1, "kv_lora_rank": 1,
           "qk_nope_head_dim": 1, "qk_rope_head_dim": 1, "v_head_dim": 1,
           "intermediate_size": 3, "moe_intermediate_size": 1,
           "n_routed_experts": 2, "num_experts_per_tok": 1,
           "n_shared_experts": 1, "first_k_dense_replace": 1,
           "num_hidden_layers": 2, "vocab_size": 5}
    # MLA 4 + 4 + 1 + 1 + 2 = 12 a layer; dense 18; MoE 4 + 6 + 6 = 16
    # per token 2*12 + 18 + 16 = 58; attention 2*2*1*3*(2*3/2) = 36;
    # head 2*2*5 at one position = 20
    assert cost_moe.forward_flops(toy, 1, 2, 1) == 2 * 2 * 58 + 36 + 20


def test_squad_rows_take_the_loss_on_the_answer():
    mix = generate.load("squad-b16-s512")
    r = generate.rows(mix, CFG["vocab_size"], 2**40 + 9, 3)
    assert r["tokens"].shape == (16, 512)
    assert (r["loss_mask"].sum(1) == 8).all()
    # the answer the loss reads is a span of the prompt
    b = 0
    at = np.nonzero(r["loss_mask"][b])[0]
    answer = r["labels"][b, at]
    prompt = r["tokens"][b, :at[0]].tolist()
    assert any(prompt[i:i + 8] == answer.tolist() for i in range(len(prompt)))
    long = generate.load("squad-b4-s2048")
    r = generate.rows(long, 50272, 5, 0)
    assert r["tokens"].shape == (4, 2048) and (r["loss_mask"].sum(1) == 16).all()


# ------------------------------------------------------------ reference
def test_reference_matches_the_program_on_seeded_weights():
    """The program's loss and final hidden states at the benchmark's
    seeded weights against the plain reference, float32 at a tiny size
    (the driver tests compare the perturbed probes of both groups)."""
    import jax.numpy as jnp
    from repro.models import lm
    cfg = tiny()
    mcfg = train_moe.model_config(cfg)
    theta = ref.make_weights(cfg, 11)
    params = ref.to_tree(theta)
    rows = generate.rows({**generate.load("squad-b16-s512"),
                          **TINY["traffic"]}, cfg["vocab_size"], 11, 0)
    batch = {k: jnp.asarray(v) for k, v in rows.items()}
    h, _, _ = lm.forward(mcfg, params, batch["tokens"])
    want = ref.hidden(cfg, theta, batch["tokens"], "f32")
    np.testing.assert_allclose(np.asarray(h), np.asarray(want), atol=2e-5)
    assert float(lm.lm_loss(mcfg, params, batch)) == pytest.approx(
        ref.loss(cfg, theta, rows, "f32"), abs=1e-5)
    # fp8 operands part from it by far more
    assert abs(ref.loss(cfg, theta, rows, "fp8")
               - ref.loss(cfg, theta, rows, "f32")) > 1e-3


def test_reference_selection_is_the_programs():
    import jax.numpy as jnp
    from repro.core import zo
    from repro.models import lm
    for L, n_drop in ((7, 5), (27, 20)):
        cfg = tiny(num_hidden_layers=L)
        tree = ref.to_tree({p: jnp.zeros(s, dt) for p, s, dt, *_
                            in ref.layout(cfg) if p.startswith("stages/")})
        spec = zo.build_spec(tree, lm.zo_group_fn)
        for t in range(2 * L):
            seed = (2654435761 * t + 17) & 0xFFFFFFFF
            masks, _, _ = zo.stratified_select(spec, jnp.uint32(seed), n_drop,
                                               step=jnp.int32(t))
            got = {g: tuple(np.nonzero(np.asarray(m))[0]) for g, m in
                   masks.items()}
            assert got == ref.select(cfg, seed, t, n_drop)


@pytest.mark.parametrize("lr_g", [1e-5, 3e-4, 0.2])
def test_applied_steps_judge_every_stacked_write(lr_g):
    """The step's writes in bfloat16, each stored (three roundings, as
    the program's row sweeps store them), once rounded (the perturbed
    value kept in excess precision), and with the routed experts'
    update left out: every stacked leaf is judged at any scale, and
    there the update left out reads one update apart from the stored
    writes; an unstacked bfloat16 leaf is judged only at or above an
    ulp, where any order of roundings reads the update whole."""
    cfg = tiny(assumed={**CFG["assumed"], "dtype": "bfloat16"})
    seed, step_seed, e = 2**33 + 5, 12345, np.float32(1e-3)
    active = ref.select(cfg, step_seed, 0, 2)
    scales = [e, -2 * e, e - np.float32(lr_g)]
    stored, once, left_out = (ref.make_weights(cfg, seed) for _ in range(3))
    ref.write(cfg, stored, step_seed, active, scales)
    ref.write(cfg, once, step_seed, active, [-np.float32(lr_g)])
    ref.write(cfg, left_out, step_seed, active, scales,
              {n: [e, -2 * e, e] for n in train_moe.EXPERT_LEAVES})
    steps = [(step_seed, active, -lr_g)]
    a, b, c = (ref.applied_steps(cfg, seed, w, steps)
               for w in (stored, once, left_out))
    assert a.keys() == b.keys() == c.keys()
    hit = {prefix for g, prefix, *_ in ref.groups(cfg) if active[g]}
    written = {p for p, *_ in ref.layout(cfg)
               if not p.startswith("stages/") or p[:len(ref.DENSE)] in hit}
    bf16 = {p for p, _, dt, *_ in ref.layout(cfg) if dt == "bfloat16"}
    judged = {k.split("@")[0] for k in a}
    stacked = {p for p in written if p.startswith("stages/")}
    assert stacked <= judged <= written and written - bf16 <= judged
    assert (judged - stacked) & bf16 == (set() if lr_g < 1e-2
                                         else (written - stacked) & bf16)
    experts = {k for k in a if k.split("@")[0].endswith(train_moe.EXPERT_LEAVES)}
    assert experts
    for k in a:
        assert c[k] == pytest.approx(a[k] - (k in experts), abs=0.25), k
        if lr_g > 1e-2:
            assert a[k] == pytest.approx(1, abs=0.02), k
            assert b[k] == pytest.approx(1, abs=0.02), k
    unchanged = ref.applied_steps(cfg, seed, ref.make_weights(cfg, seed),
                                  steps)
    assert all(v == 0 for v in unchanged.values())
    none = train_moe.judged(cfg, [0.0], {}, {}, {"probes": [0.0],
                                                 "change1": {},
                                                 "change_last": {}})
    assert none["grad_gap"] == none["change_gap"] == 1


# ------------------------------------------------------------ the driver
def test_driver_runs_a_tiny_stand_in(capsys):
    run.main(["--workload", CELL, "--seed", str(2**35 + 1), "--seconds", "2",
              "--trace", "0"], stand_in=TINY)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(c["value"] < SOUND for c in out["checks"].values())


def test_the_control_is_not_correct():
    out = run_tiny(control=True)
    for kind in ("fp8", "half_batch", "expert_update_left_out"):
        assert out["control"][kind]["correct"] is False, kind
        assert max(c["value"] for c in out["control"][kind]["checks"].values()
                   ) >= FAULT, kind


def test_a_state_left_unchanged_is_not_correct(monkeypatch):
    from repro import estimators
    make = estimators.make_step

    def broken_make(*a, **kw):
        step, init = make(*a, **kw)

        def broken(params, state, batch, t, seed):
            _, state2, m = step(params, state, batch, t, seed)
            return params, state2, m
        return broken, init
    monkeypatch.setattr(estimators, "make_step", broken_make)
    out = run_tiny()
    assert out["correct"] is False
    assert out["checks"]["grad_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_experts_left_unupdated_in_bfloat16_are_not_correct(monkeypatch):
    """At bfloat16, where this cell's updates lie under an ulp of the
    expert weights, a program whose step leaves the routed experts'
    rows as they were reads apart from the reference by about one
    update, while a sound step's shares agree."""
    from repro import estimators
    bf16 = copy.deepcopy(TINY)
    bf16["config"]["assumed"] = {**CFG["assumed"], "dtype": "bfloat16"}
    bf16["workload"]["limits"] = {"loss_gap_mean": None, "grad_gap": None,
                                  "change_gap": None}

    def gaps():
        out = run.run(["--workload", CELL, "--seed", str(2**35 + 3),
                       "--seconds", "1", "--trace", "0"], stand_in=bf16)
        return out["checks"]["grad_gap"]["value"], \
            out["checks"]["change_gap"]["value"]
    assert max(gaps()) < 0.05
    make = estimators.make_step

    def broken_make(*a, **kw):
        step, init = make(*a, **kw)

        def broken(params, state, batch, t, seed):
            p2, state2, m = step(params, state, batch, t, seed)
            ffn = p2["stages"]["s1"]["b0"]["ffn"]
            for n in ("we_g", "we_u", "we_d"):
                ffn[n] = params["stages"]["s1"]["b0"]["ffn"][n]
            return p2, state2, m
        return broken, init
    monkeypatch.setattr(estimators, "make_step", broken_make)
    assert min(gaps()) > 0.5


def test_a_dropped_expert_is_not_correct(monkeypatch):
    """Expert 0's output left out of every MoE layer, as a capacity drop
    of all its tokens would."""
    from repro.models import moe
    fwd = moe.moe_fwd

    def dropping(cfg, p, x):
        return fwd(cfg, {**p, "we_d": p["we_d"].at[0].set(0)}, x)
    monkeypatch.setattr(moe, "moe_fwd", dropping)
    out = run_tiny()
    assert out["correct"] is False
    assert out["checks"]["loss_gap_mean"]["value"] >= FAULT


def test_metric_readers():
    r = {"cfg": CFG, "peak": {"bf16_flops_per_s": 197e12}, "chips": 1,
         "counts": {"steps": 10, "batch": 16, "seq_len": 512,
                    "head_positions": 1280},
         "trace": {"window_s": 5.0, "devices": 1},
         "scope_s": {"moe_dispatch": 0.1, "moe_experts": 0.5,
                   "moe_combine": 0.05}}
    read = lambda n: run._module(HERE / "metrics" / f"{n}.py", "m").read  # noqa: E731
    assert read("step_mfu.moe_train")(r) == pytest.approx(
        100 * 20 * cost_moe.forward_flops(CFG, 16, 512, 128) / (5 * 197e12))
    assert read("expert_matmul_roofline.moe_train")(r) == pytest.approx(
        100 * cost_moe.expert_matmul_flops(CFG, 160, 512) / (0.5 * 197e12))
    assert read("moe_dispatch_ms_per_step.moe_train")(r) == pytest.approx(15.0)
    r["scope_s"].update({"perturb": 0.8, "update_axpy": 0.4})
    assert read("sweep_ms_per_step.moe_train")(r) == pytest.approx(120.0)
    # a program without the scopes reads nothing
    none = copy.deepcopy(r)
    none["scope_s"] = {}
    assert read("expert_matmul_roofline.moe_train")(none) is None
    assert read("moe_dispatch_ms_per_step.moe_train")(none) is None
    assert read("sweep_ms_per_step.moe_train")(none) is None


def test_scope_of_an_operation():
    s = train_moe.scope_of
    assert s("jit(step)/forward+εz/while/body/closed_call/moe_experts/mul",
             "%fusion.3") == "moe_experts"
    assert s("jit(step)/forward-εz/while/body/closed_call/moe_dispatch/"
             "scatter-add", "%scatter.1") == "moe_dispatch"
    assert s("ragged-dot-none", "%ragged-dot-none.2") == "moe_experts"
    assert s("jit(step)/perturb/dynamic_update_slice", "%fusion.9") == "perturb"
    assert s("jit(step)/update_axpy/while/body/add", "%fusion.4") == \
        "update_axpy"
    assert s("jit(step)/forward+εz/while/body/dot_general", "%fusion.2") == ""
