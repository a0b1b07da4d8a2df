"""The benchmark's own tests, on the CPU: the yardstick (trace reduction,
operation and byte counts, peaks), every cell's files, and each driver
run end to end at a tiny size through the same code, sound and with its
timed path broken.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import cost  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import trace as trace_mod  # noqa: E402
from reference import opt as ref  # noqa: E402
from traffic import generate  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
# cells whose files are kept but that BENCHMARK.json does not hold yet
# (PERF.md, Open questions): their drivers are rehearsed all the same
HELD = {"serve-opt1.3b-chat": {
    "name": "serve-opt1.3b-chat", "config": "opt-1.3b",
    "traffic": "chat-poisson", "chips": 1,
    "why": "paged engine, open-loop Poisson chat (held out)"}}
ALL_CELLS = CELLS + sorted(HELD)
TINY_CFG = {"hidden_size": 64, "ffn_dim": 128, "num_attention_heads": 4,
            "num_hidden_layers": 4, "vocab_size": 512,
            "max_position_embeddings": 128,
            "assumed": {"dtype": "float32", "projection_bias": False}}
TINY_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# a sound tiny float32 run reads at most ~1e-4; a fault or the control,
# 5e-3 or more (the float8 control's mean loss gap reads about 9e-3)
SOUND, FAULT = 1e-3, 5e-3
TINY_ROWS = {"sst2-b16-s128": {"batch": 4, "seq_len": 32,
                               "prompt_tokens": [4, 20], "label_ids": [5, 6]}}
# the chip's limits are set from bfloat16 readings at full size (PERF.md);
# the float32 stand-in is judged between its own sound and faulty readings
TINY_LIMITS = {"loss_gap_mean": 3e-3, "grad_gap": 3e-3, "change_gap": 3e-3}
TINY_SERVE = {
    "traffic": {"rate_per_s": 8.0, "ramp_s": 1.0,
                "prompt_tokens": {"median": 20, "sigma": 0.8, "min": 4, "max": 90},
                "output_tokens": {"median": 8, "sigma": 0.7, "min": 2, "max": 30},
                "max_total_tokens": 128},
    "workload": {"engine": {"page_size": 16, "n_pages": 64, "max_lanes": 4,
                            "prefill_chunk": 32, "max_seq": 128,
                            "max_new_tokens": 8, "temperature": 0.0,
                            "top_k": 0, "prefix_cache": False},
                 "drain_s": 30, "sample_tokens": 40}}


def stand_in(cell: str):
    entry = {w["name"]: w for w in BENCH["workloads"]}.get(cell) or HELD[cell]
    held = {"entry": entry} if cell in HELD else {}
    if entry["traffic"] in TINY_ROWS:
        return {"config": TINY_CFG, "traffic": TINY_ROWS[entry["traffic"]],
                "workload": {"limits": TINY_LIMITS}, "peaks": TINY_PEAK, **held}
    return {"config": TINY_CFG, "peaks": TINY_PEAK, **TINY_SERVE, **held}


def bench_with(cell: str):
    return ({**BENCH, "workloads": BENCH["workloads"] + [HELD[cell]]}
            if cell in HELD else BENCH)


def run_tiny(cell: str, seed: int = 2**33 + 7, **hooks):
    return run.run(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                    "--trace", "0"], stand_in=stand_in(cell), **hooks)


def limits(cell: str):
    return json.loads((HERE / "workloads" / f"{cell}.json").read_text())["limits"]


# ------------------------------------------------------------ the files
def test_benchmark_json_keeps_the_contract():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    assert set(BENCH) == {"command", "paths", "run_seconds", *keys}
    for sec, want in keys.items():
        names = [e["name"] for e in BENCH[sec]]
        assert len(names) == len(set(names))
        for e in BENCH[sec]:
            assert set(e) - {"workloads"} == want, (sec, e["name"])
            assert name.match(e["name"])
            for k in ("why", "layer", "source"):
                assert k not in e or 1 <= len(e[k]) <= 200
            if "unit" in e:
                assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", e["unit"])
                assert e["better"] in ("lower", "higher")
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_every_cell_names_files_that_exist():
    for w in BENCH["workloads"]:
        c = run.Cell(BENCH, w["name"])
        assert c.cfg and c.mix and hasattr(c.driver, "run")
        assert c.end_to_end and c.per_layer
        assert "setup_s" in {m["name"] for m in c.end_to_end}
        for m in c.per_layer:
            reader = run._module(HERE / "metrics" / f"{m['name']}.py", "r")
            assert (reader.UNIT, reader.SOURCE, reader.MOVES) == (
                m["unit"], m["source"], m["moves"])
        # every number a driver compares has its limit's key
        assert set(limits(w["name"])) >= (
            {"loss_gap_mean", "grad_gap", "change_gap"}
            if c.workload["driver"] == "train" else {"token_gap", "unfinished"})
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["source"] == json.loads((ROOT / c["file"]).read_text())["source"]
        assert set(c["reduced"]) == set(
            json.loads((ROOT / c["file"]).read_text())["reduced"])


@pytest.mark.parametrize("name,widths", [
    ("opt-13b-stage10", (5120, 20480, 40, 10, 50272, 2048)),
    ("opt-1.3b", (2048, 8192, 32, 24, 50272, 2048)),
])
def test_config_resolves_to_published_widths(name, widths):
    import jax
    from repro.configs import opt
    from repro.models import lm
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    mcfg = harness.model_config(cfg)
    assert (mcfg.d_model, mcfg.d_ff, mcfg.n_heads, mcfg.num_layers,
            mcfg.vocab, mcfg.max_seq) == widths
    published = opt.opt_13b() if "13b" in name else opt.opt_1_3b()
    for k in ("d_model", "d_ff", "n_heads", "n_kv_heads", "vocab", "act",
              "norm", "pos_emb", "tie_embeddings", "max_seq", "dtype"):
        assert getattr(mcfg, k) == getattr(published, k), k
    # the program's parameter tree is the benchmark's layout, leaf for leaf
    tree = jax.eval_shape(lambda: lm.init_params(mcfg, jax.random.PRNGKey(0)))
    got = {p: (tuple(a.shape), str(a.dtype))
           for p, a in ref.from_tree(tree).items()}
    assert got == {p: (tuple(s), dt) for p, s, dt, *_ in ref.layout(cfg)}
    assert cost.n_params(cfg) == lm.count_params(tree)


# ------------------------------------------------------------ yardstick
def test_cost_counts():
    cfg = json.loads((HERE / "configs" / "opt-13b-stage10.json").read_text())
    assert cost.n_params(cfg) == 3_413_821_440
    assert cost.layer_params(cfg) == 314_593_280
    assert cost.shared_params(cfg) == 267_888_640
    toy = {"hidden_size": 4, "ffn_dim": 8, "num_attention_heads": 2,
           "num_hidden_layers": 1, "vocab_size": 10,
           "max_position_embeddings": 16}
    # projections 2*1*3*(4*16 + 2*32) = 768, causal attention
    # 2*1*4*3*4 = 96, head at one position 2*4*10 = 80
    assert cost.forward_flops(toy, 1, 3, 1) == 944
    assert cost.zo_step_flops(toy, 1, 3, 1) == 2 * 944
    # a token at position 2: 2*128 + 4*4*3 = 304, plus a head of 80
    assert cost.token_flops(toy, 2, head=True) == 384
    assert cost.prefill_flops(toy, 0, 3, final=True) == (
        256 + 16 + 256 + 32 + 256 + 48 + 80)
    kv = 2 * 1 * 4 * 2
    assert cost.decode_bytes(toy, [0, 2]) == cost.weight_bytes(toy) + 4 * kv


def test_peaks():
    assert run.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peaks("TPU v9 imaginary")


def test_reduce_by_hand():
    """Two devices, a window 0-100 ns, ops that overlap and straddle it."""
    ops = lambda *iv: [{"name": n, "module": "jit_step", "category": c,  # noqa: E731
                        "start": s, "end": e} for n, c, s, e in iv]
    data = {"devices": {
        "/device:TPU:0": {"ops": ops(("dot.1", "convolution fusion", -10, 30),
                                     ("f.2", "loop fusion", 20, 40),
                                     ("f.3", "loop fusion", 90, 130)),
                          "modules": [{"name": "jit_step", "start": -10,
                                       "end": 40}]},
        "/device:TPU:1": {"ops": ops(("dot.1", "convolution fusion", 0, 50)),
                          "modules": []}},
        "spans": [{"name": "window", "start": 0, "end": 100},
                  {"name": "step", "start": 35, "end": 95},
                  {"name": "log_sync", "start": 60, "end": 70}]}
    t = trace_mod.reduce(data)
    assert t["window_s"] == pytest.approx(100e-9)
    # device 0 busy 0-40 and 90-100 = 50 ns; device 1 busy 50 ns
    assert t["busy_s"] == pytest.approx(50e-9)
    assert t["category_s"]["convolution fusion"] == pytest.approx(40e-9)
    assert t["category_s"]["loop fusion"] == pytest.approx(15e-9)
    assert trace_mod.matmul_s(t) == pytest.approx(40e-9)
    assert t["module_calls"]["jit_step"] == 0.5    # 1 call over 2 devices
    gaps = t["breakdown"]["idle_gaps"]
    # device 0 idles 40-90: at 65 the host is in log_sync, inside step
    assert gaps[0] == ["log_sync", pytest.approx(50e-9)]
    assert gaps[1][1] == pytest.approx(50e-9)          # device 1, 50-100


def test_nested_operations_count_their_own_time():
    """A while of 0-100 holding two ops, one of them a conditional
    holding a third: each counts only its own time."""
    ops = [{"start": 0, "end": 100}, {"start": 10, "end": 40},
           {"start": 20, "end": 30}, {"start": 50, "end": 90},
           {"start": 120, "end": 130}]
    assert trace_mod.own_times(ops) == [30, 20, 10, 40, 10]


def test_reduce_of_a_chip_trace():
    """106.6 ms of a traced window of ft-opt13b-sst2-lezo on one TPU v5
    lite (testdata/train-window.xplane.pb): the tail of one ZO step, from
    its last convolution fusion, an idle gap, and the head of the next.
    The numbers below were read from the file with the XSpace schema of
    TensorFlow's profiler by hand: the window span, the union of the
    'XLA Ops' events, the two jit_step executions of 'XLA Modules', and
    the one event whose metadata says hlo_category 'convolution fusion'.
    """
    t = trace_mod.reduce(trace_mod.load(str(HERE / "testdata" / "train-window.xplane.pb")))
    assert t["devices"] == 1
    assert t["window_s"] == pytest.approx(0.106610133594, rel=1e-9)
    assert t["busy_s"] == pytest.approx(0.106577715858, rel=1e-9)
    assert t["window_s"] - t["busy_s"] == pytest.approx(32.417736e-6, rel=1e-5)
    assert t["module_s"]["jit_step"] == pytest.approx(0.106585478594, rel=1e-9)
    assert t["module_calls"]["jit_step"] == 2
    assert trace_mod.matmul_s(t) == pytest.approx(0.005846463594, rel=1e-9)
    # own times add up to the busy time: nothing counted twice
    assert sum(t["category_s"].values()) == pytest.approx(t["busy_s"], rel=1e-9)
    assert t["breakdown"]["idle_gaps"][0][1] == pytest.approx(30.21375e-6, rel=1e-6)


def test_rows_differ_and_requests_keep_their_work():
    mix = generate.load("sst2-b16-s128")
    a, b = generate.rows(mix, 50272, 5, 0), generate.rows(mix, 50272, 5, 1)
    assert a["tokens"].shape == (16, 128) and not (a["tokens"] == b["tokens"]).all()
    assert (a["loss_mask"].sum(1) == 1).all()
    chat = generate.load("chat-poisson")
    r1 = generate.requests(chat, 50272, 1, 51)
    r2 = generate.requests(chat, 50272, 2**40 + 3, 51)
    size = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)  # noqa: E731
    assert size(r1) == size(r2)
    assert all(len(r["prompt"]) + r["max_new"] <= 2048 for r in r1)


# ------------------------------------------------------------ drivers
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_driver_runs_a_tiny_stand_in(cell, capsys):
    run.main(["--workload", cell, "--seed", str(2**35 + 1), "--seconds", "2",
              "--trace", "0"], stand_in=stand_in(cell))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    names = {m["name"] for m in run.Cell(bench_with(cell), cell).end_to_end}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["failed"] == 0
    # in float32 at this size the program and the reference agree
    assert all(c["value"] < SOUND for c in out["checks"].values())
    if all(c["limit"] is not None for c in out["checks"].values()):
        assert out["correct"] is True


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_the_control_is_not_correct(cell):
    """The reference in fp8 (and, for training, on half the batch) put in
    the program's place reads far above a sound run."""
    out = run_tiny(cell, control=True)
    kinds = {"fp8", "half_batch"} if cell.startswith("ft") else {"fp8"}
    assert set(out["control"]) == kinds
    for kind in kinds:
        assert out["control"][kind]["correct"] is False, kind
        assert max(c["value"] for c in out["control"][kind]["checks"].values()
                   ) >= FAULT, kind


def _break_step(monkeypatch, how):
    from repro import estimators
    make = estimators.make_step

    def broken_make(*a, **kw):
        step, init = make(*a, **kw)

        def broken(params, state, batch, t, seed):
            if how == "half_batch":
                n = batch["tokens"].shape[0] // 2
                return step(params, state, {k: v[:n] for k, v in batch.items()},
                            t, seed)
            _, state2, m = step(params, state, batch, t, seed)
            return params, state2, m          # the state left unchanged
        return broken, init
    monkeypatch.setattr(estimators, "make_step", broken_make)


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(how, monkeypatch):
    _break_step(monkeypatch, how)
    out = run_tiny("ft-opt13b-sst2-lezo")
    assert out["correct"] is False
    assert max(c["value"] for c in out["checks"].values()) >= FAULT


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serving import sampling
    make = sampling.make_sampler

    def altered(*a, **kw):
        sample = make(*a, **kw)
        # the greedy token, moved to its neighbour in the vocabulary
        return lambda logits, seeds, pos: (
            (sample(logits, seeds, pos) + 1) % logits.shape[-1])
    monkeypatch.setattr(sampling, "make_sampler", altered)
    out = run_tiny("serve-opt1.3b-chat")
    assert out["correct"] is False
    assert out["checks"]["token_gap"]["value"] >= FAULT


# ------------------------------------------------------------ no chip
def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    # and with the benchmark alone, no program beside it
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


def test_percentile_counts_every_request():
    assert harness.percentile([1.0, 2.0, 3.0, math.inf], 90) == math.inf
    assert harness.percentile(list(range(1, 11)), 90) == 9
