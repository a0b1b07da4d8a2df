"""The split of a device trace's time by the ZO step's stages
(``chipbench/stages.py``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import stages  # noqa: E402
import trace as trace_mod  # noqa: E402

OLD = HERE / "testdata" / "train-window.xplane.pb"
NEW = HERE / "testdata" / "train-stages.xplane.pb"


def test_stage_names_are_the_programs():
    from repro import obs
    assert stages.STAGES == (obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS,
                             obs.FWD_PAIR, obs.FWD_BASE, obs.UPDATE,
                             obs.LOSS_HEAD)
    assert set(obs.STAGES) < set(stages.STAGES)


@pytest.mark.parametrize("tf_op, stage", [
    ("jit(step)/perturb/while/body/closed_call/mul", "perturb"),
    ("jit(step)/forward+εz/loss_head/while/body/closed_call/dot_general",
     "loss_head"),
    ("jit(step)/forward-εz/while/body/closed_call/dot_general", "forward-εz"),
    ("jit(step)/update_axpy/while/body/add", "update_axpy"),
    ("jit(step)/perturb/iota:", "perturb"),
    # a cached nested jit: the call site's path, then its first lowering's
    ("jit(step)/forward-εz/while/body/closed_call/jit(flash_attention)/"
     "jit(step)/forward+εz/while/body/closed_call/jit(flash_attention)/"
     "while/body/closed_call/broadcast_in_dim:", "forward-εz"),
    ("jit(step)/jit(argsort)/sort", "unscoped"),
    ("", "unscoped"),
    # a name that only contains a stage's name is not that stage
    ("jit(step)/perturbation/add", "unscoped"),
])
def test_an_op_belongs_to_its_innermost_stage(tf_op, stage):
    assert stages.stage_of(tf_op) == stage


def test_stage_split_by_hand():
    """One device, a window 0-100 ns: a while of the probe holding the
    head's op, an op straddling the window's end, one with no stage."""
    ops = [{"name": "while.1", "module": "jit_step", "category": "while",
            "start": 0, "end": 50, "tf_op": "jit(step)/forward+εz/while"},
           {"name": "f.2", "module": "jit_step", "category": "loop fusion",
            "start": 10, "end": 30,
            "tf_op": "jit(step)/forward+εz/loss_head/while/body/exp"},
           {"name": "f.3", "module": "jit_step", "category": "loop fusion",
            "start": 60, "end": 70, "tf_op": "jit(step)/add"},
           {"name": "f.4", "module": "jit_step", "category": "loop fusion",
            "start": 90, "end": 130,
            "tf_op": "jit(step)/update_axpy/while/body/add"}]
    data = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "spans": [{"name": "window", "start": 0, "end": 100}]}
    s = stages.stage_s(data)
    assert s == pytest.approx({"forward+εz": 30e-9, "loss_head": 20e-9,
                               "unscoped": 10e-9, "update_axpy": 10e-9})
    t = trace_mod.reduce(data)
    assert sum(s.values()) == pytest.approx(sum(t["category_s"].values()),
                                            abs=1e-18)
    top = stages.top_ops(data, 2)
    assert [r[:2] for r in top] == [["jit_step:while.1 (while)", "forward+εz"],
                                    ["jit_step:f.2 (loop fusion)", "loss_head"]]


def test_a_trace_without_stage_names_is_all_unscoped():
    """The chip trace recorded before the step named its stages: every
    op is unscoped, and the split still adds up to category_s."""
    data = stages.load(str(OLD))
    s = stages.stage_s(data)
    assert list(s) == ["unscoped"]
    t = trace_mod.reduce(trace_mod.load(str(OLD)))
    assert s["unscoped"] == pytest.approx(sum(t["category_s"].values()),
                                          abs=1e-9)


def test_stage_split_of_a_chip_trace():
    """One whole ZO step of ft-opt13b-sst2-lezo on one TPU v5 lite, from
    the start of one jit_step to the start of the next
    (testdata/train-stages.xplane.pb, trimmed from a kept trace: the XLA
    Modules and XLA Ops events, each op's name, hlo_category, program_id
    and tf_op, and the window span).  Every stage of a materialized
    two-point step has time; the split adds up to category_s and to the
    busy time, so no op is counted twice or left out."""
    data = stages.load(str(NEW))
    s = stages.stage_s(data)
    assert s == pytest.approx({
        "perturb": 0.19501105289399684, "forward+εz": 0.07514405195199844,
        "forward-εz": 0.08071981101400041, "loss_head": 0.012320521717999694,
        "update_axpy": 0.07789657655599914, "unscoped": 0.02914765250400606},
        rel=1e-9)
    t = trace_mod.reduce(trace_mod.load(str(NEW)))
    assert t["module_calls"]["jit_step"] == 1
    assert sum(s.values()) == pytest.approx(sum(t["category_s"].values()),
                                            abs=1e-9)
    assert sum(s.values()) == pytest.approx(t["busy_s"], abs=1e-9)
    # the step's largest operations: the probes' MLP matmuls, then the
    # sweeps' RNG+axpy loop fusions
    top = stages.top_ops(data, 6)
    assert [r[:2] for r in top] == [
        ["jit_step:%fusion.1126 (convolution fusion)", "forward-εz"],
        ["jit_step:%fusion.1111 (convolution fusion)", "forward+εz"],
        ["jit_step:%fusion.1127 (convolution fusion)", "forward-εz"],
        ["jit_step:%fusion.1112 (convolution fusion)", "forward+εz"],
        ["jit_step:%fusion.398 (loop fusion)", "perturb"],
        ["jit_step:%fusion.614 (loop fusion)", "update_axpy"]]


@pytest.mark.parametrize("trace_file", [OLD, NEW])
def test_the_command_prints_ms_per_step(trace_file):
    r = subprocess.run([sys.executable, str(HERE / "stages.py"),
                        str(trace_file), "--steps", "2", "--top", "3"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    for k, v in out["stage_s"].items():
        assert out["ms_per_step"][k] == pytest.approx(1e3 * v / 2)
    assert len(out["top_ops"]) == 3
