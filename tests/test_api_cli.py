"""The unified spec-driven launch CLI (launch/cli.py) and the legacy
module shims that forward to it.

The headline regression here is the default-drift satellite: train and
evaluate used to carry separate argparse tables whose defaults disagreed
(--lr 1e-4 vs 1e-3, batch 16 vs 32).  Both now parse into the same
preset-backed spec, so their shared-field defaults are equal by
construction — and asserted below so they stay that way.
"""
import json
import os

import pytest

from repro import api
from repro.launch import cli


def _spec(argv, implied=None):
    ns = cli.build_parser().parse_args(argv)
    return cli.build_spec(ns, implied)


# ------------------------------------------------------- default drift
def test_train_and_evaluate_defaults_agree():
    train = _spec(["train"])
    ev = _spec(["evaluate"])
    assert train.optimizer == ev.optimizer, \
        "train/evaluate optimizer defaults drifted"
    assert train.estimator == ev.estimator
    assert train.runtime == ev.runtime
    assert train.run == ev.run
    assert train.model == ev.model
    # the historical drift, pinned explicitly: one lr, one batch size
    assert train.optimizer.lr == ev.optimizer.lr == 1e-4
    assert train.run.batch_size == ev.run.batch_size


def test_every_command_shares_the_generated_spec_surface():
    """No per-command argparse duplication for shared fields: every
    spec-driven command accepts every generated spec flag and resolves
    it through the same path.  The run-dir commands (report/replay) are
    the deliberate exception — their spec is the run's own spec.json,
    so they must reject spec flags rather than silently ignore them."""
    for cmd in cli.COMMANDS:
        if cmd in cli._NO_SPEC_CMDS:
            with pytest.raises(SystemExit):
                cli.build_parser().parse_args([cmd, "--optimizer.lr",
                                               "5e-5"])
            continue
        extra = ["--shape", "train_4k"] if cmd == "hillclimb" else []
        spec = _spec([cmd, "--optimizer.lr", "5e-5", "--arch", "opt-13b",
                      *extra])
        assert spec.optimizer.lr == 5e-5, cmd
        assert spec.model.arch == "opt-13b", cmd


# -------------------------------------------------- flags & precedence
def test_alias_and_generated_flags_are_the_same_field():
    a = _spec(["train", "--lr", "3e-4"])
    b = _spec(["train", "--optimizer.lr", "3e-4"])
    assert a == b
    assert a.optimizer.lr == 3e-4


def test_precedence_preset_flags_set():
    spec = _spec(["train", "--preset", "mezo-opt13b",
                  "--sparsity", "0.5", "--set", "optimizer.sparsity=0.25"])
    assert spec.optimizer.sparsity == 0.25     # --set wins over flags
    spec = _spec(["train", "--preset", "mezo-opt13b", "--sparsity", "0.5"])
    assert spec.optimizer.sparsity == 0.5      # flags win over preset
    spec = _spec(["train", "--preset", "mezo-opt13b"])
    assert spec.optimizer.sparsity == 0.0      # preset over base defaults


def test_train_optimizer_implications():
    spec = _spec(["train"], implied={"optimizer.sparsity": 0.0})
    assert spec.optimizer.sparsity == 0.0
    # legacy semantics: `--optimizer mezo --sparsity X` always meant
    # n_drop=0, so the command implication beats the flag ...
    spec = _spec(["train", "--sparsity", "0.6"],
                 implied={"optimizer.sparsity": 0.0})
    assert spec.optimizer.sparsity == 0.0
    # ... while an explicit --set (spec-world) still wins over both
    spec = _spec(["train", "--set", "optimizer.sparsity=0.6"],
                 implied={"optimizer.sparsity": 0.0})
    assert spec.optimizer.sparsity == 0.6


def test_unknown_set_path_and_preset_fail_with_path():
    with pytest.raises(api.SpecError, match="optimizer.bogus"):
        _spec(["train", "--set", "optimizer.bogus=1"])
    with pytest.raises(api.SpecError, match="--set"):
        _spec(["train", "--set", "optimizer.lr"])
    with pytest.raises(api.SpecError, match="preset"):
        _spec(["train", "--preset", "nope"])


# ------------------------------------------------------ specs command
def test_specs_command_dumps_all_presets_byte_identical(tmp_path, capsys):
    written = cli.main(["specs", "--out", str(tmp_path)])
    assert sorted(written) == api.presets.names()
    for name, path in written.items():
        with open(path) as f:
            text = f.read()
        assert text == api.to_json(api.presets.get(name)), name
        assert api.from_json(text) == api.presets.get(name)
    out = json.loads(capsys.readouterr().out)
    assert out == written


# ------------------------------------------------- end-to-end commands
def test_train_command_end_to_end(tmp_path, capsys):
    out = tmp_path / "hist.json"
    result = cli.main([
        "train", "--preset", "tiny-smoke", "--variant", "smoke",
        "--steps", "3", "--batch-size", "4", "--out", str(out)])
    assert result["summary"]["final_loss"] is not None
    assert len(result["history"]["loss"]) == 3
    # stdout carries the summary; --out carries spec + summary + history
    printed = json.loads(capsys.readouterr().out)
    assert printed == result["summary"]
    payload = json.loads(out.read_text())
    assert payload["spec"] == result["spec"]
    assert payload["spec"]["run"]["steps"] == 3
    assert "final_params" not in payload["history"]


def test_legacy_train_shim_accepts_historical_flags(tmp_path):
    from repro.launch import train as train_mod
    out = tmp_path / "h.json"
    result = train_mod.main([
        "--arch", "opt-13b", "--variant", "smoke", "--optimizer", "mezo",
        "--estimator", "two_point", "--q", "1", "--steps", "3",
        "--batch-size", "4", "--lr", "1e-4", "--eps", "1e-3",
        "--seq-len", "32", "--seed", "0",
        "--out", str(out)])
    assert result["summary"]["n_drop"] == 0          # mezo implication
    assert os.path.exists(out)


def test_legacy_serve_shim_smoke(capsys):
    from repro.launch import serve as serve_mod
    result = serve_mod.main(["--variant", "smoke", "--batch", "2",
                             "--prompt-len", "8", "--gen", "3"])
    assert result["spec"]["model"]["arch"] == "xlstm-350m"
    assert len(result["tokens"][0]) == 3
    assert "tok/s" in capsys.readouterr().out
