"""repro.api: the unified experiment spec (DESIGN.md §11).

Covers the spec tree's JSON round-trip (byte-stable, golden-pinned),
the single build-time validation site (every illegal combination raises
with the offending field path — property-tested), the derive() adapters
(legacy hand-wired Trainer construction vs spec construction is
bit-identical for every estimator x forward backend), the checkpoint
manifest spec embedding, and sweep/overrides plumbing.
"""
import os
import warnings

import pytest

from _hyp import given, settings, st
from repro import api, configs
from repro.core import zo
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "lezo-opt13b.spec.json")


# ------------------------------------------------------- serialization
def test_roundtrip_byte_stable_all_presets():
    for name in api.presets.names():
        spec = api.presets.get(name)
        text = api.to_json(spec)
        spec2 = api.from_json(text)
        assert spec2 == spec, name
        assert api.to_json(spec2) == text, f"{name}: re-serialize drifted"


def test_golden_spec_json_pinned():
    """The serialized schema of the headline preset is frozen; regenerate
    with `make specs` + copy if a schema change is intentional."""
    with open(GOLDEN) as f:
        golden = f.read()
    assert api.to_json(api.presets.get("lezo-opt13b")) == golden


def test_from_dict_defaults_and_unknown_keys():
    assert api.from_dict({}) == api.Experiment()
    assert api.from_dict({"optimizer": {"lr": 1e-5}}).optimizer.lr == 1e-5
    with pytest.raises(api.SpecError, match="optimizer.bogus"):
        api.from_dict({"optimizer": {"bogus": 1}})
    with pytest.raises(api.SpecError, match="nonsection"):
        api.from_dict({"nonsection": {}})


def test_stored_spec_naming_the_removed_sweep_option_is_refused():
    """The runtime section's ``backend`` field chose among four
    parameter sweeps; one is left, so a spec saved with the field fails
    as any unknown field does."""
    stored = api.to_dict(api.presets.get("lezo-opt13b"))
    stored["runtime"]["backend"] = "scan"
    with pytest.raises(api.SpecError, match="backend") as ei:
        api.from_dict(stored)
    assert "runtime" in str(ei.value)


def test_with_overrides_coercion_and_errors():
    s = api.Experiment()
    s2 = api.with_overrides(s, {
        "optimizer.lr": "1e-5", "estimator.q": "16",
        "optimizer.fused_update": "false", "runtime.peft": "none",
        "runtime.lora_targets": "wq,wk,wv", "optimizer.n_drop": "3"})
    assert s2.optimizer.lr == 1e-5 and s2.estimator.q == 16
    assert s2.optimizer.fused_update is False
    assert s2.runtime.peft is None
    assert s2.runtime.lora_targets == ("wq", "wk", "wv")
    assert s2.optimizer.n_drop == 3
    with pytest.raises(api.SpecError, match="optimizer.bogus"):
        api.with_overrides(s, {"optimizer.bogus": 1})
    with pytest.raises(api.SpecError, match="estimator.q"):
        api.with_overrides(s, {"estimator.q": "sixteen"})
    with pytest.raises(api.SpecError, match="optimizer.fused_update"):
        api.with_overrides(s, {"optimizer.fused_update": "perhaps"})


def test_spec_diff_and_resume_mutable():
    a = api.to_dict(api.Experiment())
    b = api.to_dict(api.with_overrides(api.Experiment(), {
        "optimizer.lr": 1e-5, "run.steps": 999}))
    diff = api.spec_diff(a, b)
    assert any("optimizer.lr" in line for line in diff)
    assert not any("run.steps" in line for line in diff), \
        "run.steps is resume-mutable and must not appear"
    assert api.spec_diff(a, a) == ()


# ---------------------------------------------------------- validation
ILLEGAL = [
    ({"runtime.forward_backend": "virtual_ref", "runtime.peft": "lora"},
     "runtime.peft"),
    ({"runtime.forward_backend": "virtual_ref", "runtime.peft": "prefix"},
     "runtime.peft"),
    ({"runtime.forward_backend": "virtual", "optimizer.mode": "fo"},
     "optimizer.mode"),
    ({"runtime.forward_backend": "virtual_ref",
      "optimizer.mode": "zo_momentum"}, "optimizer.mode"),
    ({"runtime.forward_backend": "virtual_ref",
      "model.arch": "granite-moe-1b-a400m"}, "runtime.forward_backend"),
    ({"runtime.forward_backend": "virtual_ref",
      "model.arch": "xlstm-350m"}, "runtime.forward_backend"),
    ({"optimizer.policy": "round_robin"}, "optimizer.policy"),
    ({"estimator.q": 0}, "estimator.q"),
    ({"estimator.q": -4}, "estimator.q"),
    ({"runtime.quorum": 0.0}, "runtime.quorum"),
    ({"runtime.quorum": 1.5}, "runtime.quorum"),
    ({"estimator.name": "three_point"}, "estimator.name"),
    ({"estimator.inner": "importance"}, "estimator.inner"),
    ({"runtime.mesh": "torus"}, "runtime.mesh"),
    ({"runtime.forward_backend": "imaginary"}, "runtime.forward_backend"),
    ({"optimizer.mode": "sgd"}, "optimizer.mode"),
    ({"optimizer.policy": "fancy"}, "optimizer.policy"),
    ({"optimizer.sparsity": 1.0}, "optimizer.sparsity"),
    ({"optimizer.sparsity": -0.1}, "optimizer.sparsity"),
    ({"optimizer.n_drop": 99}, "optimizer.n_drop"),
    ({"optimizer.eps": 0.0}, "optimizer.eps"),
    ({"model.arch": "opt-99t"}, "model.arch"),
    ({"model.variant": "gigantic"}, "model.variant"),
    ({"task.name": "imagenet"}, "task.name"),
    ({"runtime.peft": "adapters"}, "runtime.peft"),
    ({"runtime.n_loss_shards": 3, "run.batch_size": 16}, "run.batch_size"),
    ({"run.steps": 0}, "run.steps"),
    ({"run.ckpt_every": 4}, "run.ckpt_dir"),
    ({"optimizer.schedule": "cosine"}, "optimizer.schedule"),
]


@pytest.mark.parametrize("overrides,path", ILLEGAL,
                         ids=[p + "-" + str(i) for i, (_, p)
                              in enumerate(ILLEGAL)])
def test_illegal_combination_raises_at_build_time(overrides, path):
    """Every invariant that used to surface as a deep-in-Trainer
    ValueError raises at spec-build time, naming the offending field."""
    spec = api.with_overrides(api.presets.get("default"), overrides)
    with pytest.raises(api.SpecError) as ei:
        api.validate(spec)
    assert path in str(ei.value), \
        f"error message must carry the field path {path!r}: {ei.value}"


def test_unknown_task_is_also_keyerror():
    spec = api.with_overrides(api.Experiment(), {"task.name": "imagenet"})
    with pytest.raises(KeyError):
        api.validate(spec)


def test_validate_accepts_every_preset():
    for name in api.presets.names():
        api.validate(api.presets.get(name))


@settings(max_examples=30, deadline=None)
@given(
    lr=st.floats(min_value=0.0, max_value=1.0, allow_nan=False),
    eps=st.floats(min_value=1e-8, max_value=1.0, allow_nan=False),
    sparsity=st.floats(min_value=0.0, max_value=0.999),
    q=st.integers(min_value=1, max_value=64),
    estimator=st.sampled_from(("two_point", "one_sided", "averaged",
                               "importance")),
    policy=st.sampled_from(("stratified", "uniform")),
)
def test_legal_space_always_validates(lr, eps, sparsity, q, estimator,
                                      policy):
    """No legal combination of the core hyperparameters is rejected —
    validate() only hoists real invariants, it adds no new constraints."""
    spec = api.with_overrides(api.presets.get("default"), {
        "optimizer.lr": lr, "optimizer.eps": eps,
        "optimizer.sparsity": sparsity, "estimator.q": q,
        "estimator.name": estimator, "optimizer.policy": policy,
    })
    api.validate(spec)


@settings(max_examples=30, deadline=None)
@given(path=st.sampled_from(("estimator.q", "runtime.quorum",
                             "optimizer.sparsity", "optimizer.eps",
                             "run.steps", "run.batch_size")),
       bad=st.sampled_from((-5, -1, 0, 2, 99)))
def test_out_of_range_numerics_name_their_field(path, bad):
    lo, hi = {"estimator.q": (1, 10**6), "runtime.quorum": (1e-9, 1.0),
              "optimizer.sparsity": (0.0, 0.999),
              "optimizer.eps": (1e-12, 10**6),
              "run.steps": (1, 10**6), "run.batch_size": (1, 10**6)}[path]
    if lo <= bad <= hi:
        return  # in-range draw: nothing to assert
    spec = api.with_overrides(api.presets.get("default"), {path: bad})
    with pytest.raises(api.SpecError) as ei:
        api.validate(spec)
    assert path in str(ei.value)


# -------------------------------------------------------------- derive
def test_derive_matches_legacy_field_for_field():
    spec = api.with_overrides(api.presets.get("default"), {
        "model.variant": "smoke", "optimizer.lr": 2e-4,
        "estimator.name": "one_sided", "estimator.q": 4,
        "runtime.quorum": 0.75, "runtime.n_loss_shards": 4,
        "run.batch_size": 16})
    d = api.derive(spec)
    assert d.model_cfg.name == "opt-smoke"
    assert d.n_drop == int(0.75 * d.model_cfg.num_layers)
    assert d.tcfg.eval_every == max(1, spec.run.steps // 4)  # auto cadence
    assert d.tcfg.estimator == "one_sided" and d.tcfg.est_q == 4
    assert d.zo_cfg.lr == 2e-4 and d.est_cfg.lr == 2e-4
    assert d.est_cfg.name == "one_sided" and d.est_cfg.q == 4
    assert d.fo_cfg.lr == 2e-4
    # synthetic task mirrors the legacy launch/train construction
    assert isinstance(d.task, synthetic.TaskConfig)
    assert d.task.vocab == d.model_cfg.vocab
    assert d.task.seq_len == spec.model.seq_len


EQUIV_CASES = [(e, fb) for e in ("two_point", "one_sided", "averaged",
                                "importance")
               for fb in ("materialized", "virtual_ref")]


@pytest.mark.parametrize("estimator,fb", EQUIV_CASES)
def test_legacy_vs_spec_bit_identical(estimator, fb):
    """The acceptance gate: a hand-wired legacy Trainer and the spec path
    produce the same per-step losses bit-for-bit, for every estimator x
    materialized/virtual."""
    q = 2 if estimator in ("one_sided", "averaged") else 1
    spec = api.with_overrides(api.presets.get("tiny-smoke"), {
        "model.variant": "smoke", "run.steps": 6, "run.batch_size": 4,
        "run.eval_every": 0, "estimator.name": estimator,
        "estimator.q": q, "runtime.forward_backend": fb})
    res = api.run(spec)

    # the legacy construction, written out the way launch/train used to
    mcfg = configs.get("opt-13b", "smoke")
    task = synthetic.TaskConfig(vocab=mcfg.vocab, seq_len=32, n_classes=2,
                                seed=0)
    tcfg = TrainConfig(steps=6, batch_size=4, eval_every=0, log_every=1,
                       seed=0, estimator=estimator, est_q=q,
                       forward_backend=fb)
    zcfg = zo.ZOConfig(eps=1e-3, lr=1e-4,
                       n_drop=int(0.75 * mcfg.num_layers),
                       forward_backend=fb)
    hist = Trainer(mcfg, task, tcfg, zo_cfg=zcfg).train()
    assert hist["loss"] == res["history"]["loss"]
    assert hist["val_loss"] == res["history"]["val_loss"]


def test_legacy_construction_soft_warns():
    mcfg = configs.get("opt-13b", "smoke")
    task = synthetic.TaskConfig(vocab=mcfg.vocab, seq_len=32, n_classes=2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Trainer(mcfg, task, TrainConfig(steps=2, batch_size=2))
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Trainer.from_spec(api.with_overrides(
            api.presets.get("tiny-smoke"), {"model.variant": "smoke"}))
    assert not any(issubclass(x.category, DeprecationWarning) for x in w)


# ------------------------------------------------- checkpoint manifest
def _ckpt_spec(tmp_path, **extra):
    return api.with_overrides(api.presets.get("tiny-smoke"), {
        "model.variant": "smoke", "run.steps": 4, "run.batch_size": 4,
        "run.eval_every": 0, "run.ckpt_dir": str(tmp_path / "ckpt"),
        "run.ckpt_every": 2, **extra})


def test_checkpoint_embeds_spec_and_rejects_mismatch(tmp_path):
    spec = _ckpt_spec(tmp_path)
    api.run(spec)
    tr = Trainer.from_spec(spec)
    manifest = tr.ckpt.read_manifest()
    assert manifest["extra"]["spec"] == api.to_dict(spec)

    # resume-mutable drift (longer schedule) is fine
    api.run(api.with_overrides(spec, {"run.steps": 6}))

    # anything else fails loudly with a field diff
    bad = api.with_overrides(spec, {"optimizer.lr": 9e-4})
    with pytest.raises(api.SpecError, match="optimizer.lr"):
        api.run(bad)


def test_legacy_checkpoints_have_no_spec_and_still_resume(tmp_path):
    mcfg = configs.get("opt-13b", "smoke")
    task = synthetic.TaskConfig(vocab=mcfg.vocab, seq_len=32, n_classes=2)
    tcfg = TrainConfig(steps=4, batch_size=4, eval_every=0, log_every=1,
                       ckpt_dir=str(tmp_path / "l"), ckpt_every=2)
    zcfg = zo.ZOConfig(n_drop=1)
    Trainer(mcfg, task, tcfg, zo_cfg=zcfg).train()
    tr = Trainer(mcfg, task, tcfg, zo_cfg=zcfg)
    assert "spec" not in tr.ckpt.read_manifest()["extra"]
    tr.train()   # legacy resume path: no spec check, no crash


# --------------------------------------------------------------- sweep
def test_sweep_returns_structured_results():
    base = api.with_overrides(api.presets.get("tiny-smoke"), {
        "model.variant": "smoke", "run.steps": 3, "run.batch_size": 4,
        "run.eval_every": 0})
    out = api.sweep(base, [{"optimizer.sparsity": 0.0},
                           {"optimizer.sparsity": 0.5}])
    assert [o["overrides"] for o in out] == [
        {"optimizer.sparsity": 0.0}, {"optimizer.sparsity": 0.5}]
    for o in out:
        assert o["result"]["spec"]["optimizer"]["sparsity"] in (0.0, 0.5)
        assert len(o["result"]["history"]["loss"]) == 3
    # MeZO vs LeZO differ only in selection; first-step losses disagree
    # only through the dropped layers, but both must be finite
    assert all(x == x for o in out for x in o["result"]["history"]["loss"])
