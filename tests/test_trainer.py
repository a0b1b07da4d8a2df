"""End-to-end training behaviour: convergence, PEFT, quorum, FO baseline."""
import dataclasses

import numpy as np
import pytest

from repro.configs import opt
from repro.core import fo, zo
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig

MCFG = opt.opt_tiny(layers=2, d_model=64, vocab=256)
TASK = synthetic.TaskConfig(vocab=256, seq_len=48, n_classes=2,
                            signal_rate=0.35)


def test_lezo_converges():
    tr = Trainer(MCFG, TASK,
                 TrainConfig(steps=200, batch_size=16, eval_every=0,
                             log_every=50),
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1))
    h = tr.train()
    assert h["loss"][-1] < h["loss"][0] - 0.5


def test_lezo_tracks_mezo():
    """LeZO per-step progress is comparable to MeZO (paper claim)."""
    res = {}
    for name, nd in [("mezo", 0), ("lezo", 1)]:
        tr = Trainer(MCFG, TASK,
                     TrainConfig(steps=150, batch_size=16, eval_every=0,
                                 log_every=149),
                     zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=nd))
        res[name] = tr.train()["loss"][-1]
    assert res["lezo"] < res["mezo"] + 0.5


def test_fo_baseline_converges():
    tr = Trainer(MCFG, TASK,
                 TrainConfig(steps=60, batch_size=16, eval_every=0,
                             log_every=20, mode="fo"),
                 fo_cfg=fo.FOConfig(lr=3e-4))
    h = tr.train()
    assert h["loss"][-1] < h["loss"][0]


@pytest.mark.parametrize("peft", ["lora", "prefix"])
def test_peft_runs_and_moves_loss(peft):
    tr = Trainer(MCFG, TASK,
                 TrainConfig(steps=40, batch_size=8, eval_every=0,
                             log_every=39, peft=peft),
                 zo_cfg=zo.ZOConfig(eps=1e-2, lr=1e-3, n_drop=1))
    h = tr.train()
    assert np.isfinite(h["loss"]).all()
    # trainable tree is only PEFT params
    n_trainable = sum(x.size for x in
                      __import__("jax").tree.leaves(tr.trainable))
    n_total = sum(x.size for x in
                  __import__("jax").tree.leaves(tr.base_params))
    assert n_trainable < n_total / 10


def test_quorum_still_converges():
    tr = Trainer(MCFG, TASK,
                 TrainConfig(steps=200, batch_size=16, eval_every=0,
                             log_every=50, n_loss_shards=4, quorum=0.75),
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1))
    h = tr.train()
    assert h["loss"][-1] < h["loss"][0] - 0.3


def test_quorum_loss_windowed_head_matches_whole_sequence(monkeypatch):
    """``quorum_loss`` vmaps over loss shards: the head's window comes from
    the whole batch outside the vmap, so the switch stays a switch, and
    the loss equals the head over every position (the top rung)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    tr = Trainer(MCFG, TASK, TrainConfig(steps=1, batch_size=8, eval_every=0,
                                         log_every=0, n_loss_shards=4,
                                         quorum=0.75))
    batch = tr._model_batch(tr.make_dataset(8))
    assert float(jnp.sum(batch["loss_mask"][:, :-1])) == 0.0  # tail-only
    got = tr.loss_fn(tr.trainable, batch)
    assert "cond[" in str(jax.make_jaxpr(tr.loss_fn)(tr.trainable, batch))
    S = batch["loss_mask"].shape[-1]
    monkeypatch.setattr(lm, "head_rung",
                        lambda m: jnp.int32(len(lm.head_widths(S)) - 1))
    want = tr.loss_fn(tr.trainable, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_eval_accuracy_classification():
    tr = Trainer(MCFG, TASK, TrainConfig(steps=1, batch_size=4, eval_every=0,
                                         log_every=0))
    data = synthetic.make_dataset(TASK, 64)
    vl, va = tr.evaluate(tr.trainable, data)
    assert 0.0 <= va <= 1.0 and np.isfinite(vl)


def test_zo_momentum_beats_zo_sgd():
    """Beyond-paper: memory-free ZO-momentum accelerates convergence."""
    res = {}
    for mode in ("zo", "zo_momentum"):
        tr = Trainer(MCFG, TASK,
                     TrainConfig(steps=120, batch_size=16, eval_every=0,
                                 log_every=119, mode=mode),
                     zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1))
        res[mode] = tr.train()["loss"][-1]
    assert res["zo_momentum"] < res["zo"]


def test_lezo_dsv2lite_preset_trains_both_layer_groups():
    """The DeepSeek-V2-Lite preset (MLA + drop-free MoE after a dense
    first layer) runs through Trainer.from_spec's jitted step, and the
    dense first layer, a group of one, is trained too."""
    import jax
    from repro import api
    spec = api.with_overrides(api.preset("lezo-dsv2lite"), {
        "run.steps": 3, "run.batch_size": 2, "model.seq_len": 16,
        "run.eval_every": 0, "run.log_every": 1})
    tr = Trainer.from_spec(spec)
    before = jax.tree.map(np.asarray, tr.trainable)
    out = tr.train()
    assert np.isfinite(out["loss"]).all()
    after = out["final_params"]
    for g in ("s0", "s1"):
        moved = [not np.array_equal(a, np.asarray(b)) for a, b in zip(
            jax.tree.leaves(before["stages"][g]),
            jax.tree.leaves(after["stages"][g]))]
        assert all(moved), g
