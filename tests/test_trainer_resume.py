"""Resume determinism: an interrupted+resumed run replays the exact
uninterrupted trajectory.

This is the DESIGN.md §7 replay guarantee: every LeZO update derives
from (base_seed, step) and the data stream from (seed,), so restoring
(params, step) reproduces the update stream bit-for-bit — including the
``t < start`` batch-skip path in ``Trainer.train`` that keeps the batch
iterator aligned with the step counter.
"""
import numpy as np
import pytest

import jax

from repro.configs import opt
from repro.core import zo
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig

MCFG = opt.opt_tiny(layers=2, d_model=64, vocab=256)
TASK = synthetic.TaskConfig(vocab=256, seq_len=32, n_classes=2,
                            signal_rate=0.35)
ZCFG = zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1)
STEPS, CKPT_AT = 24, 8


def _tcfg(**kw):
    base = dict(steps=STEPS, batch_size=8, eval_every=0, log_every=1, seed=3)
    return TrainConfig(**{**base, **kw})


@pytest.mark.slow
def test_resume_trajectory_bit_identical(tmp_path):
    # uninterrupted reference run
    ref = Trainer(MCFG, TASK, _tcfg(), zo_cfg=ZCFG).train()

    # interrupted run: checkpoint at step CKPT_AT, stop shortly after
    d = str(tmp_path / "ckpt")
    Trainer(MCFG, TASK,
            _tcfg(steps=CKPT_AT + 3, ckpt_dir=d, ckpt_every=CKPT_AT),
            zo_cfg=ZCFG).train()

    # restart from the checkpoint and finish the schedule
    resumed_tr = Trainer(MCFG, TASK, _tcfg(ckpt_dir=d), zo_cfg=ZCFG)
    res = resumed_tr.train()

    # resumed history starts exactly at the checkpoint step
    assert res["step"][0] == CKPT_AT
    assert ref["step"][-len(res["step"]):] == res["step"]
    ref_tail = ref["loss"][-len(res["loss"]):]
    assert ref_tail == res["loss"], "loss trajectory diverged after resume"

    # and the final parameters match bit-for-bit
    for a, b in zip(jax.tree.leaves(ref["final_params"]),
                    jax.tree.leaves(res["final_params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_resume_skips_consumed_batches(tmp_path):
    """The resumed run must not replay steps < start: its logged history
    begins at the restore step, with the same wall-format keys."""
    d = str(tmp_path / "ckpt")
    Trainer(MCFG, TASK, _tcfg(steps=CKPT_AT + 1, ckpt_dir=d,
                              ckpt_every=CKPT_AT), zo_cfg=ZCFG).train()
    res = Trainer(MCFG, TASK, _tcfg(ckpt_dir=d), zo_cfg=ZCFG).train()
    assert min(res["step"]) == CKPT_AT
    assert len(res["loss"]) == STEPS - CKPT_AT
