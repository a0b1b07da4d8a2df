"""Paper Fig. 1 / Fig. 5: LeZO computation + convergence speedup vs MeZO.

Computation speedup: wall time per full optimization step at 75% layer
sparsity.  Convergence speedup: steps for the train loss to first reach a
target, MeZO / LeZO (paper reports 1.5-3.4x depending on task).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (bench_model, emit, make_batch, make_zo_parts,
                               timeit)
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig
from repro.core import zo
from repro.configs import opt


def run():
    rows = []
    # ---- computation speedup (per-step wall time) -----------------------
    cfg, seq = bench_model()
    batch = make_batch(cfg, 16, seq)
    n = cfg.num_layers
    times = {}
    for name, n_drop in [("mezo", 0), ("lezo75", int(0.75 * n))]:
        params, _, _, step = make_zo_parts(cfg, n_drop)
        times[name] = timeit(step, params, batch, jnp.int32(0), jnp.uint32(1))
        rows.append((f"step_time_{name}", times[name] * 1e6, f"n_drop={n_drop}"))
    rows.append(("computation_speedup", 0.0,
                 f"{times['mezo'] / times['lezo75']:.2f}x (paper: ~1.4-3.4x)"))

    # ---- convergence speedup (steps to target loss) ---------------------
    # Paper protocol (Appendix A): learning rate is grid-searched PER
    # METHOD, and LeZO's optimum sits higher than MeZO's (Fig. 3: sparser
    # perturbation supports larger lr).  Best-of-grid per method:
    mcfg = opt.opt_tiny(layers=4, d_model=128, vocab=512)
    task = synthetic.TaskConfig(vocab=512, seq_len=64, n_classes=2,
                                signal_rate=0.35)
    target = 3.0
    reached = {}
    for name, n_drop, lrs in [("mezo", 0, (2e-4, 3e-4)),
                              ("lezo75", 3, (3e-4, 6e-4))]:
        best = None
        for lr in lrs:
            tr = Trainer(mcfg, task,
                         TrainConfig(steps=400, batch_size=16, eval_every=0,
                                     log_every=10),
                         zo_cfg=zo.ZOConfig(eps=1e-3, lr=lr, n_drop=n_drop))
            h = tr.train()
            idx = next((s for s, l in zip(h["step"], h["loss"])
                        if l < target), None)
            if idx is not None and (best is None or idx < best):
                best = idx
        reached[name] = best
        rows.append((f"steps_to_loss{target}_{name}",
                     0.0 if best is None else float(best),
                     f"best of lr grid {lrs}"))
    if reached["mezo"] and reached["lezo75"]:
        rows.append(("convergence_speedup", 0.0,
                     f"{reached['mezo'] / max(reached['lezo75'], 1):.2f}x"))
    return emit(rows)


if __name__ == "__main__":
    run()
