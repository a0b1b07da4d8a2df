"""Paper Fig. 3 / Fig. 4: step time and convergence vs sparsity ratio.

Sweeps the dropout number (layers dropped per step); reports per-step
wall time and final training loss at a fixed budget.  Paper: runtime
falls monotonically with sparsity; accuracy holds (and improves) up to
rho=0.75-0.9, collapsing only at rho=1.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import bench_model, emit, make_batch, make_zo_parts, timeit
from repro.configs import opt
from repro.core import zo
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig


def run():
    rows = []
    cfg, seq = bench_model()
    batch = make_batch(cfg, 16, seq)
    N = cfg.num_layers
    base = None
    for frac in (0.0, 0.25, 0.5, 0.75):
        n_drop = int(frac * N)
        params, _, _, step = make_zo_parts(cfg, n_drop)
        t = timeit(step, params, batch, jnp.int32(0), jnp.uint32(1))
        base = base or t
        rows.append((f"steptime_rho{frac:.2f}", t * 1e6,
                     f"speedup={base / t:.2f}x"))

    mcfg = opt.opt_tiny(layers=4, d_model=128, vocab=512)
    task = synthetic.TaskConfig(vocab=512, seq_len=64, n_classes=2,
                                signal_rate=0.35)
    for n_drop in (0, 1, 2, 3):
        tr = Trainer(mcfg, task,
                     TrainConfig(steps=250, batch_size=16, eval_every=0,
                                 log_every=249),
                     zo_cfg=zo.ZOConfig(eps=1e-3, lr=3e-4, n_drop=n_drop))
        h = tr.train()
        rows.append((f"final_loss_drop{n_drop}of4", 0.0,
                     f"{h['loss'][-1]:.3f}"))
    return emit(rows)


if __name__ == "__main__":
    run()
