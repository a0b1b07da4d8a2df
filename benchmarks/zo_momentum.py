"""Beyond-paper ablation: LeZO-SGD vs memory-free LeZO-momentum.

Same budget, same sparsity, same seeds — momentum regenerates its K=8
directions from seeds (state = 8 scalars), so memory parity with MeZO
holds while convergence accelerates substantially.
"""
from __future__ import annotations

from benchmarks.common import emit
from repro.configs import opt
from repro.core import zo
from repro.data import synthetic
from repro.train.trainer import Trainer, TrainConfig

MCFG = opt.opt_tiny(layers=4, d_model=128, vocab=512)
TASK = synthetic.TaskConfig(vocab=512, seq_len=64, n_classes=2,
                            signal_rate=0.35)


def run():
    rows = []
    for mode in ("zo", "zo_momentum"):
        tr = Trainer(MCFG, TASK,
                     TrainConfig(steps=300, batch_size=16, eval_every=300,
                                 log_every=100, mode=mode),
                     zo_cfg=zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=3))
        h = tr.train()
        acc = h["val_acc"][-1] if h["val_acc"] else -1
        rows.append((f"lezo75_{mode}", 0.0,
                     f"final_loss={h['loss'][-1]:.3f} val_acc={acc:.3f}"))
    return emit(rows)


if __name__ == "__main__":
    run()
