"""Paper Table 4: ZO x PEFT — MeZO/LeZO with LoRA and prefix tuning."""
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
    grid = [("mezo_lora", "lora", 0, 1e-3, 1e-2),
            ("lezo_lora", "lora", 2, 1e-3, 1e-2),       # paper: 50% sparse
            ("mezo_prefix", "prefix", 0, 1e-2, 1e-1),
            ("lezo_prefix", "prefix", 3, 1e-2, 1e-1)]   # paper: 75% sparse
    for name, peft, n_drop, lr, eps in grid:
        tr = Trainer(MCFG, TASK,
                     TrainConfig(steps=300, batch_size=16, eval_every=300,
                                 log_every=0, peft=peft),
                     zo_cfg=zo.ZOConfig(eps=eps, lr=lr, n_drop=n_drop))
        h = tr.train()
        acc = h["val_acc"][-1] if h["val_acc"] else -1
        vl = h["val_loss"][-1] if h["val_loss"] else -1
        rows.append((name, 0.0, f"acc={acc:.3f} loss={vl:.3f}"))
    return emit(rows)


if __name__ == "__main__":
    run()
