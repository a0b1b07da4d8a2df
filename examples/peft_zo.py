"""LeZO composed with PEFT (paper Table 4): LoRA and prefix tuning,
each a two-line spec diff on the shared preset (DESIGN.md §11).

Run:  PYTHONPATH=src python examples/peft_zo.py

Only the PEFT parameters are perturbed/updated; the base model is
frozen.  LeZO's layer dropping applies to the PEFT tree's layer groups.
"""
import sys, pathlib
sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro import api

BASE = api.with_overrides(api.preset("tiny-smoke"), {
    "task.signal_rate": 0.35, "model.seq_len": 64,
    "optimizer.n_drop": 2,
    "run.steps": 300, "run.batch_size": 16,
    "run.eval_every": 100, "run.log_every": 100,
})

for peft, lr, eps in [("lora", 3e-3, 1e-2), ("prefix", 1e-2, 1e-1)]:
    spec = api.with_overrides(BASE, {"runtime.peft": peft,
                                     "optimizer.lr": lr,
                                     "optimizer.eps": eps})
    h = api.run(spec)["history"]
    print(f"LeZO({peft}): loss " + " -> ".join(f"{x:.3f}" for x in h["loss"])
          + f"   val_acc: {h['val_acc']}")
