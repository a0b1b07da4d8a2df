"""Beyond-paper: the estimator quality/cost frontier (DESIGN.md §5, §6).

two_point vs one_sided(q in {4,16}) vs averaged(q=4), all at LeZO
sparsity 0.75:

  * wall-clock per optimizer step (CPU) — multi-probe estimators pay
    more compute per step, visible here;
  * steps-to-target-loss on the synthetic classification task — the
    FZOO claim: q batched one-sided probes cut the *step count* to a
    fixed loss.  Each estimator runs at the variance-matched learning
    rate lr * sqrt(q) (q probes cut gradient variance ~q-fold, which is
    exactly what lets FZOO push the step size).

The target is the two_point baseline's final smoothed training loss at
a fixed step budget; ``steps`` reports when each estimator's smoothed
loss first reaches it (capped at the budget).
"""
from __future__ import annotations

import sys
from pathlib import Path

# runnable standalone (`make bench-smoke`) as well as via benchmarks/run.py
sys.path.insert(0, str(Path(__file__).parent.parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, make_batch, timeit
from repro import estimators
from repro.configs import opt
from repro.core import zo
from repro.data import synthetic
from repro.models import lm

GRID = (("two_point", 1), ("one_sided", 4), ("one_sided", 16),
        ("averaged", 4))
_SMOOTH = 20  # steps in the running-mean loss window


def _estimator_step(mcfg, name, q, n_drop, lr, eps=1e-3):
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    ecfg = estimators.EstimatorConfig(name=name, q=q, n_drop=n_drop, lr=lr,
                                      eps=eps)
    loss_fn = lambda p, b: lm.lm_loss(mcfg, p, b)
    # no buffer donation: the timing loop re-feeds the same params
    step, init = estimators.make_step(loss_fn, spec, ecfg)
    return params, jax.jit(step), init


def _loss_curve(name, q, lr, steps, mcfg, task):
    params, step, init = _estimator_step(mcfg, name, q,
                                         n_drop=int(0.75 * mcfg.num_layers),
                                         lr=lr)
    data = synthetic.make_dataset(task, 2048)
    stream = synthetic.batches(data, 16, steps, seed=7)
    p, st = params, init()
    losses = []
    for t, np_batch in enumerate(stream):
        batch = {k: jnp.asarray(v) for k, v in np_batch.items()
                 if k != "class_labels"}
        p, st, m = step(p, st, batch, jnp.int32(t), jnp.uint32(1))
        losses.append(float(m["loss"]))
    return np.asarray(losses)


def _smoothed(losses):
    c = np.convolve(losses, np.ones(_SMOOTH) / _SMOOTH, mode="valid")
    return c


def run(smoke=False, preset="bench-smoke"):
    # the sweep's model / batch / eps / lr / sparsity come from the shared
    # experiment-spec preset, so CI and the CLI can't drift on them
    from repro import api
    espec = api.presets.get(preset)
    d = api.derive(espec)
    rows = []
    budget = espec.run.steps if smoke else 300

    # ---- wall-clock per step at the preset's sparsity -------------------
    mcfg, seq = d.model_cfg, espec.model.seq_len
    batch = make_batch(mcfg, espec.run.batch_size, seq)
    n_drop = d.n_drop
    for name, q in GRID:
        params, step, init = _estimator_step(mcfg, name, q, n_drop,
                                             espec.optimizer.lr,
                                             eps=espec.optimizer.eps)
        counts = estimators.costs.step_counts(name, q=q)
        t = timeit(lambda: step(params, init(), batch, jnp.int32(0),
                                jnp.uint32(1)), warmup=1, iters=3)
        rows.append((f"steptime_{name}_q{q}", t * 1e6,
                     f"forwards={counts['forwards']}"))

    # ---- steps to the two_point target loss -----------------------------
    mcfg = opt.opt_tiny(layers=4, d_model=128, vocab=512)
    task = synthetic.TaskConfig(vocab=512, seq_len=64, n_classes=2,
                                signal_rate=0.35)
    base_lr = 3e-4
    curves = {}
    for name, q in GRID:
        lr = base_lr * float(np.sqrt(q))      # variance-matched step size
        curves[(name, q)] = _smoothed(_loss_curve(name, q, lr, budget,
                                                  mcfg, task))
    target = curves[("two_point", 1)][-1]
    rows.append(("target_loss_two_point", 0.0, f"{target:.3f}"))
    for name, q in GRID:
        c = curves[(name, q)]
        hit = np.nonzero(c <= target)[0]
        steps = int(hit[0]) + _SMOOTH if hit.size else budget
        rows.append((f"steps_to_target_{name}_q{q}", 0.0, f"{steps}"))
    return emit(rows)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--preset", default="bench-smoke",
                    help="experiment spec preset the bench runs off "
                         "(repro.api.presets)")
    args = ap.parse_args()
    run(smoke=args.smoke, preset=args.preset)
