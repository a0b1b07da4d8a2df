"""Virtual-perturbation fused runtime: materialized vs virtual step time.

A materialized two_point step is 2 forwards + 3 parameter axpy sweeps
(perturb, perturb, fused restore+update); the virtual backend
(``repro.fused``, DESIGN.md §10) evaluates both probes against
in-kernel-regenerated perturbed weights, so the step is 2 (slightly
heavier) forwards + 1 update sweep — and with paired probes (the
default) the ±εz pair rides ONE stacked forward whose kernels load each
W tile and regenerate each z tile once for both signs.  This benchmark
times full optimizer steps at LeZO sparsity rho in {0, 0.5, 0.75},
times paired vs unpaired virtual stepping, and *proves* the pairing's
W-traffic halving structurally: the eager forward runs under an obs
tracer whose ``w_tile_loads`` / ``z_regens`` counters come from the
same grid arithmetic the kernel executes (host-side Python ints —
CPU-provable, no wall clock involved).  Writes the ``BENCH_fused.json``
trajectory (``--json``; CI uploads it) with a ``tripwires`` block that
``--check`` and ``benchmarks/run.py --check`` gate on.

On CPU the virtual rows use the pure-JAX oracle (``virtual_ref`` — the
same floats the Pallas kernels produce, which the test suite pins in
interpret mode); timing the Pallas *interpreter* would measure the
emulator, not the kernel, so the kernel path gets a single microbench
row for reference instead.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import (emit, make_batch, rows_to_json,  # noqa: E402
                               timeit, write_json)
from repro import estimators, fused, obs  # noqa: E402
from repro.core import zo  # noqa: E402
from repro.estimators import costs  # noqa: E402
from repro.fused import matmul as fused_matmul  # noqa: E402
from repro.fused import ref as fused_ref  # noqa: E402
from repro.kernels import default_interpret  # noqa: E402
from repro.models import lm  # noqa: E402

RHOS = (0.0, 0.5, 0.75)


def _bench_spec(preset="bench-smoke"):
    """The experiment spec this benchmark is a projection of — model and
    optimizer knobs come from the shared preset, not inline flags."""
    from repro import api
    return api.presets.get(preset)


def _step(mcfg, espec, n_drop, forward_backend, paired=True):
    import dataclasses

    from repro import api
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    ecfg = dataclasses.replace(api.derive(espec).est_cfg, n_drop=n_drop,
                               forward_backend=forward_backend,
                               paired_probes=paired)
    loss_fn = lambda p, b, perturb=None: lm.lm_loss(mcfg, p, b,
                                                    perturb=perturb)
    step, init = estimators.make_step(loss_fn, spec, ecfg)
    return params, jax.jit(step), init


def structural_counters(mcfg, params, tokens):
    """The pairing's halving claim as deterministic Python ints: run the
    eager forward under a counting tracer for (a) ONE paired ±εz ctx and
    (b) the two unpaired ±εz ctxs it replaces, and read back the
    ``w_tile_loads`` / ``z_regens`` counters — host-side grid arithmetic
    (``fused.matmul.grid_cells``), identical for ref and pallas impls,
    so the halving is provable on CPU where wall-clock speedups are not.

    Runs under ``jax.disable_jit()``: the transformer blocks live inside
    a ``lax.scan`` whose body traces once, and obs counters no-op under
    tracing — disabling jit turns the scan into an eager Python loop so
    every layer's lens call actually counts.
    """
    seed, eps = jnp.uint32(7), 1e-3

    def count(ctxs):
        tr = obs.Tracer(sinks=[])
        with obs.use(tr), jax.disable_jit():
            for ctx in ctxs:
                jax.block_until_ready(
                    lm.forward(mcfg, params, tokens, perturb=ctx))
        return {"w_tile_loads": tr.counters.get(obs.CTR_WLOAD, 0),
                "z_regens": tr.counters.get(obs.CTR_ZREGEN, 0)}

    paired = count([fused.make_pair_ctx(seed, eps, None, "virtual_ref")])
    unpaired = count([fused.make_ctx(seed, eps, None, "virtual_ref"),
                      fused.make_ctx(seed, -eps, None, "virtual_ref")])
    return {"paired": paired, "unpaired": unpaired}


def build_tripwires(struct):
    """-> {name: {ok, value, limit, note}} (run.py --check collects)."""
    tw = {}
    for key in ("w_tile_loads", "z_regens"):
        p, u = struct["paired"][key], struct["unpaired"][key]
        tw[f"paired_{key}_halved"] = {
            "ok": p > 0 and 2 * p == u,
            "value": {"paired": p, "unpaired": u},
            "limit": "paired == unpaired / 2, both > 0",
            "note": f"per-forward-pass {key} (host-side grid arithmetic "
                    "over every block matmul; the ±εz pair shares one "
                    "stacked kernel pass)"}
    return tw


def run(smoke=False, json_path=None, preset="bench-smoke", check=False):
    from repro import api
    espec = _bench_spec(preset)
    d = api.derive(espec)
    mcfg, seq = d.model_cfg, espec.model.seq_len
    batch = make_batch(mcfg, espec.run.batch_size if smoke else 16, seq)
    iters = 3 if smoke else 5
    rows, cells = [], []
    for rho in RHOS:
        n_drop = int(rho * mcfg.num_layers)
        times = {}
        for fb in ("materialized", "virtual_ref"):
            params, step, init = _step(mcfg, espec, n_drop, fb)
            t = timeit(lambda: step(params, init(), batch, jnp.int32(0),
                                    jnp.uint32(1)), warmup=1, iters=iters)
            times[fb] = t
            sweeps = costs.step_counts("two_point",
                                       forward_backend=fb)["axpy_sweeps"]
            rows.append((f"steptime_{fb}_rho{rho:g}", t * 1e6,
                         f"axpy_sweeps={sweeps}"))
        speedup = times["materialized"] / times["virtual_ref"]
        rows.append((f"virtual_speedup_rho{rho:g}", 0.0, f"{speedup:.2f}x"))
        cells.append({"rho": rho,
                      "materialized_s": times["materialized"],
                      "virtual_s": times["virtual_ref"],
                      "speedup": speedup})

    # Paired vs unpaired virtual stepping: same estimator, same floats
    # (tests/test_fused.py pins bit-identity), ±εz stacked into one
    # forward vs two sequential probe forwards.
    times_pair = {}
    for paired in (True, False):
        params, step, init = _step(mcfg, espec, 0, "virtual_ref",
                                   paired=paired)
        t = timeit(lambda: step(params, init(), batch, jnp.int32(0),
                                jnp.uint32(1)), warmup=1, iters=iters)
        times_pair[paired] = t
        name = "paired" if paired else "unpaired"
        rows.append((f"steptime_virtual_{name}_rho0", t * 1e6,
                     "1 stacked ±εz forward" if paired
                     else "2 probe forwards"))
    rows.append(("paired_speedup_rho0", 0.0,
                 f"{times_pair[False] / times_pair[True]:.2f}x"))

    # Structural proof of the halving (deterministic, wall-clock-free).
    struct = structural_counters(mcfg, params, batch["tokens"])
    for side in ("paired", "unpaired"):
        for key in ("w_tile_loads", "z_regens"):
            rows.append((f"struct_{side}_{key}", 0.0,
                         str(struct[side][key])))
    tripwires = build_tripwires(struct)

    # Pallas kernel reference point: one fused pmatmul tile pass vs its
    # oracle.  Interpret mode follows the platform: off a TPU the kernel
    # runs in the Pallas interpreter and the number is emulator-bound.
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 512), jnp.float32)
    seed = jnp.uint32(7)
    t_k = timeit(lambda: fused_matmul.pmatmul(x, w, seed, 1e-3),
                 warmup=1, iters=iters)
    t_r = timeit(jax.jit(lambda: fused_ref.pmatmul(x, w, seed, 1e-3)),
                 warmup=1, iters=iters)
    if default_interpret():
        rows.append(("pmatmul_pallas_interpret_512", t_k * 1e6,
                     "emulator-bound"))
    else:
        rows.append(("pmatmul_pallas_mosaic_512", t_k * 1e6,
                     "compiled by Mosaic"))
    rows.append(("pmatmul_ref_512", t_r * 1e6, "oracle (XLA-compiled)"))

    emit(rows)
    if json_path:
        write_json(json_path, {
            "bench": "fused_forward",
            "model": mcfg.name,
            "impl": "virtual_ref on CPU (kernel pinned vs oracle by "
                    "tests/test_fused.py in interpret mode)",
            "cells": cells,
            "structural": struct,
            "tripwires": tripwires,
            "rows": rows_to_json(rows),
        }, spec=espec)
    if check:
        bad = sorted(n for n, r in tripwires.items() if not r["ok"])
        for n in bad:
            r = tripwires[n]
            print(f"TRIPWIRE {n} value={r['value']!r} limit={r['limit']!r}",
                  file=sys.stderr)
        if bad:
            raise SystemExit(f"fused bench tripwires failed: {bad}")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--preset", default="bench-smoke",
                    help="experiment spec preset the bench runs off "
                         "(repro.api.presets)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_fused.json trajectory here")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when a structural tripwire "
                         "(±εz pairing must halve W-tile loads and "
                         "z regens) fails")
    args = ap.parse_args()
    run(smoke=args.smoke, json_path=args.json, preset=args.preset,
        check=args.check)
