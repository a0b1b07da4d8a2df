"""Paper Fig. 6: computational speedup vs input token length.

LeZO's absolute saving per step is fixed (perturb/update bytes); the
forward grows with tokens, so speedup decays with sequence length.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import bench_model, emit, make_batch, make_zo_parts, timeit


def run():
    rows = []
    cfg, _ = bench_model()
    N = cfg.num_layers
    for seq in (16, 32, 64, 128):
        batch = make_batch(cfg, 16, seq)
        t = {}
        for name, nd in [("mezo", 0), ("lezo", int(0.75 * N))]:
            params, _, _, step = make_zo_parts(cfg, nd)
            t[name] = timeit(step, params, batch, jnp.int32(0), jnp.uint32(1))
        rows.append((f"seqlen_{seq}", t["mezo"] * 1e6,
                     f"speedup={t['mezo'] / t['lezo']:.2f}x"))
    return emit(rows)


if __name__ == "__main__":
    run()
