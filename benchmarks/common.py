"""Shared benchmark utilities."""
from __future__ import annotations

import json
import platform
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import opt  # noqa: E402
from repro.core import zo  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402

# every benchmark script imports this module before its first compile
compile_cache.enable()


def timeit(fn, *args, warmup=2, iters=5):
    """Median wall time (s) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_model(seq=32):
    """A CPU-timeable model whose params/token ratio mirrors the paper's
    short-sequence fine-tuning regime (perturb work ~ forward work).
    The shape is the registry's ``bench`` variant — the same model the
    ``bench-smoke`` spec preset resolves to — so every benchmark suite
    measures one config."""
    return opt.bench(), seq


def make_batch(cfg, batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)
    return {"tokens": toks, "labels": toks,
            "loss_mask": jnp.ones((batch, seq), jnp.float32)}


def make_zo_parts(cfg, n_drop, lr=1e-4, eps=1e-3):
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    zcfg = zo.ZOConfig(eps=eps, lr=lr, n_drop=n_drop)
    step = jax.jit(zo.make_zo_step(lambda p, b: lm.lm_loss(cfg, p, b),
                                   spec, zcfg))
    return params, spec, zcfg, step


def emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


def rows_to_json(rows):
    """The emit() row triples as JSON-ready dicts."""
    return [{"name": n, "us_per_call": float(us), "derived": str(d)}
            for n, us, d in rows]


def write_json(path, payload, spec=None):
    """Write a BENCH_*.json trajectory file with environment metadata.
    ``spec`` (a ``repro.api.Experiment``) is embedded when given, so
    bench artifacts carry the exact experiment they measured."""
    payload = dict(payload)
    if spec is not None:
        from repro import api
        payload["spec"] = api.to_dict(spec)
    payload.setdefault("meta", {})
    payload["meta"].update({
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
    })
    p = Path(path)
    if p.parent != Path("."):
        p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {path}")
    return payload
