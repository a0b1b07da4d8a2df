"""Legacy serving shim + the lockstep generate loop (DESIGN.md §12).

``python -m repro.launch serve`` is the real surface: it drives the
continuous-batching paged engine (``repro.serving``) when the arch
supports it and falls back to the lockstep ``generate`` below (one
prompt batch in, all lanes decode in step) otherwise — which also
exercises the prefill/serve_step code paths the dry-run lowers for the
decode_32k / long_500k cells, at CPU-runnable sizes.  ``benchmarks/
serving.py`` measures the two against each other.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import lm


@functools.partial(jax.jit, static_argnums=(0,))
def _greedy_step(cfg, params, caches, tok, pos):
    # module-level jit: one compile per (cfg, cache shape), shared by
    # every generate() call
    lg, caches = lm.serve_step(cfg, params, caches, tok, pos)
    return jnp.argmax(lg, -1)[:, None].astype(jnp.int32), caches


def generate(cfg, params, tokens, gen_steps: int, max_seq: int):
    B, S = tokens.shape
    logits, caches = lm.prefill(cfg, params, tokens, max_seq=max_seq)
    out = [jnp.argmax(logits, -1)[:, None]]
    tok = out[0].astype(jnp.int32)
    for i in range(gen_steps - 1):
        tok, caches = _greedy_step(cfg, params, caches, tok, jnp.int32(S + i))
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    """Shim over ``python -m repro.launch serve`` (launch/cli.py):
    spec flags (--arch/--variant/--seed and any --set) plus the serve
    extras --batch/--prompt-len/--gen.  The default arch moves with the
    legacy surface via the implied override below."""
    import sys

    from repro.launch import cli
    argv = list(sys.argv[1:] if argv is None else argv)
    if not any(a == "--arch" or a.startswith("--arch=")
               or a == "--model.arch" or a.startswith("--model.arch=")
               for a in argv):
        argv = ["--arch", "xlstm-350m"] + argv
    return cli.main(["serve"] + argv)


if __name__ == "__main__":
    main()
