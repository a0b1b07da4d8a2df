"""Continuous batching vs lockstep serving on mixed-length traffic.

The lockstep loop (launch/serve.generate) pays the convoy tax twice:
every prompt in a batch is padded to the longest, and every lane decodes
until the *slowest* request's budget — a batch with one 8x-longer
generation runs 8x decode steps for everyone.  The paged engine
(repro/serving/, DESIGN.md §12) frees lanes the moment a request
finishes and admits the next one, so wall time tracks *total* tokens,
not ``batches x max``.

Traffic: each arrival group of ``max_lanes`` requests holds one
long-generation request and ``lanes-1`` short ones (the convoy shape).
Writes BENCH_serving.json — request throughput, p50/p99 latency, engine
vs lockstep speedup — which CI uploads next to BENCH_fused.json.  The
acceptance target is engine >= 2x lockstep request throughput
(measured 2.0-2.6x on CPU smoke sizes, recorded in the JSON);
``--check`` enforces the MIN_SPEEDUP regression tripwire (1.5x, below
which continuous batching is broken, with headroom for noisy CI boxes)
and ``make bench-smoke`` runs with it.

Two prefix-sharing scenarios ride along (DESIGN.md §12): a
shared-system-prompt convoy run twice — ``prefix_cache`` off then on —
and a zipfian repeat workload.  The JSON carries page_hit_rate,
cow_copies, and the on/off wall-time ratio; ``--check`` additionally
trips when the convoy hit rate drops below MIN_HIT_RATE, when sharing
runs slower than not sharing, or when a drained engine leaks pages the
trie does not account for.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from repro import api, serving  # noqa: E402
from repro.models import lm  # noqa: E402

LONG_GEN, SHORT_GENS = 64, (2, 4, 6)
PROMPT_RANGE = (4, 16)
MIN_SPEEDUP = 1.5     # --check tripwire; the acceptance target is 2x

# --- prefix-sharing scenario (DESIGN.md §12) -------------------------
SYS_LEN = 40          # shared system prompt: 5 full pages — NOT chunk-
                      # aligned, so the re-run chunk COWs its last page
TAIL_RANGE = (2, 9)   # per-request unique suffix
SHARE_GENS = (3, 4, 5)
MIN_HIT_RATE = 0.5    # --check: shared-convoy page hit rate floor
MIN_SHARE_RATIO = 1.0  # --check: sharing-on must not run slower than off


def make_traffic(rng, n_requests, lanes, vocab, long_gen, short_gens):
    """One long-generation request per arrival group of ``lanes``."""
    reqs = []
    for rid in range(n_requests):
        gen = (long_gen if rid % lanes == 0
               else int(short_gens[rid % len(short_gens)]))
        plen = int(rng.integers(*PROMPT_RANGE))
        reqs.append(serving.Request(
            rid=rid, tokens=rng.integers(0, vocab, plen).tolist(),
            max_new_tokens=gen, seed=rid))
    return reqs


def make_lockstep(cfg, params, lanes, prompt_bucket, max_seq):
    """The old loop as a *fair* baseline: arrival-order batches of
    ``lanes``, prompts padded to one fixed bucket and caches to one
    fixed ``max_seq``, with prefill/decode jitted ONCE up front — the
    measured gap is pure convoy tax, not compile time (the naive
    launch/serve.generate re-jits per call and would flatter the
    engine)."""
    pstep = jax.jit(lambda p, t: lm.prefill(cfg, p, t, max_seq=max_seq))

    @jax.jit
    def dstep(p, caches, tok, pos):
        lg, caches = lm.serve_step(cfg, p, caches, tok, pos)
        return jnp.argmax(lg, -1)[:, None].astype(jnp.int32), caches

    def serve(reqs):
        latencies, t0 = {}, time.perf_counter()
        for base in range(0, len(reqs), lanes):
            batch = reqs[base:base + lanes]
            toks = np.zeros((lanes, prompt_bucket), np.int32)
            for i, r in enumerate(batch):
                toks[i, :len(r.tokens)] = r.tokens
            gen = max(r.max_new_tokens for r in batch)
            logits, caches = pstep(params, jnp.asarray(toks))
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            for s in range(gen - 1):
                tok, caches = dstep(params, caches, tok,
                                    jnp.int32(prompt_bucket + s))
            jax.block_until_ready(tok)
            done = time.perf_counter() - t0
            for r in batch:
                latencies[r.rid] = done   # everyone waits for the convoy
        return time.perf_counter() - t0, latencies
    return serve


def make_shared_traffic(rng, n_requests, vocab):
    """Shared-system-prompt convoy: every request opens with the same
    SYS_LEN tokens (the per-tenant prompt shape) plus a short unique
    tail — the workload prefix caching exists for."""
    system = rng.integers(0, vocab, SYS_LEN).tolist()
    return [serving.Request(
        rid=rid,
        tokens=system + rng.integers(
            0, vocab, int(rng.integers(*TAIL_RANGE))).tolist(),
        max_new_tokens=int(SHARE_GENS[rid % len(SHARE_GENS)]), seed=rid)
        for rid in range(n_requests)]


def make_zipf_traffic(rng, n_requests, vocab, n_prompts=6):
    """Zipf-distributed repeats over a small prompt population —
    realistic cache-hit structure without a designed shared prefix."""
    population = [rng.integers(0, vocab,
                               int(rng.integers(16, 41))).tolist()
                  for _ in range(n_prompts)]
    ranks = np.minimum(rng.zipf(1.3, size=n_requests) - 1, n_prompts - 1)
    return [serving.Request(rid=rid, tokens=population[int(k)],
                            max_new_tokens=int(SHARE_GENS[rid % 3]),
                            seed=rid)
            for rid, k in enumerate(ranks)]


def _serve_prefix(cfg, params, sv, reqs, prefix_cache):
    """One engine pass over ``reqs``; returns (seconds, engine) with the
    drain leak count asserted into the engine's scheduler."""
    import dataclasses
    engine = serving.Engine(cfg, params,
                            dataclasses.replace(sv,
                                                prefix_cache=prefix_cache))
    warm = make_traffic(np.random.default_rng(1), sv.max_lanes,
                        sv.max_lanes, cfg.vocab, 2, (2,))
    engine.run(warm)
    if prefix_cache:
        # a same-prompt pair with a non-chunk-aligned prefix forces one
        # COW, compiling the page-clone step outside the measured run
        wrng = np.random.default_rng(2)
        wsys = wrng.integers(0, cfg.vocab, SYS_LEN).tolist()
        for i in range(2):      # sequential: second run hits, COWs
            engine.run([serving.Request(rid=10 ** 6 + i,
                                        tokens=wsys + [int(i)] * 3,
                                        max_new_tokens=2, seed=i)])
    sched = engine.sched
    sched.prefix_hits = sched.prefix_lookups = 0     # report post-warm
    sched.cow_copies = sched.trie_evictions = 0
    t0 = time.perf_counter()
    engine.run(reqs)
    return time.perf_counter() - t0, engine


def _leaked(engine):
    """Pages still allocated after drain that the trie does not hold."""
    trie = engine.sched.trie
    return engine.pool.in_use - (trie.reclaimable() if trie else 0)


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def run(smoke: bool = False, json_path=None, preset: str = "bench-smoke",
        check: bool = False):
    n_requests = 16 if smoke else 32
    long_gen = LONG_GEN
    # pool sized to the worst case (lanes x pages-per-request + trash):
    # the arena rides through every bucketed call, so oversizing it is
    # pure per-step copy tax (DESIGN.md §12)
    spec = api.with_overrides(api.preset(preset), {
        "model.variant": "tiny",
        "serving.page_size": 8, "serving.n_pages": 44,
        "serving.max_lanes": 4, "serving.prefill_chunk": 16,
        "serving.max_seq": 96,
        "serving.max_new_tokens": long_gen})
    cfg = api.validate(spec)
    sv = spec.serving
    params = lm.init_params(cfg, jax.random.PRNGKey(spec.run.seed))
    rng = np.random.default_rng(spec.run.seed)
    reqs = make_traffic(rng, n_requests, sv.max_lanes, cfg.vocab,
                        long_gen, SHORT_GENS)

    # warm both paths so the comparison is steady-state, not compile time
    warm = make_traffic(np.random.default_rng(1), sv.max_lanes,
                        sv.max_lanes, cfg.vocab, 2, (2,))
    engine = serving.Engine(cfg, params, sv)
    engine.run(warm)
    engine.n_prefill_calls = engine.n_decode_steps = 0   # report post-warm
    lockstep = make_lockstep(cfg, params, sv.max_lanes, PROMPT_RANGE[1],
                             max_seq=PROMPT_RANGE[1] + long_gen + 1)
    lockstep(warm)

    t0 = time.perf_counter()
    results = engine.run(reqs)
    dt_engine = time.perf_counter() - t0
    lat_engine = [r.t_done - r.t_submit for r in results]
    assert engine.n_compiles() == 2, "bucket promise broken"

    dt_lock, lat_lock = lockstep(reqs)

    # --- prefix-sharing scenarios (same spec, prefix_cache toggled) ---
    share_rng = np.random.default_rng(spec.run.seed + 1)
    shared = make_shared_traffic(share_rng, n_requests, cfg.vocab)
    dt_off, eng_off = _serve_prefix(cfg, params, sv, shared, False)
    dt_on, eng_on = _serve_prefix(cfg, params, sv, shared, True)
    share_ratio = dt_off / dt_on
    hit_rate = eng_on.sched.page_hit_rate
    cow_copies = eng_on.sched.cow_copies
    leaked = _leaked(eng_on) + _leaked(eng_off)
    dt_zipf, eng_zipf = _serve_prefix(
        cfg, params, sv, make_zipf_traffic(share_rng, n_requests,
                                           cfg.vocab), True)
    zipf_hit_rate = eng_zipf.sched.page_hit_rate
    leaked += _leaked(eng_zipf)

    rps_e, rps_l = n_requests / dt_engine, n_requests / dt_lock
    speedup = rps_e / rps_l
    rows = common.emit([
        ("serving_engine_req", dt_engine * 1e6 / n_requests,
         f"{rps_e:.1f} req/s ({engine.n_prefill_calls} prefill + "
         f"{engine.n_decode_steps} decode calls)"),
        ("serving_lockstep_req", dt_lock * 1e6 / n_requests,
         f"{rps_l:.1f} req/s"),
        ("serving_engine_p50_ms", _pct(lat_engine, 50) * 1e3,
         f"p99 {_pct(lat_engine, 99) * 1e3:.0f} ms"),
        ("serving_lockstep_p50_ms", _pct(list(lat_lock.values()), 50) * 1e3,
         f"p99 {_pct(list(lat_lock.values()), 99) * 1e3:.0f} ms"),
        ("serving_speedup", 0.0, f"{speedup:.2f}x request throughput"),
        ("serving_shared_prefix_on", dt_on * 1e6 / n_requests,
         f"hit rate {hit_rate:.2f}, {cow_copies} COW copies"),
        ("serving_shared_prefix_off", dt_off * 1e6 / n_requests,
         f"{share_ratio:.2f}x from sharing"),
        ("serving_zipf_hit_rate", 0.0,
         f"{zipf_hit_rate:.2f} over zipf(1.3) repeats"),
    ])
    if json_path:
        common.write_json(json_path, {
            "bench": "serving",
            "traffic": {"n_requests": n_requests, "long_gen": long_gen,
                        "short_gens": list(SHORT_GENS),
                        "prompt_range": list(PROMPT_RANGE)},
            "engine": {"seconds": dt_engine, "req_per_s": rps_e,
                       "p50_s": _pct(lat_engine, 50),
                       "p99_s": _pct(lat_engine, 99),
                       "prefill_calls": engine.n_prefill_calls,
                       "decode_steps": engine.n_decode_steps,
                       "compiles": engine.n_compiles()},
            "lockstep": {"seconds": dt_lock, "req_per_s": rps_l,
                         "p50_s": _pct(list(lat_lock.values()), 50),
                         "p99_s": _pct(list(lat_lock.values()), 99)},
            "speedup": speedup,
            "sharing": {"on_seconds": dt_on, "off_seconds": dt_off,
                        "ratio": share_ratio,
                        "page_hit_rate": hit_rate,
                        "cow_copies": cow_copies,
                        "trie_evictions": eng_on.sched.trie_evictions,
                        "zipf_seconds": dt_zipf,
                        "zipf_hit_rate": zipf_hit_rate,
                        "leaked_pages": leaked,
                        "sys_len": SYS_LEN, "tail_range": list(TAIL_RANGE)},
            "tripwires": {
                "serving_speedup": {
                    "ok": speedup >= MIN_SPEEDUP, "value": speedup,
                    "limit": MIN_SPEEDUP,
                    "note": "engine vs lockstep request throughput "
                            "(continuous batching broken below this)"},
                "serving_page_hit_rate": {
                    "ok": hit_rate >= MIN_HIT_RATE, "value": hit_rate,
                    "limit": MIN_HIT_RATE,
                    "note": "shared-system-prompt convoy: fraction of "
                            "prompt pages served from the prefix trie"},
                "serving_sharing_throughput": {
                    "ok": share_ratio >= MIN_SHARE_RATIO,
                    "value": share_ratio, "limit": MIN_SHARE_RATIO,
                    "note": "sharing-on vs sharing-off wall time on the "
                            "shared convoy (below 1.0 sharing costs more "
                            "than it saves)"},
                "serving_page_leaks": {
                    "ok": leaked == 0, "value": leaked, "limit": 0,
                    "note": "pages still allocated after drain that the "
                            "prefix trie does not account for"}},
            "rows": common.rows_to_json(rows),
        }, spec=spec)
    if check:
        fails = []
        if speedup < MIN_SPEEDUP:
            fails.append(f"speedup {speedup:.2f}x < {MIN_SPEEDUP}x")
        if hit_rate < MIN_HIT_RATE:
            fails.append(f"page hit rate {hit_rate:.2f} < {MIN_HIT_RATE}")
        if share_ratio < MIN_SHARE_RATIO:
            fails.append(f"sharing ratio {share_ratio:.2f}x < "
                         f"{MIN_SHARE_RATIO}x")
        if leaked:
            fails.append(f"{leaked} leaked pages after drain")
        if fails:
            raise SystemExit("serving tripwires failed: "
                             + "; ".join(fails))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_serving.json here")
    ap.add_argument("--preset", default="bench-smoke")
    ap.add_argument("--check", action="store_true",
                    help=f"exit nonzero when speedup < {MIN_SPEEDUP}x, "
                         f"convoy page hit rate < {MIN_HIT_RATE}, sharing "
                         "runs slower than not sharing, or pages leak")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke, json_path=args.json, preset=args.preset,
        check=args.check)


if __name__ == "__main__":
    main()
