"""Serving cells: the paged ``serving.Engine`` under an open loop.

Set-up makes the weights on the device from the seed, builds the engine
with the cell's settings, and warms its two buckets (one prefill chunk
shape, one decode shape) with a request of its own.  Arrivals then
start ``ramp_s`` before the window, so the window opens on a running
engine, and the ramp counts in setup_s.  One host loop submits each
request once its scheduled time has passed and calls ``Engine.step``
while anything is queued or running.  The window closes after
``seconds``; arrivals stop, and the engine drains what arrived in the
window, for at most ``drain_s`` more.

Latencies are taken from each request's scheduled arrival, so a late
submit or a queue counts: TTFT to the first token, TPOT over the rest.
A request that is refused, or unfinished at the end of the drain, is
failed and enters the tails as never finished.

The check runs after the drain, once the engine is freed: a sample of
the window's finished requests drawn from the seed, the longest among
them, is run through the reference in float32, and for every served
token the gap by which its logit lies below the reference's best is
read.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from typing import Dict, List

import numpy as np

import harness
from reference import opt as ref
from traffic import generate


class CallLog:
    """Wraps the engine's two jitted buckets to log, from the host's own
    scheduler state, what each call computes: the lane and span of a
    prefill chunk, the positions of a decode step's live lanes."""

    def __init__(self, eng):
        self.eng, self.on = eng, False
        self.prefill: List[tuple] = []       # (start, real tokens, final)
        self.decode: List[List[int]] = []    # live lanes' positions
        p, d = eng._pstep, eng._dstep
        eng._pstep = lambda *a: (self._log_prefill(), p(*a))[1]
        eng._dstep = lambda *a: (self._log_decode(), d(*a))[1]
        self.pstep, self.dstep = p, d

    def _log_prefill(self):
        if not self.on:
            return
        s = self.eng.sched
        lane = s.lanes[min(s.prefilling(), key=lambda j: s.lanes[j].admit_seq)]
        start = lane.next_chunk * s.prefill_chunk
        real = max(0, min(start + s.prefill_chunk, lane.prompt_len) - start)
        self.prefill.append((start, real,
                             start + s.prefill_chunk >= lane.padded_len))

    def _log_decode(self):
        if self.on:
            s = self.eng.sched
            self.decode.append([s.lanes[i].pos for i in s.decoding()
                                if not self.eng._done(s.lanes[i])])

    def n_compiles(self) -> int:
        return self.pstep._cache_size() + self.dstep._cache_size()


def build(cfg: Dict, wl: Dict, seed: int):
    """Weights from the seed, the engine with the cell's settings, both
    of its buckets compiled by a warm-up request of its own."""
    from repro import api
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Request
    params = ref.to_tree(ref.make_weights(cfg, seed))
    eng = Engine(harness.model_config(cfg), params, api.Serving(**wl["engine"]),
                 clock=time.perf_counter)
    log = CallLog(eng)
    chunk = wl["engine"]["prefill_chunk"]
    eng.run([Request(rid=-1, tokens=[generate.FIRST_ID] * (chunk + 1),
                     max_new_tokens=3)])
    if log.n_compiles() != 2:
        raise SystemExit(f"chipbench: engine compiled {log.n_compiles()} "
                         "bucket shapes in warm-up, not 2")
    return eng, log, params


class OpenLoop:
    """One host loop over an engine: submit each request once its
    scheduled time (t0 + t) has passed, step while anything is queued
    or running, sleep until the next arrival otherwise."""

    def __init__(self, eng, reqs: List[Dict], span):
        self.eng, self.span = eng, span
        self.reqs = deque(reqs)
        self.t0 = time.perf_counter()
        self.due, self.results, self.failed = {}, {}, set()
        self.done_tokens = 0

    def generated(self) -> int:
        lanes = [ln for ln in self.eng.sched.lanes if ln is not None]
        return self.done_tokens + sum(len(ln.out) for ln in lanes)

    def _submit_due(self, now):
        from repro.serving.scheduler import Request
        while self.reqs and self.t0 + self.reqs[0]["t"] <= now:
            r = self.reqs.popleft()
            self.due[r["rid"]] = (self.t0 + r["t"], r)
            try:
                with self.span("submit"):
                    self.eng.submit(Request(
                        rid=r["rid"], tokens=r["prompt"].tolist(),
                        max_new_tokens=r["max_new"], seed=r["rid"]))
            except ValueError:
                self.failed.add(r["rid"])

    def step(self):
        with self.span("engine_step"):
            for res in self.eng.step():
                self.results[res.rid] = res
                self.done_tokens += len(res.tokens)

    def until(self, t_end: float) -> float:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return now
            self._submit_due(now)
            if self.eng.sched.busy:
                self.step()
            else:
                nxt = self.t0 + self.reqs[0]["t"] if self.reqs else t_end
                with self.span("await_arrival"):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))

    def arrived(self, lo: float, hi: float) -> set:
        return {rid for rid, (t, _) in self.due.items() if lo <= t < hi}

    def drain(self, ids: set, seconds: float) -> float:
        """Step, admitting nothing new, until every request of ``ids`` is
        finished or failed, for at most ``seconds``."""
        deadline = time.perf_counter() + seconds
        while (self.eng.sched.busy
               and not ids <= set(self.results) | self.failed
               and time.perf_counter() < deadline):
            self.step()
        return time.perf_counter()

    def latencies(self, ids: set, t_end: float):
        """TTFT and TPOT of each request of ``ids``, from its scheduled
        arrival; one never finished waited at least until ``t_end``."""
        ttft, tpot, failed = [], [], 0
        for rid in sorted(ids):
            res, t_due = self.results.get(rid), self.due[rid][0]
            if res is None:
                failed += 1
                ttft.append(t_end - t_due)
                tpot.append(t_end - t_due)
                continue
            ttft.append(res.t_first - t_due)
            if len(res.tokens) > 1:
                tpot.append((res.t_done - res.t_first) / (len(res.tokens) - 1))
        return ttft, tpot, failed


def run(ctx: "harness.Run") -> "harness.Result":
    cfg, mix, wl = ctx.cell.cfg, ctx.cell.mix, ctx.cell.workload
    eng, log, params = build(cfg, wl, ctx.seed)
    loop = OpenLoop(eng, generate.requests(mix, cfg["vocab_size"], ctx.seed,
                                           ctx.seconds), ctx.span)
    t_open = loop.t0 + mix["ramp_s"]
    t_close = t_open + ctx.seconds
    loop.until(t_open)                           # the ramp: set-up
    setup_s = ctx.setup_s(time.perf_counter())
    compiles0 = ctx.watch.n
    with ctx.window():
        log.on = True
        g0, w0 = loop.generated(), time.perf_counter()
        w1 = loop.until(t_close)
        g1 = loop.generated()
        log.on = False
    window_s = w1 - w0
    compiles = ctx.watch.n - compiles0
    ids = loop.arrived(t_open, t_close)
    t_end = loop.drain(ids, wl["drain_s"])
    peak = harness.memory_peak_bytes()
    ttft, tpot, failed = loop.latencies(ids, t_end)
    e2e = {"serve_tokens_per_s": (g1 - g0) / window_s,
           "ttft_p90_ms": 1e3 * harness.percentile(ttft, 90),
           "tpot_p90_ms": 1e3 * harness.percentile(tpot, 90),
           "setup_s": setup_s}
    counts = {"prefill": log.prefill, "decode": log.decode,
              "window_s": window_s, "tokens": g1 - g0, "requests": len(ids)}

    # -- the check, with the engine gone
    sample = pick_sample(ids, loop.results, ctx.seed, wl["sample_tokens"])
    served = [(loop.due[rid][1]["prompt"], np.asarray(loop.results[rid].tokens))
              for rid in sample]
    del eng, log, params, loop
    gc.collect()
    gap = token_gap(cfg, ctx.seed, served, "f32")
    # a request of the window that never finished is a wrong answer
    checks = ctx.checks({"token_gap": gap, "unfinished": failed})
    if ctx.control:
        ctx.extra["fp8"] = {"token_gap": token_gap(cfg, ctx.seed, served,
                                                   "fp8", control=True)}
    readings = {"counts": counts, "cfg": cfg, "peak": ctx.peak,
                "chips": ctx.cell.chips, "trace": ctx.trace_summary}
    return harness.Result(end_to_end=e2e, checks=checks,
                          attempted=len(ids), failed=failed,
                          memory_peak_bytes=peak, window_compiles=compiles,
                          readings=readings)


def pick_sample(ids, results, seed: int, want_tokens: int) -> List[int]:
    """The finished request that served the most tokens, then others
    drawn from the seed until ``want_tokens`` served tokens are in."""
    done = sorted(rid for rid in ids if rid in results)
    if not done:
        return []
    longest = max(done, key=lambda rid: (len(results[rid].tokens), -rid))
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x5A3]
                                  ).permutation(len(done))
    pick, n = [longest], len(results[longest].tokens)
    for i in order:
        rid = done[i]
        if n >= want_tokens:
            break
        if rid != longest:
            pick.append(rid)
            n += len(results[rid].tokens)
    return pick


def token_gap(cfg: Dict, seed: int, served, mm: str, control: bool = False
              ) -> float:
    """Widest gap, over every served token, between the reference's best
    logit and its logit of the served token.  With ``control`` the
    served token is the one that ``mm`` puts first, and its gap is read
    from the float32 reference."""
    theta = ref.make_weights(cfg, seed)
    widest = 0.0
    for prompt, out in served:
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        pos = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(ref.logits_at(cfg, theta, seq, pos, "f32"))
        toks = out
        if control:
            toks = np.asarray(ref.logits_at(cfg, theta, seq, pos, mm)).argmax(-1)
        gaps = lg.max(-1) - lg[np.arange(len(toks)), toks]
        widest = max(widest, float(gaps.max()))
    del theta
    return widest
