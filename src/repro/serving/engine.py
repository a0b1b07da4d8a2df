"""The continuous-batching engine: jitted paged steps over the KV pool
(DESIGN.md §12).

One engine owns one arena (``models.lm.init_paged_cache``), one pool
(:class:`~repro.serving.pool.KVPool`), one scheduler, and exactly two
compiled shapes of the same ``lm.paged_step`` function:

  * the *prefill bucket*:  (1, prefill_chunk) tokens, one lane's row
  * the *decode bucket*:   (max_lanes, 1) tokens, the full page table

(plus, when prefix sharing triggers a copy-on-write, one tiny
page-duplication kernel — a scalar-indexed clone compiled once and
outside the bucket promise ``n_compiles`` guards).  With
``serving.prefix_cache`` on, prompts that share a full-page token
prefix attach the same physical pages through the scheduler's radix
trie and skip the chunk-aligned part of prefill; greedy output is
bit-identical to sharing off (the correctness anchor pinned in
tests/test_serving.py).  ``serving.preempt`` lets a starved
higher-priority admission evict the lowest-priority decoding lane.

Prompts are padded to the chunk bucket and streamed in chunk-by-chunk,
interleaved with decode steps (one chunk per engine step), so a long
admission never stalls the running lanes for more than one chunk's
latency.  Inactive decode lanes ride along pointed at the trash page —
the batch shape never changes, so nothing ever recompiles after warmup.

The arena is donated through every call so XLA may update pages in
place; where the layer scan forces a fresh output buffer the cost is one
arena-sized copy per call — which is why the pool should be sized to the
workload's worst case, not padded "to be safe" (benchmarks/serving.py
measures the copy tax directly; recorded in DESIGN.md §12).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.models import lm
from repro.serving import sampling
from repro.serving.pool import KVPool
from repro.serving.scheduler import DECODE, Lane, Request, Scheduler


class EngineUnsupported(NotImplementedError):
    """The model's block family is outside the paged engine's coverage
    (SSM/MLA mixers, stub frontends) — serve it with the lockstep path."""


@dataclasses.dataclass
class GenResult:
    rid: int
    tokens: List[int]                # generated ids (prompt excluded)
    prompt_len: int
    t_submit: float
    t_admit: float
    t_first: float                   # first generated token (prefill done)
    t_done: float

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit

    @property
    def ttft(self) -> float:
        """Time to first token."""
        return self.t_first - self.t_submit


class Engine:
    """Drive ``spec.serving`` over a model: submit() requests, step()
    until drained (or just run())."""

    def __init__(self, cfg, params, serving, mesh=None, clock=None,
                 obs=None):
        if not lm.supports_paged(cfg):
            kinds = sorted({b.kind for s in cfg.stages for b in s.pattern})
            raise EngineUnsupported(
                f"{cfg.name}: paged serving covers attn mixers only, "
                f"got {kinds}; use the lockstep serve path")
        self.cfg = cfg
        self.params = params
        self.serving = serving
        self.clock = clock or time.perf_counter
        # learned position tables are finite: the engine cannot place a
        # token beyond them, whatever serving.max_seq asks for
        max_seq = serving.max_seq
        if cfg.pos_emb == "learned":
            max_seq = min(max_seq,
                          serving.page_size
                          * (cfg.max_seq // serving.page_size))
        self.pool = KVPool(serving.n_pages, serving.page_size)
        self.sched = Scheduler(self.pool, max_lanes=serving.max_lanes,
                               prefill_chunk=serving.prefill_chunk,
                               max_seq=max_seq,
                               prefix_cache=serving.prefix_cache,
                               priorities=serving.priorities,
                               preempt=serving.preempt)
        self.arena = lm.init_paged_cache(cfg, serving.n_pages,
                                         serving.page_size)
        sample = sampling.make_sampler(serving.temperature, serving.top_k)

        def pstep(p, a, t, pg, pos, sel, seeds, spos):
            # prefill bucket; sampling fused in so the final chunk's
            # first token comes back in the same dispatch
            logits, a2 = lm.paged_step(cfg, p, a, t, pg, pos, sel)
            return sample(logits, seeds, spos), a2

        def dstep(p, a, t, pg, pos, seeds):
            # decode bucket: token/position state stays ON DEVICE between
            # steps — the returned (toks, pos) feed the next call as-is,
            # so a steady-state decode step uploads nothing (host arrays
            # are rebuilt only when the lane set changes)
            B = pos.shape[0]
            logits, a2 = lm.paged_step(cfg, p, a, t, pg, pos,
                                       jnp.zeros((B,), jnp.int32))
            nxt = sample(logits, seeds, pos + 1)
            return nxt[:, None], pos + 1, a2

        def cstep(a, src, dst):
            # copy-on-write page duplication: clone physical page src
            # into dst across every stage-block leaf (page axis is 1)
            return jax.tree_util.tree_map(
                lambda x: x.at[:, dst].set(x[:, src]), a)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.distributed import ctx, sharding
            ctx.set_mesh(mesh)
            a_shard = sharding.arena_sharding(
                jax.eval_shape(lambda: lm.init_paged_cache(
                    cfg, serving.n_pages, serving.page_size)), mesh)
            p_shard = sharding.params_sharding(
                cfg, jax.eval_shape(lambda: lm.init_params(
                    cfg, jax.random.PRNGKey(0))), mesh)
            repl = NamedSharding(mesh, P())
            self._pstep = jax.jit(pstep, donate_argnums=(1,),
                                  in_shardings=(p_shard, a_shard, repl,
                                                repl, repl, repl, repl,
                                                repl),
                                  out_shardings=(repl, a_shard))
            self._dstep = jax.jit(dstep, donate_argnums=(1,),
                                  in_shardings=(p_shard, a_shard, repl,
                                                repl, repl, repl),
                                  out_shardings=(repl, repl, a_shard))
            self._cstep = jax.jit(cstep, donate_argnums=(0,),
                                  in_shardings=(a_shard, repl, repl),
                                  out_shardings=a_shard)
        else:
            self._pstep = jax.jit(pstep, donate_argnums=(1,))
            self._dstep = jax.jit(dstep, donate_argnums=(1,))
            self._cstep = jax.jit(cstep, donate_argnums=(0,))
        self.n_prefill_calls = 0
        self.n_decode_steps = 0
        self._t_submit: Dict[int, float] = {}
        self._decode_dirty = True        # device lane state needs rebuild
        self._d_toks = self._d_table = self._d_pos = self._d_seeds = None
        # telemetry (DESIGN.md §13): an obs.Session, or the free
        # NULL_SESSION — the engine never branches on "is obs on"
        self.obs = obs if obs is not None else obs_mod.NULL_SESSION
        reg = self.obs.registry
        self._m_queue = reg.gauge("serving_queue_depth",
                                  "requests waiting for admission")
        self._m_lanes = reg.gauge("serving_lanes_active",
                                  "lanes prefilling or decoding")
        self._m_pages = reg.gauge("serving_pages_in_use",
                                  "KV pool pages allocated")
        self._m_util = reg.gauge("serving_page_utilization",
                                 "pages in use / usable pages")
        self._m_ttft = reg.histogram("serving_ttft_seconds",
                                     "submit -> first generated token")
        self._m_lat = reg.histogram("serving_latency_seconds",
                                    "submit -> request finished")
        self._m_toks = reg.counter("serving_tokens_generated",
                                   "generated tokens over all requests")
        self._m_reqs = reg.counter("serving_requests_completed",
                                   "requests retired")
        # prefix sharing / preemption (DESIGN.md §12)
        self._m_hit = reg.gauge("serving_page_hit_rate",
                                "shared prompt pages attached / looked up")
        self._m_preempt = reg.gauge("serving_preemptions",
                                    "decoding lanes evicted and requeued")
        self._m_cow = reg.gauge("serving_cow_copies",
                                "shared pages duplicated before a write")

    def _sample_gauges(self):
        self._m_queue.set(len(self.sched.queue))
        self._m_lanes.set(len(self.sched.prefilling())
                          + len(self.sched.decoding()))
        in_use = self.pool.in_use
        self._m_pages.set(in_use)
        usable = self.pool.n_pages - 1        # page 0 is the trash page
        self._m_util.set(in_use / usable if usable else 0.0)
        self._m_hit.set(self.sched.page_hit_rate)
        self._m_preempt.set(self.sched.preemptions)
        self._m_cow.set(self.sched.cow_copies)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine's metrics."""
        return self.obs.registry.to_text()

    # ----------------------------------------------------------- compiles
    def n_compiles(self) -> int:
        """Compiled shapes behind the paged steps — stays at <= 2 (one
        per bucket) for the engine's whole life; the bench asserts it."""
        return self._pstep._cache_size() + self._dstep._cache_size()

    # ------------------------------------------------------------- submit
    def submit(self, req: Request):
        if req.max_new_tokens is None:   # spec default for the budget
            req = dataclasses.replace(
                req, max_new_tokens=self.serving.max_new_tokens)
        self.sched.submit(req)           # validates span vs pool/table
        self._t_submit[req.rid] = self.clock()

    # --------------------------------------------------------------- step
    def step(self) -> List[GenResult]:
        """One engine iteration: admit, one prefill chunk, one batched
        decode step.  Returns the requests that finished this iteration."""
        sched = self.sched
        pre_preempt = sched.preemptions
        while sched.try_admit(now=self.clock()) is not None:
            pass
        if sched.preemptions != pre_preempt:
            self._decode_dirty = True    # a decoding lane was evicted

        # -- chunked prefill: one chunk for the oldest prefilling lane
        # (admission order, NOT lane index — a later admission into a
        # lower lane must not overtake an in-progress prefill)
        pre = sched.prefilling()
        if pre:
            i = min(pre, key=lambda j: sched.lanes[j].admit_seq)
            lane = sched.lanes[i]
            c = sched.prefill_chunk
            start = lane.next_chunk * c
            chunk = np.zeros((1, c), np.int32)
            lo = min(start + c, lane.prompt_len)
            if lo > start:
                chunk[0, :lo - start] = np.asarray(
                    lane.req.tokens[start:lo], np.int32)
            final = start + c >= lane.padded_len
            sel = (min(lane.prompt_len - 1 - start, c - 1) if final else 0)
            # copy-on-write: shared pages this chunk writes get a
            # private duplicate before the write lands (scheduler swaps
            # the page table; the device content copy happens here)
            for src, dst in sched.cow_range(lane, start, start + c):
                self.arena = self._cstep(self.arena, jnp.int32(src),
                                         jnp.int32(dst))
            with self.obs.tracer.span(obs_mod.SERVE_PREFILL) as sp:
                toks, self.arena = self._pstep(
                    self.params, self.arena, jnp.asarray(chunk),
                    jnp.asarray(np.asarray(sched.page_row(lane),
                                           np.int32)[None]),
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([sel], jnp.int32),
                    jnp.asarray([lane.req.seed], jnp.uint32),
                    jnp.asarray([lane.prompt_len], jnp.int32))
                sp.fence(toks)
            self.n_prefill_calls += 1
            lane.next_chunk += 1
            lane.pos = min(start + c, lane.padded_len)
            if final:
                sched.register_prefix(lane)   # full prompt pages -> trie
                tok = int(toks[0])
                lane.t_first = self.clock()
                lane.out.append(tok)
                lane.last_token = tok
                lane.pos = lane.prompt_len
                lane.state = DECODE
                self._decode_dirty = True

        # -- batched decode over every decoding lane (fixed bucket)
        finished: List[GenResult] = []
        dec = sched.decoding()
        live = [i for i in dec if not self._done(sched.lanes[i])]
        for i in sorted(set(dec) - set(live)):
            finished.append(self._retire(i))
        if live:
            B = sched.max_lanes
            if self._decode_dirty:
                toks = np.zeros((B, 1), np.int32)
                table = np.zeros((B, sched.table_width), np.int32)
                pos = np.zeros((B,), np.int32)
                seeds = np.zeros((B,), np.uint32)
                for i in live:
                    lane = sched.lanes[i]
                    toks[i, 0] = lane.last_token
                    table[i] = sched.page_row(lane)
                    pos[i] = lane.pos
                    seeds[i] = lane.req.seed
                self._d_toks = jnp.asarray(toks)
                self._d_table = jnp.asarray(table)
                self._d_pos = jnp.asarray(pos)
                self._d_seeds = jnp.asarray(seeds)
                self._decode_dirty = False
            with self.obs.tracer.span(obs_mod.SERVE_DECODE) as sp:
                self._d_toks, self._d_pos, self.arena = self._dstep(
                    self.params, self.arena, self._d_toks, self._d_table,
                    self._d_pos, self._d_seeds)
                sp.fence(self._d_toks)
            self.n_decode_steps += 1
            nxt = np.asarray(self._d_toks)[:, 0]
            for i in live:
                lane = sched.lanes[i]
                tok = int(nxt[i])
                lane.out.append(tok)
                lane.last_token = tok
                lane.pos += 1
                if self._done(lane):
                    finished.append(self._retire(i))
        self._sample_gauges()
        return finished

    def _done(self, lane: Lane) -> bool:
        eos = self.serving.eos_id
        return (len(lane.out) >= lane.req.max_new_tokens
                or (eos is not None and lane.out and lane.out[-1] == eos))

    def _retire(self, i: int) -> GenResult:
        self._decode_dirty = True        # lane composition changed
        lane = self.sched.finish(i)      # pages return to the pool now
        res = GenResult(rid=lane.req.rid, tokens=list(lane.out),
                        prompt_len=lane.prompt_len,
                        t_submit=self._t_submit.pop(lane.req.rid, 0.0),
                        t_admit=lane.t_admit, t_first=lane.t_first,
                        t_done=self.clock())
        self._m_reqs.inc()
        self._m_toks.inc(len(res.tokens))
        if res.t_submit:
            self._m_ttft.observe(res.ttft)
            self._m_lat.observe(res.latency)
        return res

    # ---------------------------------------------------------------- run
    def run(self, requests: Sequence[Request]) -> List[GenResult]:
        """Drain ``requests``: submit everything, step until idle.
        Results come back in finish order (not submit order).  Finished
        results are handed to the caller, never retained — a long-lived
        engine stays O(active lanes), not O(requests ever served)."""
        for r in requests:
            self.submit(r)
        results: List[GenResult] = []
        guard = 0
        while self.sched.busy:
            before = (self.n_prefill_calls, self.n_decode_steps,
                      len(results), len(self.sched.queue),
                      self.sched.preemptions)
            results.extend(self.step())
            after = (self.n_prefill_calls, self.n_decode_steps,
                     len(results), len(self.sched.queue),
                     self.sched.preemptions)
            guard = guard + 1 if before == after else 0
            if guard > 2:    # admission blocked with nothing running
                raise RuntimeError(
                    "engine stalled: queue head needs "
                    "more pool pages than will ever free up")
        return results
