#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate once, to find its knee.

    python chipbench/knee.py --workload serve-opt1.3b-chat --seed <n> \\
        --seconds 40 --rates 1.5,2,2.5,3,3.5

Each rate gets a fresh engine (its programs come from the compile
cache) serving the cell's mix: a ramp of the mix's ramp_s, a window of
``seconds``, then a drain of what arrived, for at most ``drain_s``.
Each rate prints one JSON line: tokens/s delivered in the window,
requests that arrived in it and how many of them finished by its close,
the queue at its open and close, and the TTFT / TPOT tails after the
drain.  The knee is the highest rate
whose queue does not grow through the window; the cell runs at about
four fifths of it (PERF.md records the sweep).  Benchmark runs never
run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import run  # noqa: E402
from drivers import serve  # noqa: E402
from traffic import generate  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = run.Cell(json.loads((run.ROOT / "BENCHMARK.json").read_text()),
                    args.workload)
    run.device_info(cell.chips)
    run.enable_compile_cache(run.ROOT)
    cfg, mix, wl = cell.cfg, cell.mix, cell.workload
    for rate in [float(x) for x in args.rates.split(",")]:
        eng, log, params = serve.build(cfg, wl, args.seed)
        reqs = generate.requests(mix, cfg["vocab_size"], args.seed,
                                 args.seconds, rate=rate)
        loop = serve.OpenLoop(eng, reqs, harness.Run.span)
        t_open = loop.t0 + mix["ramp_s"]
        t_close = t_open + args.seconds
        loop.until(t_open)
        q_open = len(eng.sched.queue)
        g0, w0 = loop.generated(), time.perf_counter()
        w1 = loop.until(t_close)
        g1, q_close = loop.generated(), len(eng.sched.queue)
        ids = loop.arrived(t_open, t_close)
        in_time = len([i for i in ids if i in loop.results])
        t_end = loop.drain(ids, wl["drain_s"])
        ttft, tpot, failed = loop.latencies(ids, t_end)
        del eng, log, params, loop      # the next rate's engine needs the room
        gc.collect()
        print(json.dumps({
            "rate_per_s": rate, "tokens_per_s": (g1 - g0) / (w1 - w0),
            "arrived": len(ids), "finished_in_window": in_time,
            "queue_open": q_open, "queue_close": q_close, "failed": failed,
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * harness.percentile(ttft, 90),
            "tpot_p90_ms": 1e3 * harness.percentile(tpot, 90)}), flush=True)


if __name__ == "__main__":
    main()
