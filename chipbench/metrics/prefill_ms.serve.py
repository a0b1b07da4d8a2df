"""Mean device time of one prefill chunk (the engine's prefill bucket,
XLA module jit_pstep) in the traced window."""
UNIT, SOURCE, MOVES = "ms", "device_trace", "ttft_p90_ms"
MODULE = "jit_pstep"


def read(r):
    t = r["trace"]
    n = t["module_calls"].get(MODULE, 0)
    return 1e3 * t["module_s"][MODULE] / n if n else None
