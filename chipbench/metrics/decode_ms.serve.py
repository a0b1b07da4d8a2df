"""Mean device time of one decode step (the engine's decode bucket,
XLA module jit_dstep) in the traced window."""
UNIT, SOURCE, MOVES = "ms", "device_trace", "tpot_p90_ms"
MODULE = "jit_dstep"


def read(r):
    t = r["trace"]
    n = t["module_calls"].get(MODULE, 0)
    return 1e3 * t["module_s"][MODULE] / n if n else None
