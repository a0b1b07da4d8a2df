"""Share of the traced window in which no operation ran on the device,
under the serving engine's host loop (scheduler, page tables, the read-back
of every decoded token)."""
UNIT, SOURCE, MOVES = "%", "device_trace", "tpot_p90_ms"


def read(r):
    t = r["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
