"""Share of the traced window in which no operation ran on the device,
under the trainer's host loop (feed, step dispatch, loss read-back)."""
UNIT, SOURCE, MOVES = "%", "device_trace", "train_tokens_per_s"


def read(r):
    t = r["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
