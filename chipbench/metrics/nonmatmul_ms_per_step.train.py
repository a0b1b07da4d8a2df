"""Device time per step in operations that are not matrix products:
z regeneration and the perturb, restore and update sweeps, norms, the
loss's softmax.  It stands in for the sweeps' own time until the step
names its stages inside the jitted program."""
import trace as trace_mod

UNIT, SOURCE, MOVES = "ms", "device_trace", "train_tokens_per_s"


def read(r):
    t = r["trace"]
    steps = r["counts"]["steps"]
    other = sum(t["category_s"].values()) - trace_mod.matmul_s(t)
    return 1e3 * other / steps if steps else None
