"""The probes' matrix products against their roofline: the FLOPs the
window's forwards require over the device time of every convolution and
dot operation (their fusions included) at the chip's bf16 peak."""
import cost
import trace as trace_mod

UNIT, SOURCE, MOVES = "%", "device_trace", "train_tokens_per_s"
PROBES = 2       # two_point: one forward at +eps, one at -eps


def read(r):
    t, c = r["trace"], r["counts"]
    mm = trace_mod.matmul_s(t)
    if mm <= 0:
        return None
    flops = cost.zo_step_flops(r["cfg"], c["batch"] * c["steps"], c["seq_len"],
                               c["head_positions"], probes=PROBES)
    return 100.0 * flops / (mm * t["devices"] * r["peak"]["bf16_flops_per_s"])
