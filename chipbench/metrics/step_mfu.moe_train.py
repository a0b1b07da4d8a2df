"""The whole ZO step's share of the chips' peak on an MLA + MoE model:
the FLOPs the window's steps require (``cost_moe``: one forward per
probe, causal attention, the routed and shared experts each token
reads, the head where the loss is taken; sweeps are not FLOPs) over
window x chips x peak."""
import cost_moe

UNIT, SOURCE, MOVES = "%", "device_trace", "train_tokens_per_s"
PROBES = 2       # two_point: one forward at +eps, one at -eps


def read(r):
    t, c = r["trace"], r["counts"]
    flops = cost_moe.zo_step_flops(r["cfg"], c["batch"] * c["steps"],
                                   c["seq_len"], c["head_positions"],
                                   probes=PROBES)
    return 100.0 * flops / (t["window_s"] * r["chips"]
                            * r["peak"]["bf16_flops_per_s"])
