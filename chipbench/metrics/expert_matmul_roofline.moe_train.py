"""The routed experts' grouped matmuls against their roofline: the FLOPs
of every token's assignments in every MoE layer of both probes over the
own device time of the operations under the ``moe_experts`` scope (the
grouped matmul kernels and their activation), at the chip's bf16 peak.
Nothing to read where the program has no such scope."""
import cost_moe

UNIT, SOURCE, MOVES = "%", "device_trace", "train_tokens_per_s"
PROBES = 2


def read(r):
    s = r.get("scope_s", {}).get("moe_experts", 0.0)
    if s <= 0:
        return None
    c = r["counts"]
    flops = cost_moe.expert_matmul_flops(r["cfg"], c["batch"] * c["steps"],
                                         c["seq_len"], probes=PROBES)
    return 100.0 * flops / (s * r["trace"]["devices"]
                            * r["peak"]["bf16_flops_per_s"])
