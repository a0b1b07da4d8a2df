"""The serving step's share of the chip's peak: the FLOPs that the
window's prompt tokens and generated tokens require (padding and idle
lanes excluded) over window x peak."""
import cost

UNIT, SOURCE, MOVES = "%", "device_trace", "serve_tokens_per_s"


def read(r):
    t, cfg, c = r["trace"], r["cfg"], r["counts"]
    flops = (sum(cost.prefill_flops(cfg, s, n, f) for s, n, f in c["prefill"])
             + sum(cost.decode_flops(cfg, p) for p in c["decode"]))
    if flops <= 0:
        return None
    return 100.0 * flops / (t["window_s"] * r["chips"]
                            * r["peak"]["bf16_flops_per_s"])
