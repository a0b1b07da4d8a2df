"""The decode step against its roofline: the least time the chip could
take for the window's decode calls (the larger of their required FLOPs
over the bf16 peak and their required bytes over HBM bandwidth: the
weights once a call, and each live lane's real context of K/V) over
their device time.  Gathering a lane's whole page table and copying
the arena count as waste."""
import cost

UNIT, SOURCE, MOVES = "%", "device_trace", "tpot_p90_ms"
MODULE = "jit_dstep"


def read(r):
    t, cfg, pk = r["trace"], r["cfg"], r["peak"]
    calls = r["counts"]["decode"]
    dev = t["module_s"].get(MODULE, 0.0)
    if not calls or dev <= 0:
        return None
    least = sum(max(cost.decode_flops(cfg, p) / pk["bf16_flops_per_s"],
                    cost.decode_bytes(cfg, p) / pk["hbm_bytes_per_s"])
                for p in calls)
    return 100.0 * least / dev
