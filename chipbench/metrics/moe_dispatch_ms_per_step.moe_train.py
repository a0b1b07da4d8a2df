"""Own device ms a step in the MoE FFN's routing around its experts: the
``moe_dispatch`` scope (router, top-k, sort by expert, row gather) and
the ``moe_combine`` scope (routing weights, scatter-add to the tokens).
Nothing to read where the program has no such scopes."""
UNIT, SOURCE, MOVES = "ms", "device_trace", "train_tokens_per_s"


def read(r):
    s = r.get("scope_s", {})
    steps = r["counts"]["steps"]
    if not steps or not ({"moe_dispatch", "moe_combine"} & set(s)):
        return None
    return 1e3 * (s.get("moe_dispatch", 0.0) + s.get("moe_combine", 0.0)) / steps
