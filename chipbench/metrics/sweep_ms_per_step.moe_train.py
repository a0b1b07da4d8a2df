"""Own device ms a step in the ZO parameter sweeps: the ``perturb`` and
``update_axpy`` stages (the z regeneration and the in-place axpy over
the kept layers' rows, the embedding and the head).  Nothing to read
where the program has no such stages."""
UNIT, SOURCE, MOVES = "ms", "device_trace", "train_tokens_per_s"


def read(r):
    s = r.get("scope_s", {})
    steps = r["counts"]["steps"]
    if not steps or not ({"perturb", "update_axpy"} & set(s)):
        return None
    return 1e3 * (s.get("perturb", 0.0) + s.get("update_axpy", 0.0)) / steps
