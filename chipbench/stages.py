#!/usr/bin/env python3
"""Split a device trace's operation time by the ZO step's stages.

The program names its stages inside the compiled step (``repro.obs.stage``,
a ``jax.named_scope``): every HLO operation carries the scopes it was
traced under on its ``op_name`` path, and the TPU profiler reports that
path as the operation's ``tf_op`` stat, as in
``jit(step)/forward+εz/loss_head/while/body/closed_call/dot_general``.
The profiler appends ``:<op type>`` (often empty) to the path.  An
operation belongs to the innermost stage on its path (the loss head's
operations count as ``loss_head``, not as their probe); one with no
stage on its path is ``unscoped``.  A nested jit that XLA lowered once
and calls from two stages (``jit(flash_attention)`` in both probes)
carries its call site's path followed by the whole path of its first
lowering, which starts again at the root (``jit(step)/forward-εz/...
/jit(flash_attention)/jit(step)/forward+εz/...``): it runs at the call
site, so the path is cut where its root repeats.

    python3 chipbench/stages.py <trace.xplane.pb> [--steps N]

prints, as one JSON object, each stage's own device seconds in the
trace's ``chipbench.window`` span (``stage_s``: the same clipped own
times that ``trace.reduce`` sums into ``category_s``), each stage's ms
per step when ``--steps`` is given, and the largest operations with
their stage.  A run keeps its raw trace with ``run.run(...,
keep_trace=<path>)``.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace as trace_mod  # noqa: E402

# repro.obs's stage names (chipbench imports nothing of the program; a
# test pins this tuple to repro.obs's constants)
STAGES = ("perturb", "forward+εz", "forward-εz", "forward_pair", "forward",
          "update_axpy", "loss_head")
UNSCOPED = "unscoped"


def stage_of(tf_op: str) -> str:
    """The innermost stage on an operation's scope path, up to where the
    path's root repeats (so of the paths XLA joins with ``;`` when it
    merges operations, the first counts)."""
    parts = re.split(r"[/;:]", tf_op or "")
    if parts[0] in parts[1:]:
        parts = parts[:parts.index(parts[0], 1)]
    for part in reversed(parts):
        if part in STAGES:
            return part
    return UNSCOPED


def _tf_ops(path: str) -> Dict[str, List[str]]:
    """Each TPU plane's ``XLA Ops`` events' ``tf_op`` stat, in the order
    ``trace.load`` lists the plane's operations ('' where it has none)."""
    with open(path, "rb") as f:
        xs = trace_mod.parse(f.read())
    out = {}
    for plane in xs.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}

        def tf_op(md):
            for st in md.stats:
                if stat_name.get(st.metadata_id) == "tf_op":
                    return st.str_value or stat_name.get(st.ref_value, "")
            return ""

        (ops,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        out[plane.name] = [tf_op(meta[ev.metadata_id]) for ev in ops.events]
    return out


def load(path: str) -> Dict:
    """``trace.load``, with each device operation's ``tf_op`` kept."""
    data = trace_mod.load(path)
    for plane, names in _tf_ops(path).items():
        for op, name in zip(data["devices"][plane]["ops"], names):
            op["tf_op"] = name
    return data


def _own_in_window(data: Dict):
    """(operation, own ns) for every operation inside the first window
    span, clipped to it, as ``trace.reduce`` counts them."""
    windows = [s for s in data["spans"] if s["name"] == "window"]
    if not windows or not data["devices"]:
        raise SystemExit("chipbench: the trace has no window span or no "
                         "TPU device plane")
    lo, hi = windows[0]["start"], windows[0]["end"]
    for dev in data["devices"].values():
        inside = []
        for op in dev["ops"]:
            s, e = trace_mod._clip(op["start"], op["end"], lo, hi)
            if e > s:
                inside.append({**op, "start": s, "end": e})
        yield from zip(inside, trace_mod.own_times(inside))


def stage_s(data: Dict) -> Dict[str, float]:
    """Own device seconds per stage, averaged over the devices; an
    operation with no stage on its path counts as ``unscoped``."""
    out = defaultdict(float)
    for op, own in _own_in_window(data):
        out[stage_of(op.get("tf_op", ""))] += own / 1e9
    n_dev = len(data["devices"])
    return {k: v / n_dev for k, v in out.items()}


def top_ops(data: Dict, n: int = 10, stage: str = None) -> List[List]:
    """The ``n`` operations with the most own time (named as
    ``trace.reduce``'s breakdown names them), each with its stage and
    seconds; only those of ``stage`` when it is given."""
    op_s, op_stage = defaultdict(float), {}
    for op, own in _own_in_window(data):
        key = f"{op['module']}:{op['name']} ({op['category']})"
        op_stage[key] = stage_of(op.get("tf_op", ""))
        op_s[key] += own / 1e9 / len(data["devices"])
    keys = [k for k in op_s if stage is None or op_stage[k] == stage]
    keys.sort(key=lambda k: -op_s[k])
    return [[k, op_stage[k], op_s[k]] for k in keys[:n]]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--steps", type=int, default=0,
                    help="ZO steps in the window: adds ms per step")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    data = load(args.trace)
    s = stage_s(data)
    out = {"stage_s": s, "top_ops": top_ops(data, args.top),
           "top_unscoped": top_ops(data, args.top, UNSCOPED)}
    if args.steps:
        out["ms_per_step"] = {k: 1e3 * v / args.steps for k, v in s.items()}
    print(json.dumps(out, ensure_ascii=False))


if __name__ == "__main__":
    main()
