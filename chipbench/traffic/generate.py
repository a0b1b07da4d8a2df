"""The one generator of every traffic mix: rows for training, requests
for serving.  A mix is a data file beside this one (``<name>.json``); a
new mix is a new file, never new code.

Training rows (``kind: rows``) follow the batch layout of the program's
task compiler (``repro.tasks.base``): a full row of seq_len + 1 ids,
tokens = full[:-1], labels = full[1:], PAD-filled and right-aligned
prompts, and a loss mask on the answer only.  ``classification`` ends
the prompt with a query token and one verbalizer token (SST-2);
``generation`` ends it with a query token and an answer span copied out
of the prompt (SQuAD-style).  Each step draws fresh rows from (seed,
step), so no two steps share a row.

Requests (``kind: requests``) are an open loop.  Every seed gets the same
multiset of prompt lengths, output lengths and gaps between arrivals
(quantiles of the stated distributions), in an order and with prompt
tokens drawn from the seed: seeds change which request comes when, not
how much work a run holds.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent
PAD, QUERY, FIRST_ID = 1, 2, 4      # OPT's pad and </s>; 0-3 are special


def load(name: str) -> Dict:
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no traffic mix {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *salt])


# ------------------------------------------------------------------ rows
def rows(mix: Dict, vocab: int, seed: int, step: int) -> Dict[str, np.ndarray]:
    """One step's batch: tokens, labels (B, S) int32, loss_mask (B, S)."""
    B, S = mix["batch"], mix["seq_len"]
    lo, hi = mix["prompt_tokens"]
    r = _rng(seed, 0x5EED, step)
    full = np.full((B, S + 1), PAD, np.int64)
    mask = np.zeros((B, S), np.float32)
    for b in range(B):
        n = int(r.integers(lo, hi + 1))
        prompt = r.integers(FIRST_ID, vocab, n)
        if mix["layout"] == "classification":
            full[b, S - 1 - n:S - 1] = prompt
            full[b, S - 1] = QUERY
            full[b, S] = mix["label_ids"][int(r.integers(0, 2))]
            mask[b, S - 1] = 1.0
        elif mix["layout"] == "generation":
            A = mix["answer_tokens"]
            W = S - A                          # full[W] is the query
            full[b, W - n:W] = prompt
            full[b, W] = QUERY
            at = int(r.integers(0, n - A + 1))
            full[b, W + 1:W + 1 + A] = prompt[at:at + A]
            mask[b, W:W + A] = 1.0
        else:
            raise SystemExit(f"unknown row layout {mix['layout']!r}")
    return {"tokens": full[:, :-1].astype(np.int32),
            "labels": full[:, 1:].astype(np.int32), "loss_mask": mask}


# -------------------------------------------------------------- requests
def _lognormal_quantile(d: Dict, q: float) -> int:
    z = statistics.NormalDist().inv_cdf(q)
    v = math.exp(math.log(d["median"]) + d["sigma"] * z)
    return int(min(d["max"], max(d["min"], round(v))))


def requests(mix: Dict, vocab: int, seed: int, seconds: float,
             rate: float = None) -> List[Dict]:
    """Arrivals over ramp_s + seconds, in arrival order: each a dict of
    rid, t (seconds after the first arrival), prompt (int32 ids) and
    max_new.  ``rate`` overrides the mix's rate (the knee sweep)."""
    rate = rate or mix["rate_per_s"]
    n = max(1, round(rate * (mix["ramp_s"] + seconds)))
    qs = [(i + 0.5) / n for i in range(n)]
    # prompt and output lengths are paired by one fixed shuffle, the
    # same for every seed, so that the two are independent
    pair = np.random.default_rng(0x9A1).permutation(n)
    sizes = []
    for i in range(n):
        p = _lognormal_quantile(mix["prompt_tokens"], qs[i])
        o = _lognormal_quantile(mix["output_tokens"], qs[pair[i]])
        sizes.append((p, min(o, mix["max_total_tokens"] - p)))
    r = _rng(seed, 0xA771)
    gaps = np.asarray([-math.log(1.0 - q) / rate for q in qs])[r.permutation(n)]
    sizes = [sizes[i] for i in r.permutation(n)]
    t = np.cumsum(gaps) - gaps[0]
    return [{"rid": i, "t": float(t[i]),
             "prompt": r.integers(FIRST_ID, vocab, p).astype(np.int32),
             "max_new": int(o)} for i, (p, o) in enumerate(sizes)]
