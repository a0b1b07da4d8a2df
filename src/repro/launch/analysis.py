"""Scan-aware HLO cost analysis for the roofline.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body **once**,
ignoring ``known_trip_count`` — for a 62-layer scanned transformer that
under-counts FLOPs, HBM bytes *and* the per-layer TP collectives by 62x.
This module parses the optimized HLO text and accumulates costs
recursively through the call graph with loop multipliers:

  * flops: dot ops = 2 * numel(result) * prod(contracting dims); element
    -wise arithmetic (incl. the ZO perturbation RNG) = numel per op;
    reduces = numel(operand).
  * hbm bytes: per *top-level* op in each computation: operands + result
    (internal ops of a fusion stay in registers, matching
    HloCostAnalysis' model).
  * collective bytes per kind, with trip-count multipliers; all-reduce
    counted 2x (ring reduce + broadcast), all-gather / all-to-all /
    collective-permute / reduce-scatter counted at result size.
  * conditional: max over branches (conservative).

Shapes are post-SPMD-partitioning, so everything is per-device.

Benchmark/paper-artifact analysis (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16, "token": 0, "opaque": 0, "s4": 1, "u4": 1}

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "sqrt", "rsqrt", "cbrt", "power", "tanh", "sine", "cosine", "atan2",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "sign",
    "logistic", "erf", "remainder", "and", "or", "xor", "not", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "select", "clamp",
    "compare", "convert", "is-finite",
}
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute"}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\(.*?\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\((.*)$")
_CALLS_RE = re.compile(r"(?:calls|body)=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[="\s:{]+n["\s:]+"?(\d+)')
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


def _shape_elems_bytes(text: str) -> Tuple[int, int]:
    """Total (elements, bytes) over all array shapes in ``text``."""
    elems = tot = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        tot += n * _DTYPE_BYTES[dt]
    return elems, tot


@dataclasses.dataclass
class Op:
    name: str
    shape: str           # result type text
    opcode: str
    rest: str            # remainder of the line (operands + attrs)
    is_root: bool = False


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(default_factory=dict)
    detail: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v * mult
        for k, v in other.detail.items():
            self.detail[k] = self.detail.get(k, 0.0) + v * mult


class HloCost:
    def __init__(self, hlo_text: str):
        self.comps: Dict[str, List[Op]] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)
        self._memo: Dict[str, Cost] = {}

    def _parse(self, text: str):
        cur = None
        for line in text.splitlines():
            if cur is None:
                m = _COMP_HDR.match(line.strip())
                if m:
                    cur = m.group(1)
                    self.comps[cur] = []
                    if line.lstrip().startswith("ENTRY"):
                        self.entry = cur
                continue
            if line.strip() == "}":
                cur = None
                continue
            m = _OP_RE.match(line)
            if m:
                name, shape, opcode, rest = m.groups()
                self.comps[cur].append(
                    Op(name, shape, opcode, rest,
                       is_root=line.lstrip().startswith("ROOT")))

    # ------------------------------------------------------------- costs
    def comp_cost(self, comp: str, fused: bool = False) -> Cost:
        key = f"{comp}|{int(fused)}"
        if key in self._memo:
            return self._memo[key]
        total = Cost()
        symtab = {op.name: op.shape for op in self.comps.get(comp, [])}
        for op in self.comps.get(comp, []):
            total.add(self._op_cost(op, symtab, fused))
        self._memo[key] = total
        return total

    def _operand_bytes(self, op: Op, symtab) -> float:
        b = 0
        # operands are leading %refs before any attr keywords
        args = op.rest.split("),")[0]
        for m in _OPERAND_RE.finditer(args):
            ref = m.group(1)
            if ref in symtab:
                b += _shape_elems_bytes(symtab[ref])[1]
        return b

    def _op_cost(self, op: Op, symtab, fused: bool) -> Cost:
        c = Cost()
        res_elems, res_bytes = _shape_elems_bytes(op.shape)
        code = op.opcode

        if code == "while":
            m = _TRIP_RE.search(op.rest)
            trip = int(m.group(1)) if m else 1
            body = _CALLS_RE.search(op.rest)
            cond = _COND_RE.search(op.rest)
            if body:
                c.add(self.comp_cost(body.group(1)), trip)
            if cond:
                c.add(self.comp_cost(cond.group(1)), trip)
            return c
        if code == "conditional":
            m = _BRANCHES_RE.search(op.rest)
            branches = []
            if m:
                branches = [b.strip().lstrip("%") for b in m.group(1).split(",")]
            else:
                branches = [x.group(1) for x in re.finditer(
                    r"(?:true_computation|false_computation)=%?([\w\.\-]+)",
                    op.rest)]
            best = Cost()
            for b in branches:
                bc = self.comp_cost(b)
                if bc.flops + bc.bytes >= best.flops + best.bytes:
                    best = bc
            c.add(best)
            c.bytes += res_bytes
            return c
        if code == "fusion":
            m = _CALLS_RE.search(op.rest)
            slice_root = None
            if m:
                inner = self.comp_cost(m.group(1), fused=True)
                c.flops += inner.flops
                c.add(Cost(coll=inner.coll))
                slice_root = self._slice_root_bytes(m.group(1))
            if slice_root is not None:
                # root is an in-place / slicing op: traffic is proportional
                # to the slice, not the whole buffer (XLA aliases it).
                c.bytes += slice_root
            else:
                c.bytes += res_bytes + self._fusion_operand_bytes(op, symtab)
            return c
        if code in ("call", "custom-call", "async-start"):
            m = _CALLS_RE.search(op.rest)
            if m and m.group(1) in self.comps:
                c.add(self.comp_cost(m.group(1)))
            c.bytes += res_bytes + self._operand_bytes(op, symtab)
            return c
        if code in _COLLECTIVES or any(code == k + "-start" for k in _COLLECTIVES):
            kind = code.replace("-start", "")
            wire = res_bytes * (2.0 if kind == "all-reduce" else 1.0)
            c.coll[kind] = wire
            c.detail[f"{kind} {op.shape[:60]}"] = wire
            c.bytes += res_bytes + self._operand_bytes(op, symtab)
            return c
        if code == "dot":
            m = _CONTRACT_RE.search(op.rest)
            lhs_ref = _OPERAND_RE.search(op.rest)
            contract = 1
            if m and lhs_ref and lhs_ref.group(1) in symtab:
                lhs_shape = _SHAPE_RE.search(symtab[lhs_ref.group(1)])
                if lhs_shape:
                    dims = [int(d) for d in lhs_shape.group(2).split(",") if d]
                    for ci in m.group(1).split(","):
                        if ci:
                            contract *= dims[int(ci)]
            c.flops += 2.0 * res_elems * contract
            if not fused:
                c.bytes += res_bytes + self._operand_bytes(op, symtab)
            return c
        if code in ("reduce", "reduce-window"):
            c.flops += self._operand_bytes(op, symtab) / 4.0  # ~elems
            if not fused:
                c.bytes += res_bytes + self._operand_bytes(op, symtab)
            return c
        if code in _ELEMENTWISE:
            c.flops += res_elems
            if not fused:
                c.bytes += res_bytes + self._operand_bytes(op, symtab)
            return c
        if code in ("parameter", "constant", "get-tuple-element", "tuple",
                    "bitcast", "after-all", "iota", "partition-id"):
            return c
        if code in ("dynamic-update-slice", "scatter", "dynamic-slice",
                    "gather"):
            if not fused:
                c.bytes += self._slice_op_bytes(op, symtab, res_bytes)
            return c
        # data movement ops (copy, sort, pad, broadcast, transpose,
        # reshape, concatenate, slice, ...)
        if not fused:
            c.bytes += res_bytes + self._operand_bytes(op, symtab)
        return c

    def _fusion_operand_bytes(self, op: Op, symtab) -> float:
        """Operand traffic of a fusion, slice-aware.

        If the fused computation dynamic-slices / gathers one of its
        *parameters* (the classic scan pattern: read this layer's slice of
        a stacked tensor, or this chunk of a loop-invariant buffer), the
        fusion touches only the slice — charge 2x slice bytes instead of
        the full outer operand.
        """
        full = self._operand_bytes(op, symtab)
        m = _CALLS_RE.search(op.rest)
        if not m or m.group(1) not in self.comps:
            return full
        inner_ops = self.comps[m.group(1)]
        param_order = {}
        for o in inner_ops:
            if o.opcode == "parameter":
                pm = re.match(r"\s*(\d+)\s*\)", o.rest)
                if pm:
                    param_order[o.name] = int(pm.group(1))
        outer_refs = _OPERAND_RE.findall(op.rest.split("),")[0])
        adjust = 0.0
        seen = set()
        for o in inner_ops:
            if o.opcode not in ("dynamic-slice", "gather"):
                continue
            refs = _OPERAND_RE.findall(o.rest.split("),")[0])
            if not refs or refs[0] not in param_order or refs[0] in seen:
                continue
            seen.add(refs[0])
            idx = param_order[refs[0]]
            if idx < len(outer_refs) and outer_refs[idx] in symtab:
                outer_bytes = _shape_elems_bytes(symtab[outer_refs[idx]])[1]
                adjust += 2.0 * _shape_elems_bytes(o.shape)[1] - outer_bytes
        return max(0.0, full + adjust)

    # ------------------------------------------------- slice-proportional
    def _slice_op_bytes(self, op: Op, symtab, res_bytes: float) -> float:
        """Traffic for in-place update / slicing ops: ~2x the moved slice."""
        refs = _OPERAND_RE.findall(op.rest.split("),")[0])
        if op.opcode in ("dynamic-update-slice", "scatter"):
            upd = refs[1] if op.opcode == "dynamic-update-slice" else (
                refs[2] if len(refs) > 2 else None)
            if upd and upd in symtab:
                return 2.0 * _shape_elems_bytes(symtab[upd])[1]
            return res_bytes  # fallback
        # dynamic-slice / gather: read+write proportional to the result
        return 2.0 * res_bytes

    def _slice_root_bytes(self, comp: str) -> Optional[float]:
        """If ``comp``'s ROOT is a slice-ish op, its slice-proportional
        bytes; else None."""
        ops = self.comps.get(comp, [])
        root = next((o for o in ops if o.is_root), ops[-1] if ops else None)
        if root is None:
            return None
        if root.opcode in ("dynamic-update-slice", "scatter", "dynamic-slice",
                           "gather"):
            symtab = {o.name: o.shape for o in ops}
            res_bytes = _shape_elems_bytes(root.shape)[1]
            return self._slice_op_bytes(root, symtab, res_bytes)
        return None

    def total(self) -> Cost:
        assert self.entry, "no ENTRY computation found"
        return self.comp_cost(self.entry)


def analyze(hlo_text: str) -> Dict:
    cost = HloCost(hlo_text).total()
    return {"flops": cost.flops, "bytes": cost.bytes,
            "collectives": cost.coll}


# --------------------------------------------------- estimator cost model
_HBM_BW_DEFAULT = 819e9  # TPU v5e bytes/s — matches launch/dryrun.py


def estimator_step_cost(terms: Dict, name: str, q: int = 1,
                        param_bytes: Optional[float] = None,
                        fused_update: bool = True,
                        hbm_bw: float = _HBM_BW_DEFAULT,
                        forward_backend: str = "materialized") -> Dict:
    """Project lowered-step roofline terms onto a different ZO estimator.

    The train graph we lower and cost (launch/specs.py) is a fused
    two-point step — ``repro.estimators.costs.BASELINE``: 2 forwards + 3
    parameter axpy sweeps.  Other estimators change only the *counts* of
    those two primitives, so their step time projects from the measured
    terms without recompiling per estimator:

      * forward-scaling work (flops, activation HBM traffic, per-layer TP
        collectives) scales with the estimator's forward count;
      * when ``param_bytes`` (per-device) is known, axpy sweeps are
        re-priced exactly: each sweep moves ~2x the active parameter
        bytes through HBM.  Without it, memory scales with forwards and
        the sweep counts are still reported for the caller.

    ``forward_backend="virtual"``/``"virtual_ref"`` prices the fused
    runtime (DESIGN.md §10): probe sweeps vanish from the counts, so with
    ``param_bytes`` the perturb+update share of memory time collapses to
    the single update sweep.
    """
    from repro.estimators import costs  # pure-python counts, no jax

    base = costs.step_counts(costs.BASELINE, fused_update=True)
    est = costs.step_counts(name, q=q, fused_update=fused_update,
                            forward_backend=forward_backend)
    f = est["forwards"] / base["forwards"]
    # scaled times + counts only: copying the raw hlo_flops/bytes fields
    # through unscaled would contradict the scaled *_s terms
    out = {"estimator": name, "q": q, "forwards": est["forwards"],
           "axpy_sweeps": est["axpy_sweeps"],
           "forward_backend": forward_backend}
    out["compute_s"] = terms["compute_s"] * f
    out["collective_s"] = terms["collective_s"] * f
    if param_bytes:
        sweep_s = 2.0 * param_bytes / hbm_bw
        fwd_mem = max(0.0, terms["memory_s"] - base["axpy_sweeps"] * sweep_s)
        out["memory_s"] = fwd_mem * f + est["axpy_sweeps"] * sweep_s
    else:
        out["memory_s"] = terms["memory_s"] * f
    return out
