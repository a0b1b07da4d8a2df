"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The file is an ``XSpace`` protocol buffer (the schema of xplane.proto,
declared below field by field, so that nothing but ``protobuf`` reads
it).  On a TPU device plane (``/device:TPU:<n>``):

  * line ``XLA Modules`` holds one event per execution of a compiled
    program, named ``<module>(<program id>)``, as ``jit_step(34330...)``;
  * line ``XLA Ops`` holds one event per HLO operation, named by its
    HLO text (``%fusion.140 = bf16[...] fusion(...)``).  The operation's
    category (``convolution fusion``, ``loop fusion``, ``while``, ...)
    and program id are stats of the event's metadata, not of the event.
    Events nest: a ``while`` or ``conditional`` spans the operations of
    its body, so an operation's own time is its span less its children's.

On the host plane (``/host:CPU``) the harness's spans are events named
``chipbench.<span>`` (``jax.profiler.TraceAnnotation``).  Event times
are the line's ``timestamp_ns`` plus the event's ``offset_ps``, on one
clock for host and device.

``load`` turns the file into plain lists; ``reduce`` bounds everything
by the harness's ``chipbench.window`` span and gives:

  * busy_s: the union of the intervals in which an operation ran, per
    device, averaged over the devices; window_s: the window's length;
  * module_s / module_calls: device time and executions per XLA module
    (``jit_dstep`` and so on, the program id dropped);
  * category_s: operations' own device time per category;
  * breakdown: the ten operations that took most time of their own, and
    the ten longest idle gaps, each named by the innermost harness span
    that was open on the host at the gap's middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "chipbench."
MATMUL_CATEGORIES = ("convolution", "dot")

# xplane.proto: (message, [(field, number, type, repeated, message type)])
_T_DOUBLE, _T_INT64, _T_UINT64, _T_STRING, _T_MSG, _T_BYTES = 1, 3, 4, 9, 11, 12
_SCHEMA = [
    ("XStat", [("metadata_id", 1, _T_INT64, 0, None),
               ("double_value", 2, _T_DOUBLE, 0, None),
               ("uint64_value", 3, _T_UINT64, 0, None),
               ("int64_value", 4, _T_INT64, 0, None),
               ("str_value", 5, _T_STRING, 0, None),
               ("bytes_value", 6, _T_BYTES, 0, None),
               ("ref_value", 7, _T_UINT64, 0, None)]),
    ("XEvent", [("metadata_id", 1, _T_INT64, 0, None),
                ("offset_ps", 2, _T_INT64, 0, None),
                ("num_occurrences", 5, _T_INT64, 0, None),
                ("duration_ps", 3, _T_INT64, 0, None),
                ("stats", 4, _T_MSG, 1, "XStat")]),
    ("XLine", [("id", 1, _T_INT64, 0, None),
               ("display_id", 10, _T_INT64, 0, None),
               ("name", 2, _T_STRING, 0, None),
               ("display_name", 11, _T_STRING, 0, None),
               ("timestamp_ns", 3, _T_INT64, 0, None),
               ("duration_ps", 9, _T_INT64, 0, None),
               ("events", 4, _T_MSG, 1, "XEvent")]),
    ("XEventMetadata", [("id", 1, _T_INT64, 0, None),
                        ("name", 2, _T_STRING, 0, None),
                        ("display_name", 4, _T_STRING, 0, None),
                        ("metadata", 3, _T_BYTES, 0, None),
                        ("stats", 5, _T_MSG, 1, "XStat"),
                        ("child_id", 6, _T_INT64, 1, None)]),
    ("XStatMetadata", [("id", 1, _T_INT64, 0, None),
                       ("name", 2, _T_STRING, 0, None),
                       ("description", 3, _T_STRING, 0, None)]),
    ("EventMetadataEntry", [("key", 1, _T_INT64, 0, None),
                            ("value", 2, _T_MSG, 0, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _T_INT64, 0, None),
                           ("value", 2, _T_MSG, 0, "XStatMetadata")]),
    ("XPlane", [("id", 1, _T_INT64, 0, None),
                ("name", 2, _T_STRING, 0, None),
                ("lines", 3, _T_MSG, 1, "XLine"),
                ("event_metadata", 4, _T_MSG, 1, "EventMetadataEntry"),
                ("stat_metadata", 5, _T_MSG, 1, "StatMetadataEntry"),
                ("stats", 6, _T_MSG, 1, "XStat")]),
    ("XSpace", [("planes", 1, _T_MSG, 1, "XPlane"),
                ("errors", 2, _T_STRING, 1, None),
                ("warnings", 3, _T_STRING, 1, None),
                ("hostnames", 4, _T_STRING, 1, None)]),
]
_PKG = "chipbench.xplane"


def _xspace_class():
    """The XSpace message class, built from _SCHEMA in a pool of its own."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                            package=_PKG, syntax="proto3")
    for msg, fields in _SCHEMA:
        m = fd.message_type.add(name=msg)
        for name, number, ftype, repeated, mtype in fields:
            f = m.field.add(name=name, number=number, type=ftype,
                            label=3 if repeated else 1)
            if mtype:
                f.type_name = f".{_PKG}.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def parse(data: bytes):
    xs = _xspace_class()()
    xs.ParseFromString(data)
    return xs


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _op_name(name: str) -> str:
    """``%fusion.140 = bf16[...] fusion(...)`` -> ``%fusion.140``."""
    return name.split(" = ", 1)[0].strip()


def _events(line):
    base = line.timestamp_ns
    for ev in line.events:
        s = base + ev.offset_ps / 1e3
        yield ev, s, s + ev.duration_ps / 1e3


def load(path: str) -> Dict:
    with open(path, "rb") as f:
        xs = parse(f.read())
    devices, spans = {}, []
    for plane in xs.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            stat_name = {e.key: e.value.name for e in plane.stat_metadata}

            def stat(md, key):
                for st in md.stats:
                    if stat_name.get(st.metadata_id) == key:
                        return (st.str_value or stat_name.get(st.ref_value)
                                or st.int64_value or st.uint64_value)
                return None

            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines or "XLA Modules" not in lines:
                # a renamed line would silence every per-layer metric
                raise SystemExit(f"chipbench: {plane.name} has no 'XLA Ops' "
                                 f"or 'XLA Modules' line; its lines: "
                                 f"{sorted(lines)}")
            mods, program = [], {}
            for ev, s, e in _events(lines["XLA Modules"]):
                name = meta[ev.metadata_id].name
                m = re.search(r"\((\d+)\)$", name)
                if m:
                    program[int(m.group(1))] = _module_name(name)
                mods.append({"name": _module_name(name), "start": s, "end": e})
            ops = []
            for ev, s, e in _events(lines["XLA Ops"]):
                md = meta[ev.metadata_id]
                pid = stat(md, "program_id")
                ops.append({"name": _op_name(md.name),
                            "module": program.get(int(pid or 0), "?"),
                            "category": str(stat(md, "hlo_category") or "?"),
                            "start": s, "end": e})
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev, s, e in _events(line):
                    name = meta[ev.metadata_id].name
                    if name.startswith(SPAN_PREFIX):
                        spans.append({"name": name[len(SPAN_PREFIX):],
                                      "start": s, "end": e})
    return {"devices": devices, "spans": spans}


def own_times(ops: List[Dict]) -> List[float]:
    """Each operation's own time, in ``ops``' order: its span less the
    spans of the operations directly inside it (a ``while`` holds its
    body's operations on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start"],
                                                   -ops[i]["end"]))
    own = [op["end"] - op["start"] for op in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]]["end"] <= ops[i]["start"]:
            stack.pop()
        if stack and ops[i]["end"] <= ops[stack[-1]]["end"]:
            own[stack[-1]] -= ops[i]["end"] - ops[i]["start"]
        stack.append(i)
    return own


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(data: Dict) -> Dict:
    windows = [s for s in data["spans"] if s["name"] == "window"]
    if not windows or not data["devices"]:
        raise SystemExit("chipbench: the trace has no window span or no "
                         "TPU device plane")
    lo, hi = windows[0]["start"], windows[0]["end"]
    window_s = (hi - lo) / 1e9
    busy, module_s, module_calls, category_s = [], defaultdict(float), \
        defaultdict(int), defaultdict(float)
    op_s = defaultdict(float)
    gaps = []
    for dev in data["devices"].values():
        ivs = []
        inside = []
        for op in dev["ops"]:
            s, e = _clip(op["start"], op["end"], lo, hi)
            if e > s:
                inside.append({**op, "start": s, "end": e})
                ivs.append((s, e))
        for op, own in zip(inside, own_times(inside)):
            category_s[op["category"]] += own / 1e9
            op_s[f"{op['module']}:{op['name']} ({op['category']})"] += own / 1e9
        for m in dev["modules"]:
            s, e = _clip(m["start"], m["end"], lo, hi)
            if e > s:
                module_s[m["name"]] += (e - s) / 1e9
                module_calls[m["name"]] += 1
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    n_dev = len(data["devices"])
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_at(data["spans"], (s + e) / 2), (e - s) / 1e9]
             for s, e in gaps[:10]]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n_dev, "window_s": window_s,
            "devices": n_dev,
            "module_s": {k: v / n_dev for k, v in module_s.items()},
            "module_calls": {k: v / n_dev for k, v in module_calls.items()},
            "category_s": {k: v / n_dev for k, v in category_s.items()},
            "breakdown": {"device_ops": [[k, v / n_dev] for k, v in top],
                          "idle_gaps": named}}


def _host_at(spans: List[Dict], t: float) -> str:
    inner = [s for s in spans if s["start"] <= t <= s["end"]
             and s["name"] != "window"]
    if not inner:
        return "host outside any harness span"
    return min(inner, key=lambda s: s["end"] - s["start"])["name"]


def matmul_s(summary: Dict) -> float:
    """Device seconds in matrix products: categories the TPU's profiler
    files convolutions and dots (and their fusions) under."""
    return sum(v for k, v in summary["category_s"].items()
               if any(c in k for c in MATMUL_CATEGORIES))
