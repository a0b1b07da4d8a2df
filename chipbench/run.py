#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is comes from files found by name:

  * ``BENCHMARK.json`` (the checkout's root): the cell's configuration,
    traffic and chips, and which metrics it reports;
  * ``chipbench/workloads/<cell>.json``: its driver kind, the program's
    settings and the limits of its correctness check;
  * ``chipbench/configs/<config>.json``: the model, at published widths;
  * ``chipbench/traffic/<traffic>.json``: the mix that
    ``traffic/generate.py`` turns into rows or requests;
  * ``chipbench/drivers/<kind>.py``: set-up, the measured window and the
    check, for every cell of that kind;
  * ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

With ``--trace 0`` the result's metrics are the cell's end-to-end ones,
taken on the host's clock; with ``--trace 1`` the window runs under the
profiler and the metrics are the cell's per-layer ones, read from the
device trace.  The last line of standard output is one JSON object:
correct, attempted, failed, metrics, device (and with --trace 1
breakdown), and last, ``checks``: each number compared with its limit.
Those also end standard error.  With no TPU, or fewer chips than the
cell asks for, the run exits non-zero before it builds anything.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))


def fail(msg: str) -> "SystemExit":
    return SystemExit(f"chipbench: {msg}")


def _json(path: Path) -> Dict:
    if not path.is_file():
        raise fail(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _module(path: Path, name: str):
    if not path.is_file():
        raise fail(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's workloads with every file it names."""

    def __init__(self, bench: Dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise fail(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.workload = _json(HERE / "workloads" / f"{name}.json")
        self.cfg = _json(HERE / "configs" / f"{self.entry['config']}.json")
        from traffic import generate
        self.mix = generate.load(self.entry["traffic"])
        self.driver = _module(HERE / "drivers" / f"{self.workload['driver']}.py",
                              f"chipbench_driver_{self.workload['driver']}")
        self.end_to_end = self._metrics(bench["end_to_end"])
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self._metrics(bench["per_layer"])
                          if m["moves"] in moved]

    def _metrics(self, entries: List[Dict]) -> List[Dict]:
        return [m for m in entries
                if "workloads" not in m or self.name in m["workloads"]]


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    """The devices JAX finds; no TPU, or too few chips, is an error."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise fail(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                   f"{d.platform} device(s) ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peaks(kind: str) -> Dict:
    table = _json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise fail(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


class CompileWatch:
    """Counts backend compiles (cache hits included) from JAX's own
    monitoring events, so a window can prove that none happened."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def enable_compile_cache(root: Path) -> None:
    """The program's persistent cache at its fixed place; every program
    is written to it, however short its compile."""
    import jax
    sys.path.insert(0, str(root / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def judge(checks: Dict[str, Dict]) -> bool:
    """Every compared number must be finite and within its limit; a
    number whose limit is not yet set fails."""
    return all(c["limit"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())


def run(argv: Optional[List[str]] = None, *, stand_in: Optional[Dict] = None,
        keep_trace: Optional[str] = None, control: bool = False) -> Dict:
    """Parse ``argv``, run the cell and return the result line's dict.

    ``stand_in`` (tests only) replaces the configuration's and the
    mix's sizes by tiny ones and lifts the TPU requirement, so a CPU
    runs the same code; its ``entry`` adds a cell that BENCHMARK.json
    does not hold; ``keep_trace`` copies the raw trace there;
    ``control`` (chipbench/control.py) adds, under ``control``, each
    control's readings judged by the cell's limits."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise fail(f"no program beside the benchmark (no src/repro in {ROOT})")
    bench = _json(ROOT / "BENCHMARK.json")
    if stand_in and "entry" in stand_in:
        # a cell held out of BENCHMARK.json (PERF.md), rehearsed by the tests
        bench = {**bench, "workloads": bench["workloads"] + [stand_in["entry"]]}
    cell = Cell(bench, args.workload)
    if stand_in:
        cell.cfg = {**cell.cfg, **stand_in.get("config", {})}
        cell.mix = {**cell.mix, **stand_in.get("traffic", {})}
        cell.workload = {**cell.workload, **stand_in.get("workload", {})}
    device = device_info(cell.chips, require_tpu=stand_in is None)
    peak = peaks(device["kind"]) if stand_in is None else stand_in["peaks"]
    enable_compile_cache(ROOT)
    watch = CompileWatch()
    from harness import Run
    ctx = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t_start=T_START, watch=watch,
              peak=peak, keep_trace=keep_trace, control=control)
    res = cell.driver.run(ctx)

    if res.window_compiles:
        raise fail(f"{res.window_compiles} compile(s) inside the measured "
                   "window")
    device["memory_peak_bytes"] = res.memory_peak_bytes
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            reader = _module(HERE / "metrics" / f"{m['name']}.py",
                             "chipbench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(res.readings)
            if value is None and "workloads" in m:
                # the cell is listed as one where the reader finds its
                # reading: a trace without it is a fault, not a silence
                raise fail(f"{m['name']} found nothing to read in the trace")
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = res.readings["trace"]["busy_s"]
        device["window_s"] = res.readings["trace"]["window_s"]
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in res.end_to_end:
                raise fail(f"driver gave no {m['name']}")
            metrics[m["name"]] = {"value": res.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": judge(res.checks), "attempted": res.attempted,
           "failed": res.failed, "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = res.readings["trace"]["breakdown"]
    if control:
        # each control judged by the cell's own limits, as a run is
        out["control"] = {}
        for kind, readings in ctx.extra.items():
            c = ctx.checks(readings)
            out["control"][kind] = {"correct": judge(c), "checks": c}
    out["checks"] = res.checks
    return out


def main(argv: Optional[List[str]] = None, **hooks) -> None:
    out = run(argv, **hooks)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
