"""What every driver shares: the run's context, the measured window under
an optional profiler trace, and the result a driver hands back."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import math
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import trace as trace_mod


def memory_peak_bytes() -> int:
    """The peak of the fullest local chip so far."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file of
    ``chipbench/configs``."""
    from repro.models.config import dense_lm
    run_as = cfg["assumed"]
    if (cfg["model_type"] != "opt" or not cfg["do_layer_norm_before"]
            or run_as["projection_bias"]):
        raise SystemExit(f"chipbench: no mapping for {cfg['model_type']!r}")
    H = cfg["num_attention_heads"]
    return dense_lm(f"chipbench-{cfg['hidden_size']}x{cfg['num_hidden_layers']}",
                    cfg["num_hidden_layers"], cfg["hidden_size"], H, H,
                    cfg["ffn_dim"], cfg["vocab_size"],
                    act=cfg["activation_function"], norm="ln",
                    pos_emb="learned", tie_embeddings=cfg["tie_word_embeddings"],
                    max_seq=cfg["max_position_embeddings"],
                    dtype=run_as["dtype"])


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it (inf where a request never finished)."""
    if not values:
        return math.inf
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


@dataclasses.dataclass
class Result:
    end_to_end: Dict[str, float]
    checks: Dict[str, Dict]          # name -> {"value": x, "limit": y}
    attempted: int
    failed: int
    memory_peak_bytes: int
    window_compiles: int
    readings: Dict                   # what the per-layer readers read


class Run:
    def __init__(self, *, cell, seed, seconds, trace, t_start, watch, peak,
                 keep_trace=None, control=False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t_start, self.watch = trace, t_start, watch
        self.peak, self.keep_trace = peak, keep_trace
        # control runs (chipbench/control.py) also read the reference put
        # in the program's place, in lower precision and under faults
        self.control, self.extra = control, {}
        self.trace_summary: Optional[Dict] = None
        self.excluded_s = 0.0        # check bookkeeping kept out of setup_s

    # -------------------------------------------------------- checks
    def checks(self, readings: Dict[str, float]) -> Dict[str, Dict]:
        """Each reading beside its limit from the workload file.  A
        reading with no key there is not compared (PERF.md names it); a
        key whose limit is null fails until one is set."""
        limits = self.cell.workload.get("limits", {})
        return {k: {"value": float(v), "limit": limits[k]}
                for k, v in readings.items() if k in limits}

    @contextlib.contextmanager
    def bookkeeping(self):
        """Work done only for the check (its readings of the program's
        state); its time is left out of setup_s."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.excluded_s += time.perf_counter() - t0

    def setup_s(self, now: float) -> float:
        return now - self.t_start - self.excluded_s

    # ------------------------------------------------------- tracing
    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (free when not tracing):
        the harness wraps each call into a layer in one."""
        import jax
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    @contextlib.contextmanager
    def window(self):
        """The measured window: a ``window`` span, under the profiler when
        the run traces.  On exit the trace is reduced to
        ``trace_summary`` and its files are deleted."""
        if not self.trace:
            with self.span("window"):
                yield
            return
        import jax
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
            with jax.profiler.trace(tmp):
                with self.span("window"):
                    yield
            (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
            if self.keep_trace:
                shutil.copy(path, self.keep_trace)
            self.trace_summary = trace_mod.reduce(trace_mod.load(path))
