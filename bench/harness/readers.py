"""What a per-layer metric reader sees, and how readers are found.

A reader is ``bench/metrics/<metric name>.py`` with one function,
``read(ctx)``, that returns the metric's value or None when the run
gave it nothing to read.  ``ctx`` is a ``Context``: the run's request
and tick records, the reduced trace and the traced window, the
configuration, the chip's peaks, and the shared arithmetic below.
"""
from __future__ import annotations

import importlib.util
from typing import Any, Callable, Dict, List, Optional

from harness import spec, tracing


class Context:
    def __init__(self, res: Dict[str, Any], cfg: Dict[str, Any],
                 mix: Dict[str, Any], peaks: Dict[str, Any]):
        self.res, self.cfg, self.mix, self.peaks = res, cfg, mix, peaks
        self.window_recs = [r for r in res["recs"]
                            if r.arrival.segment == "window"]
        self.trace = res.get("trace")
        self.lo = self.hi = 0
        self.traced_ticks: List[tuple] = []
        if self.trace:
            win = tracing.window(self.trace)
            if win is not None:
                self.lo, self.hi, ids = win
                ids = set(ids)
                self.traced_ticks = [t for t in res["ticks"] if t[7] in ids]

    # --------------------------------------------------------- trace --
    @property
    def traced(self) -> bool:
        return bool(self.traced_ticks)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        return sum(e - s for s, e in
                   tracing.busy(self.trace, self.lo, self.hi)) / 1e9

    def module(self, pattern: str):
        return tracing.module_time(self.trace, pattern, self.lo, self.hi)

    def ops(self, pattern: str):
        return tracing.op_time(self.trace, pattern, self.lo, self.hi)

    # ---------------------------------------------------------- work --
    def decode_contexts(self) -> List[int]:
        return [c for t in self.traced_ticks for c in t[4]]

    def prompt_lens(self) -> List[int]:
        return [p for t in self.traced_ticks for p in t[5]]

    def model_ops(self) -> float:
        """Operations the traced ticks' live work needs: real prompt
        tokens, live decode rows at their contexts and, where a tick
        trained, its train leg."""
        fam = spec.family(self.cfg)
        ops = fam.prefill_ops(self.cfg, self.prompt_lens()) \
            + fam.decode_ops(self.cfg, self.decode_contexts())
        train = self.mix.get("train")
        if train:
            seq = int(train["seq"])
            for t in self.traced_ticks:
                if t[2]:
                    ops += fam.train_ops(self.cfg, t[3], seq)
        return float(ops)

    def mfu_pct(self) -> Optional[float]:
        if not self.traced:
            return None
        peak = float(self.peaks["bf16_flops_per_s"])
        return 100.0 * self.model_ops() / (self.window_s * peak)

    def idle_pct(self) -> Optional[float]:
        if not self.traced or self.window_s <= 0:
            return None
        busy = self.busy_s()
        if busy <= 0:        # no device plane in the trace
            return None
        return 100.0 * (1.0 - busy / self.window_s)


def load_reader(name: str) -> Callable[[Context], Optional[float]]:
    path = spec.bench_dir() / "metrics" / f"{name}.py"
    found = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod.read


def read_all(metrics: List[Dict[str, Any]], ctx: Context
             ) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell that found something."""
    out = {}
    for m in metrics:
        val = load_reader(m["name"])(ctx)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out
