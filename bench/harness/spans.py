"""The program's own host spans in a compact trace: the ``collm.*``
annotations ``ContinuousBatcher.step`` opens around each phase of a
tick (``collm.step`` around the whole tick).  They sit on the same
host line and clock as the harness's ``bench.tick``, so the device's
idle time can be split by the phase the host was in.

A program without these spans (an older tree) gives nothing to read:
``step_idle`` returns None, as it does for a trace with no device
plane."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from harness import tracing

PREFIX = "collm."
STEP = "collm.step"
OUTSIDE = "outside collm.step"


def _idle(trace: Dict[str, List], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Device-idle intervals in [lo, hi]: the gaps between busy ones."""
    out, prev = [], lo
    for s, e in tracing.busy(trace, lo, hi) + [(hi, hi)]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def idle_by_span(trace: Dict[str, List], lo: int, hi: int
                 ) -> Dict[str, int]:
    """Device-idle ns in [lo, hi], summed by the innermost ``collm.*``
    span the host was in (spans of one thread nest, so a stack sweep
    over the span and idle boundaries finds it); idle under no
    ``collm.*`` span goes to ``OUTSIDE``."""
    spans = sorted(((max(s, lo), min(s + d, hi), name)
                    for s, d, name, _ in trace["host"]
                    if name.startswith(PREFIX) and d > 0
                    and s < hi and s + d > lo),
                   key=lambda sp: (sp[0], -sp[1]))
    idle = _idle(trace, lo, hi)
    cuts = sorted({lo, hi} | {t for sp in spans for t in sp[:2]}
                  | {t for iv in idle for t in iv})
    out: Dict[str, int] = {}
    stack: List[Tuple[int, int, str]] = []
    i = j = 0
    for a, b in zip(cuts, cuts[1:]):
        while stack and stack[-1][1] <= a:
            stack.pop()
        while i < len(spans) and spans[i][0] <= a:
            stack.append(spans[i])
            i += 1
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        if j < len(idle) and idle[j][0] <= a:
            label = stack[-1][2] if stack else OUTSIDE
            out[label] = out.get(label, 0) + (b - a)
    return out


def step_idle(ctx, note: str) -> Optional[float]:
    """Device-idle ms inside ``collm.step`` spans of the traced window,
    per ``collm.step`` span (one a tick).  Writes ``note`` into the
    run's notes: that idle per tick by innermost span, and the idle
    outside ``collm.step``."""
    if not ctx.traced or not ctx.trace["device"]:
        return None
    steps = sum(1 for s, _d, name, _ in ctx.trace["host"]
                if name == STEP and ctx.lo <= s <= ctx.hi)
    if steps == 0:
        return None
    split = idle_by_span(ctx.trace, ctx.lo, ctx.hi)
    per_tick = {k: v / 1e6 / steps for k, v in
                sorted(split.items(), key=lambda kv: -kv[1])}
    ctx.res.setdefault("notes", {})[note] = (
        f"device-idle ms per tick over {steps} ticks, by innermost "
        f"span: {per_tick}")
    return sum(v for k, v in per_tick.items() if k != OUTSIDE)
