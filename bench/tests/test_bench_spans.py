"""The readers of the program's own stamps and spans, on hand-made
records and traces with known answers (a gap outside ``collm.step``
that is not counted, spans that nest, a trace with no device plane or
from a program without the spans), then on a traced CPU run, where the
spans must sit on the harness's host line, nested in ``bench.tick``."""
from __future__ import annotations

import time
import types

import pytest

from bench_testlib import bench_copy, benchmark_with  # noqa: F401
from harness import readers, serve, spans, spec

STEP_READERS = ["step_idle_ms.serve", "step_idle_ms.cotrain",
                "step_idle_ms.longprompt"]


def _hand(with_spans=True, with_device=True):
    # ns; two ticks 0-100 and 100-200; device busy 10-30, 60-100,
    # 130-170, so idle 0-10, 30-60, 100-130, 170-200
    host = [[0, 100, "bench.tick", 0], [100, 100, "bench.tick", 1]]
    if with_spans:
        host += [[5, 90, "collm.step", -1], [5, 10, "collm.admit", -1],
                 [40, 20, "collm.token_pull", -1],
                 [105, 80, "collm.step", -1], [170, 10, "collm.emit", -1]]
    host.sort(key=lambda h: h[0])
    device = [[10, 20, "fusion.1", ""], [60, 40, "fusion.2", ""],
              [130, 40, "fusion.3", ""]] if with_device else []
    return {"device": device, "modules": [], "host": host}


def _ctx(trace):
    ticks = [(0.0, 1e-7, False, 0, [5], [], 1, 0),
             (1e-7, 2e-7, False, 0, [6], [], 1, 1)]
    return readers.Context({"recs": [], "ticks": ticks, "trace": trace},
                           {}, {}, {})


def test_idle_split_by_innermost_span_by_hand():
    # 0-5 and 100-105 and 185-200 lie under no collm.* span; 5-10 under
    # collm.admit; 30-40, 105-130 and 180-185 under collm.step itself;
    # 40-60 under collm.token_pull; 170-180 under collm.emit
    assert spans.idle_by_span(_hand(), 0, 200) == {
        spans.OUTSIDE: 25, "collm.admit": 5, "collm.step": 40,
        "collm.token_pull": 20, "collm.emit": 10}


@pytest.mark.parametrize("name", STEP_READERS)
@pytest.mark.parametrize("trace,want", [
    # 75 ns of idle inside collm.step over two ticks; the 25 ns outside
    # it (before, between and after the steps) are not counted
    (_hand(), 37.5e-6),
    (_hand(with_device=False), None),     # no device plane
    (_hand(with_spans=False), None),      # a program without the spans
    (None, None),                         # an untraced run
], ids=["nested", "no-device-plane", "no-spans", "untraced"])
def test_step_idle_readers_by_hand(name, trace, want):
    ctx = _ctx(trace) if trace is not None else readers.Context(
        {"recs": [], "ticks": [], "trace": None}, {}, {}, {})
    got = readers.load_reader(name)(ctx)
    if want is None:
        assert got is None
        assert not ctx.res.get("notes")
        return
    assert got == pytest.approx(want)
    (note,) = ctx.res["notes"].values()
    assert "collm.token_pull" in note and spans.OUTSIDE in note


def _rec(stamps):
    req = types.SimpleNamespace(**stamps) if stamps is not None else None
    return types.SimpleNamespace(
        arrival=types.SimpleNamespace(segment="window"), req=req)


@pytest.mark.parametrize("stamps,want", [
    ([{"submitted_wall": 1.0, "admitted_wall": 1.002},
      {"submitted_wall": 2.0, "admitted_wall": 2.010},
      {"submitted_wall": 3.0, "admitted_wall": 3.004},
      None], 4.0),                        # a refused request has no req
    ([{}, {}], None),                     # a program without the stamps
], ids=["stamped", "unstamped"])
def test_admit_wait_by_hand(stamps, want):
    ctx = readers.Context({"recs": [_rec(s) for s in stamps], "ticks": [],
                           "trace": None}, {}, {}, {})
    got = readers.load_reader("admit_wait_ms")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_traced_cpu_run_nests_the_spans_in_the_tick(bench_copy):
    cell = spec.load_cell("tiny.tiny-chat", benchmark_with("tiny-chat"))
    t0 = time.perf_counter()
    res = serve.run_once(cell, 2**31 + 5, 3.0, True, time.perf_counter,
                         t0, serve.CompileCounter())
    host = res["trace"]["host"]
    ticks = [h for h in host if h[2] == "bench.tick"]
    steps = [h for h in host if h[2] == "collm.step"]
    assert ticks and len(steps) == len(ticks)
    for (ts, td, *_), (ss, sd, *_) in zip(ticks, steps):
        assert ts <= ss and ss + sd <= ts + td
    names = {h[2] for h in host}
    assert {"collm.admit", "collm.dispatch", "collm.token_pull",
            "collm.emit"} <= names
    ctx = readers.Context(res, cell.config, cell.traffic,
                          spec.peaks_for("TPU v5 lite"))
    got = readers.read_all(cell.per_layer, ctx)
    # the test cell reports like the long-prompt cell
    assert got["admit_wait_ms.longprompt"]["value"] >= 0.0
    # the CPU trace has no TPU plane: nothing to read there
    assert "step_idle_ms.longprompt" not in got
