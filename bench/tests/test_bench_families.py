"""Model families found by name: a configuration that names a second
family is served, checked and counted through that family alone, and a
family with no file, or a file without the whole interface, stops the
run and says which."""
from __future__ import annotations

import time

import pytest

from bench_testlib import bench_copy, benchmark_with  # noqa: F401
from families import INTERFACE
from harness import readers, serve, spec

SEED = 2**31 + 23


def test_second_family_is_reached_for_everything(bench_copy):
    cell = spec.load_cell("tiny-recording.tiny-cotrain",
                          benchmark_with("tiny-cotrain", "tiny-recording"))
    fam = spec.family(cell.config)
    t0 = time.perf_counter()
    res = serve.run_once(cell, SEED, 2.0, False, time.perf_counter, t0,
                         serve.CompileCounter())
    chk = serve.checks(res, cell.traffic["check"]["limits"])
    assert serve.is_correct(res, chk), chk
    # the window's ticks stand in for a traced window of one second,
    # and a kernel call of a millisecond for the trace
    ctx = readers.Context(res, cell.config, cell.traffic,
                          spec.peaks_for("TPU v5 lite"))
    ctx.traced_ticks = list(res["ticks"])
    ctx.lo, ctx.hi = 0, 10**9
    ctx.ops = lambda pattern: (1e-3, 1)
    assert any(t[2] for t in ctx.traced_ticks) and ctx.decode_contexts()
    assert readers.load_reader("mfu.cotrain")(ctx) > 0
    assert readers.load_reader("paged_attn_roofline")(ctx) > 0
    assert fam.CALLED == set(INTERFACE)


@pytest.mark.parametrize("cfg,says", [
    ({"family": "no-such"}, "families/no-such.py"),
    ({"family": "partial"}, "lacks make_params, make_adapter"),
    ({}, "names no model family"),
], ids=["no-file", "partial-interface", "no-key"])
def test_family_without_file_or_interface_exits(bench_copy, cfg, says):
    (bench_copy / "families" / "partial.py").write_text(
        "def dims(cfg):\n    return {}\n\n\n"
        "def program_config(cfg):\n    return None\n")
    with pytest.raises(SystemExit) as err:
        spec.family(cfg)
    assert says in str(err.value)
