"""Run one benchmark cell once, on the chip this process finds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration and
traffic mix are files under ``bench/configs`` and ``bench/traffic``,
found by name.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit).  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a profiler trace of a few seconds of the window.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.

Two more modes serve the benchmark's author, not its checks:
``--sweep r1,r2,...`` measures the unloaded TTFT and TPOT (one request
at a time) and then the SLO attainment and backlog at each offered
rate after one set-up; ``--calibrate s1,s2,...`` runs the cell on each
seed in one process and prints every correctness reading together with
the fp8 control's and the half-batch fault's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (req/s)")
    ap.add_argument("--calibrate", default="",
                    help="comma-separated seeds read in one process")
    ap.add_argument("--dump-trace", default="",
                    help="write 30 ticks of the compact trace to this file")
    return ap.parse_args(argv)


def device_or_exit(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{d0.platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return d0, len(devs)


def setup_jax() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program goes to the cache, however fast it compiled, so
    # that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # and none is evicted: a cap smaller than a cell's programs (the
    # co-train cell's pass 190 MiB) drops each program, in the order
    # the warm-up asks for them, before the next run can find it
    jax.config.update("jax_compilation_cache_max_size", -1)


def result_line(args, cell, res, d0, count, peaks) -> dict:
    from harness import readers, serve, tracing
    recs = [r for r in res["recs"] if r.arrival.segment == "window"]
    failed = sum(1 for r in recs if r.last is None)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": count, "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": None, "attempted": len(recs), "failed": failed}
    if args.trace:
        ctx = readers.Context(res, cell.config, cell.traffic, peaks)
        out["metrics"] = readers.read_all(cell.per_layer, ctx)
        if ctx.traced:
            ids = {t[7] for t in ctx.traced_ticks}
            rest = [t for t in res["ticks"] if t[7] not in ids
                    and 0.0 <= t[0] and t[1] <= args.seconds]
            span = lambda ts: sum(t[1] - t[0] for t in ts) / max(len(ts), 1)
            res["traced_ticks"] = (
                f"{len(ids)} traced ticks of {span(ctx.traced_ticks)} s "
                f"on average, {span(rest)} s untraced in the window")
            device["busy_s"] = ctx.busy_s()
            device["window_s"] = ctx.window_s
            out["breakdown"] = tracing.breakdown(ctx.trace, ctx.lo, ctx.hi)
    else:
        values = dict(res["metrics"], setup_s=res["setup_s"])
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": float(values[k]), "unit": units[k]}
                          for k in units}
    out["device"] = device
    chk = serve.checks(res, cell.traffic["check"]["limits"])
    out["correct"] = serve.is_correct(res, chk)
    out["checks"] = chk
    return out


def report(res, out) -> None:
    """The run's facts on standard error, the compared numbers last."""
    from harness import stats
    log = lambda m: print(m, file=sys.stderr, flush=True)
    late = res["lateness"]
    if late:
        log(f"generator lateness (s): median {stats.percentile(late, 50)} "
            f"p99 {stats.percentile(late, 99)} max {max(late)}")
    rows = [{"due": r.arrival.due, "first": r.first, "last": r.last,
             "n": r.n} for r in res["recs"] if r.arrival.segment == "window"]
    slo = res["slo"]
    log(f"share of the window's requests within both SLO limits "
        f"(TTFT {slo['ttft_ms']} ms, TPOT {slo['tpot_ms']} ms): "
        f"{stats.attainment(rows, res['drain_end'], slo['ttft_ms'] / 1e3, slo['tpot_ms'] / 1e3)}")
    log(f"window metrics, end to end or not in this cell: {res['metrics']}")
    log(f"model: {res['model']}")
    log(f"programs compiled or loaded inside the window: "
        f"{res['compiles_in_window']}")
    w = res["warm"]
    log(f"set-up: {res['setup_s']} s: {w['build_s']} s to build the "
        f"program, {w['warm_s']} s to warm {res['warm_programs']} programs "
        f"over {w['ticks']} ticks ({w['compiles']} compiles taking "
        f"{w['compile_s']} s, {w['cache_loads']} loaded from the cache)")
    log(f"reference check: {res['reference_s']} s over "
        f"{res['served']['requests']} requests, "
        f"{res['served']['tokens']} served tokens")
    if res.get("train"):
        log(f"train check: {res['train']}")
    for k, v in res.get("notes", {}).items():
        log(f"{k}: {v}")
    if res.get("traced_ticks"):
        log(f"tracing cost: {res['traced_ticks']}")
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")


def main(argv=None) -> dict:
    args = parse(argv)
    from harness import spec
    cell = spec.load_cell(args.workload)
    d0, count = device_or_exit(cell.chips)
    peaks = spec.peaks_for(d0.device_kind)
    setup_jax()
    from harness import serve
    counter = serve.CompileCounter()
    if args.sweep:
        from harness import sweep
        sweep.run(cell, args, counter)
        return {}
    if args.calibrate:
        from harness import sweep
        sweep.calibrate(cell, args, counter)
        return {}
    res = serve.run_once(cell, args.seed, args.seconds, bool(args.trace),
                         time.perf_counter, T_START, counter)
    if args.dump_trace and res.get("trace"):
        from harness import tracing
        with open(args.dump_trace, "w") as fh:
            json.dump(tracing.cut(res["trace"], 30), fh)
    out = result_line(args, cell, res, d0, count, peaks)
    report(res, out)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    main()
