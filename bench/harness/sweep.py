"""Author's tools: find a cell's SLO limits and knee, and read the
correctness numbers over many seeds.  The benchmark's checks never run
these.

``run``: one set-up, then the unloaded TTFT and TPOT (one request at a
time at the mix's 95th-percentile prompt length and its longest
output), then for each offered rate a window of ``--seconds``: the
share of requests within both limits, the tails, and the backlog left
when the window closed.  The limits are the unloaded readings times
the mix's ``slo.scale`` (DistServe's method).

``calibrate``: the whole run, seed after seed in one process (the
compiled programs are shared), printing the program's correctness
readings beside the fp8 control's and the half-batch fault's.
"""
from __future__ import annotations

import gc
import json
import time
from typing import Any, Dict

import numpy as np

from harness import serve, spec, stats
from harness import traffic as gen


def _drain(b) -> None:
    while not b.idle():
        b.step(now=0.0)


def unloaded(prog, mix: Dict[str, Any], vocab: int, n: int = 5
             ) -> Dict[str, float]:
    """Median TTFT and TPOT of ``n`` requests served one at a time."""
    b = prog.batcher
    q = gen.length_quantiles(mix["prompt"], 100)
    plen = int(q[94])
    out_len = int(mix["output"]["max"])
    ttft, tpot = [], []
    for i in range(n):
        r = serve._request(10_000 + i, plen, out_len, i % int(mix["tenants"]),
                           vocab)
        t0 = time.perf_counter()
        b.submit(r)
        first = None
        while not b.idle():
            b.step(now=0.0)
            if first is None and r.tokens:
                first = time.perf_counter()
        last = time.perf_counter()
        ttft.append(first - t0)
        tpot.append((last - first) / max(len(r.tokens) - 1, 1))
    return {"prompt_len": plen, "ttft_ms": 1e3 * float(np.median(ttft)),
            "tpot_ms": 1e3 * float(np.median(tpot))}


def run(cell, args, counter) -> None:
    cfg, mix = cell.config, dict(cell.traffic)
    vocab = spec.family(cfg).dims(cfg)["V"]
    prog = serve.build_program(cfg, mix, args.seed)
    serve.warm(prog, mix, vocab)
    base = unloaded(prog, mix, vocab)
    scale = float(mix["slo"]["scale"])
    lim = {"ttft": scale * base["ttft_ms"] / 1e3,
           "tpot": scale * base["tpot_ms"] / 1e3}
    print(json.dumps({"unloaded": base, "scale": scale,
                      "ttft_limit_ms": 1e3 * lim["ttft"],
                      "tpot_limit_ms": 1e3 * lim["tpot"]}), flush=True)
    for rate in [float(x) for x in args.sweep.split(",")]:
        m = dict(mix, rate_rps=rate)
        arrivals = gen.generate(m, args.seed, args.seconds, vocab)
        t_open = time.perf_counter() + float(m["ramp_s"])
        res = serve.run_window(prog, m, arrivals, args.seconds,
                               time.perf_counter, t_open, None, counter)
        rows = [{"due": r.arrival.due, "first": r.first, "last": r.last,
                 "n": r.n} for r in res["recs"]
                if r.arrival.segment == "window"]
        met = serve.window_metrics(res, args.seconds, m)
        print(json.dumps({
            "rate_rps": rate, "requests": len(rows),
            "attainment": stats.attainment(rows, res["drain_end"],
                                           lim["ttft"], lim["tpot"]),
            "backlog_at_end": res["backlog_end"],
            "unfinished": sum(1 for r in rows if r["last"] is None),
            "compiles_in_window": res["compiles_in_window"],
            **met}), flush=True)
        _drain(prog.batcher)


def calibrate(cell, args, counter) -> None:
    engine = serve.engine_for(cell.config, cell.traffic)
    for seed in [int(x) for x in args.calibrate.split(",")]:
        t0 = time.perf_counter()
        res = serve.run_once(cell, seed, args.seconds, False,
                             time.perf_counter, t0, counter,
                             engine=engine, control=True)
        limits = cell.traffic["check"]["limits"]
        chk = serve.checks(res, limits)
        out = {"seed": seed, "correct": serve.is_correct(res, chk),
               "controls_correct": serve.control_verdicts(res, limits),
               "served": res["served"],
               "train": res.get("train"), "metrics": res["metrics"],
               "setup_s": res["setup_s"],
               "compiles_in_window": res["compiles_in_window"],
               "memory_peak_bytes": res["memory_peak_bytes"]}
        print(json.dumps(out), flush=True)
        del res
        gc.collect()
