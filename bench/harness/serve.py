"""One run of a serving cell: build the program from the cell's files,
warm every program variant the window can reach, ramp the traffic,
measure an open-loop window, follow the window's requests to their end
under a bounded drain, and check what was served against the plain
reference.

The window drives ``ContinuousBatcher.submit``/``step`` directly.  Each
request is stamped on the harness's clock: due time (open loop), end
of the tick that produced its first token, end of the tick that
produced its last.  Ticks run back to back while there is work; a
serve-only batcher with nothing to do waits for the next due time.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from harness import spec, stats, tracing
from harness import traffic as gen

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """XLA compiles and persistent-cache loads seen by this process
    (the listener of ``chip_smoke.py``, plus cache hits: a program
    loaded from the cache is a new program all the same)."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.loads += 1

    def programs(self) -> int:
        return self.compiles + self.loads


# ---------------------------------------------------------------- build --
def engine_for(cfg: Dict[str, Any], mix: Dict[str, Any]):
    from repro.core.engine import Engine
    from repro.models.model import build
    from repro.optim.adamw import AdamW
    opt = mix.get("train", {}).get("optimizer")
    adamw = AdamW() if opt is None else AdamW(
        lr=float(opt["lr"]), b1=float(opt["b1"]), b2=float(opt["b2"]),
        eps=float(opt["eps"]), weight_decay=0.0,
        clip_norm=float(opt["clip_norm"]))
    return Engine(model=build(spec.family(cfg).program_config(cfg)),
                  optimizer=adamw)


@dataclasses.dataclass
class Program:
    batcher: Any
    train_batches: List[Dict[str, Any]]
    lora0: Any                    # the train adapter before any step
    max_seq: int


def sizes(cfg: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    mx = gen.max_sizes(mix)
    bs = int(cfg["serving"]["block_size"])
    max_seq = mx["prompt_max"] + mx["output_max"]
    return {"prompt_pad": mx["prompt_max"], "max_seq": max_seq,
            "block_size": bs, "n_slots": int(cfg["serving"]["n_slots"]),
            "n_blocks": 1 + int(cfg["serving"]["n_slots"])
            * (-(-max_seq // bs))}


def build_program(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                  engine=None) -> Program:
    from repro.runtime.serving_loop import AdapterRegistry, \
        ContinuousBatcher
    engine = engine or engine_for(cfg, mix)
    model = engine.model
    fam = spec.family(cfg)
    params = fam.make_params(cfg, seed)
    want = jax.tree.map(lambda s: (s.shape, s.dtype.name),
                        jax.eval_shape(model.init, jax.random.key(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype.name), params)
    if want != got:
        raise SystemExit(f"weights do not match the program's layout:\n"
                         f"program {want}\nbench {got}")
    n_ten = int(mix["tenants"])
    registry = AdapterRegistry(model, capacity=n_ten)
    tenants = [fam.make_adapter(cfg, seed, t) for t in range(n_ten)]
    for t, tree in enumerate(tenants):
        registry.register(f"tenant{t}", tree)
    train = mix.get("train")
    lora0 = tenants[0]
    opt_state = engine.optimizer.init(lora0) if train else None
    sz = sizes(cfg, mix)
    batcher = ContinuousBatcher(
        engine, params, lora0, n_slots=sz["n_slots"],
        max_seq=sz["max_seq"], prompt_pad=sz["prompt_pad"],
        opt_state=opt_state, paged=True, block_size=sz["block_size"],
        n_blocks=sz["n_blocks"], adapters=registry,
        tpot_target=float(mix["slo"]["tpot_ms"]) / 1e3 if train else 0.0)
    batches = []
    if train:
        pool = gen.train_pool(mix, seed, fam.dims(cfg)["V"],
                              int(train["pool"]))
        for k in range(int(train["pool"])):
            batches.append({key: jnp.asarray(v[k])
                            for key, v in pool.items()})
    return Program(batcher, batches, lora0, sz["max_seq"])


# --------------------------------------------------------------- warm-up --
def _request(rid: int, prompt_len: int, max_new: int, tenant: int,
             vocab: int):
    from repro.runtime.serving_loop import GenRequest
    toks = (np.arange(prompt_len, dtype=np.int64) * 7919 + rid) % vocab
    return GenRequest(request_id=-1 - rid, prompt=toks.astype(np.int32),
                      max_new_tokens=max_new,
                      adapter_id=f"tenant{tenant}")


class _Cycle:
    """Stands in for the batcher's tick budget while warming: plans
    the train leg of each tick in turn full, halved and skipped, so
    every variant the budget can choose runs at every table width, and
    learns nothing from warm-up ticks (which compile)."""

    target_s = 0.0
    decode_tick_s = None

    def __init__(self) -> None:
        self.n = 0

    def train_tokens(self, b: int, s: int, _spent: float):
        self.n += 1
        return (0, (b // 2) * s, None)[self.n % 3]

    def prefill_allowance(self, _n: int) -> float:
        return float("inf")

    def observe_decode(self, *_a) -> None:
        pass

    observe_prefill = observe_train = observe_decode


def warm(prog: Program, mix: Dict[str, Any], vocab: int) -> Dict[str, Any]:
    """Run every program variant the window can reach, through the
    batcher's own ``submit``/``step`` only:

      every prefill wave size W = 1..n_slots (W requests admitted in
        one tick);
      every decode table width: one request per block count from the
        smallest prompt's first decode write up to the prompt budget,
        then one request decoding from the longest prompt to the
        longest output (the widths only long outputs reach);
      with a train leg, each of those ticks carries a train batch that
        ``_Cycle`` plans full, halved or skipped in turn (three ticks
        at each width), and one idle tick trains alone.  The train
        adapter and optimizer state are put back afterwards, so the
        window's first train step starts from the seed's adapter.

    Returns how many ticks and requests it took."""
    b = prog.batcher
    n_ten = int(mix["tenants"])
    bs = b.block_size
    mx = gen.max_sizes(mix)
    train = bool(mix.get("train"))
    tb = prog.train_batches[0] if train else None
    if train:
        keep = (jax.tree.map(jnp.copy, b.lora),
                jax.tree.map(jnp.copy, b.opt_state))
        budget, b.budget = b.budget, _Cycle()
    rid, ticks = 0, 0

    def run_idle():
        nonlocal ticks
        while not b.idle():
            b.step(train_batch=tb, now=0.0)
            ticks += 1

    def submit(p, n):
        nonlocal rid
        b.submit(_request(rid, p, n, rid % n_ten, vocab))
        rid += 1

    for w in range(1, b.n_slots + 1):
        for _ in range(w):
            submit(mx["prompt_max"], 2)
        run_idle()
    # the first decode write lands at position len(prompt): a request
    # of length p decodes first with p // bs + 1 blocks; four tokens
    # give three decode ticks in that block, one per train plan
    first_need = mx["prompt_min"] // bs + 1
    for need in range(first_need, mx["prompt_max"] // bs + 2):
        submit(min(max((need - 1) * bs, 1), mx["prompt_max"]), 4)
        run_idle()
    submit(mx["prompt_max"], mx["output_max"])
    run_idle()
    if train:
        b.step(train_batch=tb, now=0.0)     # an idle tick trains alone
        ticks += 1
        b.lora, b.opt_state = keep
        b.budget = budget
    return {"ticks": ticks, "requests": rid}


# ----------------------------------------------------------------- window --
@dataclasses.dataclass
class Rec:
    arrival: gen.Arrival
    req: Any = None
    submitted: Optional[float] = None
    admit_tick: Optional[float] = None   # start of the tick that prefilled
    first: Optional[float] = None
    last: Optional[float] = None
    n: int = 0
    error: Optional[str] = None


class TrainCheck:
    """Reads the first three train steps the batcher takes: each
    step's loss, the per-leaf norm of the first gradient as the
    optimizer got it (from Adam's first moment after one step) and
    the per-leaf change of the adapter after three."""

    def __init__(self, prog: Program, opt: Dict[str, Any]):
        self.prog = prog
        # build the two norm programs now, not inside the window
        leaf_norms(prog.lora0)
        leaf_diff_norms(prog.lora0, prog.lora0)
        self.b1 = float(opt["b1"])
        self.batches: List[Dict[str, Any]] = []
        self.losses: List[float] = []
        self.first_grad: Optional[Dict[str, float]] = None
        self.change: Optional[Dict[str, float]] = None

    def done(self) -> bool:
        return self.change is not None

    def after_tick(self, batch_idx: int) -> None:
        b = self.prog.batcher
        if self.done() or not b.last_tick_trained:
            return
        rows = int(b.last_tick_train_rows)
        self.batches.append({"index": batch_idx, "rows": rows})
        self.losses.append(float(b.train_losses[-1]))
        if len(self.batches) == 1:
            self.first_grad = {k: v / (1.0 - self.b1) for k, v in
                               leaf_norms(b.opt_state.m).items()}
        if len(self.batches) == 3:
            self.change = leaf_diff_norms(b.lora, self.prog.lora0)


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree.map(lambda x, y: x - y, a, b))


def _named(tree, values) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.tree.leaves(jax.device_get(values))
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norms, keyed by the leaf's path."""
    return _named(tree, _norms(tree))


def leaf_diff_norms(a, b) -> Dict[str, float]:
    return _named(a, _diff_norms(a, b))


def run_window(prog: Program, mix: Dict[str, Any], arrivals, seconds: float,
               clock, t_open: float, trace_dir: Optional[str],
               counter: CompileCounter) -> Dict[str, Any]:
    """Drive the arrivals open loop against the batcher.  ``t_open`` is
    the clock reading at which the window opens (arrivals are due at
    ``t_open + due``).  Returns the per-request records, per-tick
    records and window facts."""
    from repro.runtime.serving_loop import AdapterError, GenRequest
    b = prog.batcher
    train = mix.get("train")
    opt = train.get("optimizer") if train else None
    check = TrainCheck(prog, opt) if train else None
    recs = [Rec(a) for a in arrivals]
    nxt = 0
    inflight: List[Rec] = []
    ticks: List[tuple] = []
    drain_s = float(mix["drain_s"])
    window_recs = [r for r in recs if r.arrival.segment == "window"]
    compiles_at_open = None
    trace_lo = seconds / 3.0
    trace_hi = trace_lo + min(float(mix.get("trace_s", 2.0)), seconds / 3)
    tracing_on = False
    tick_i = 0
    batch_i = 0
    gen_prev = b.stats.generated_tokens

    def pending_window() -> bool:
        return any(r.last is None and r.error is None for r in window_recs)

    if check is not None:
        # the first checked train step: an idle tick trains alone
        b.step(train_batch=prog.train_batches[0], now=clock() - t_open)
        check.after_tick(0)
        batch_i = 1
    while True:
        now = clock() - t_open
        if compiles_at_open is None and now >= 0:
            compiles_at_open = counter.programs()
        if trace_dir and not tracing_on and trace_lo <= now < trace_hi:
            tracing.start(trace_dir)
            tracing_on = True
        elif tracing_on and now >= trace_hi:
            tracing.stop()
            tracing_on = False
            trace_dir = None
        while nxt < len(recs) and recs[nxt].arrival.due <= now:
            r = recs[nxt]
            a = r.arrival
            r.req = GenRequest(request_id=a.rid, prompt=a.prompt,
                               max_new_tokens=a.max_new,
                               adapter_id=f"tenant{a.tenant}",
                               arrival=a.due)
            r.submitted = now
            try:
                b.submit(r.req)
                inflight.append(r)
            except (AdapterError, AssertionError, ValueError) as e:
                # refused at submission: the request counts as failed
                r.error = repr(e)
                log(f"request {a.rid} refused: {r.error}")
            nxt += 1
        if now >= seconds and (not pending_window() or now >= seconds
                               + drain_s):
            break
        if not inflight and check is None:
            # serve-only and idle: wait for the next arrival
            if nxt < len(recs):
                wait = recs[nxt].arrival.due - (clock() - t_open)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
                continue
            if now >= seconds:
                break
            time.sleep(0.001)
            continue
        before = [(r, len(r.req.tokens)) for r in inflight]
        tb = None
        if check is not None:
            tb = prog.train_batches[batch_i % len(prog.train_batches)]
        t0 = clock() - t_open
        with jax.profiler.TraceAnnotation(tracing.TICK, tick=tick_i):
            b.step(train_batch=tb, now=t0)
        t1 = clock() - t_open
        if check is not None:
            check.after_tick(batch_i % len(prog.train_batches))
            batch_i += 1
        ctx, plens = [], []
        still = []
        for r, n0 in before:
            n1 = len(r.req.tokens)
            admitted = n0 == 0 and n1 >= 1
            if admitted:
                r.admit_tick = t0
                r.first = t1
                plens.append(len(r.arrival.prompt))
            if n1 - n0 - (1 if admitted else 0) >= 1:
                ctx.append(len(r.arrival.prompt) + n0
                           + (1 if admitted else 0))
            r.n = n1
            if r.req.done:
                r.last = t1
            else:
                still.append(r)
        inflight = still
        gen_now = b.stats.generated_tokens
        ticks.append((t0, t1, bool(b.last_tick_trained),
                      int(b.last_tick_train_rows), ctx, plens,
                      gen_now - gen_prev, tick_i))
        gen_prev = gen_now
        tick_i += 1
    if tracing_on:
        tracing.stop()
    end = clock() - t_open
    late = [r.submitted - r.arrival.due for r in recs
            if r.submitted is not None and r.arrival.segment == "window"]
    if check is not None:
        check.prog = None    # the run's state is freed before checking
    return {"recs": recs, "ticks": ticks, "drain_end": end,
            "compiles_in_window": counter.programs()
            - (compiles_at_open or counter.programs()),
            "lateness": late, "train_check": check,
            "backlog_end": len(b.queue)}


def window_metrics(res: Dict[str, Any], seconds: float,
                   mix: Dict[str, Any]) -> Dict[str, float]:
    """The end-to-end metrics other than ``setup_s``."""
    recs = [r for r in res["recs"] if r.arrival.segment == "window"]
    rows = [{"due": r.arrival.due, "first": r.first, "last": r.last,
             "n": r.n} for r in recs]
    lat = stats.latencies(rows, res["drain_end"])
    in_win = [t for t in res["ticks"] if 0.0 <= t[1] <= seconds]
    out = {"ttft_p95_ms": 1e3 * stats.percentile(lat["ttft"], 95),
           "tpot_p95_ms": 1e3 * stats.percentile(lat["tpot"], 95),
           "output_tok_s": stats.rate(sum(t[6] for t in in_win), seconds)}
    train = mix.get("train")
    if train:
        seq = int(train["seq"])
        out["train_tok_s"] = stats.rate(
            sum(t[3] * seq for t in in_win if t[2]), seconds)
    return out


# ----------------------------------------------------------------- check --
def sample_requests(recs: List[Rec], seed: int, min_tokens: int,
                    max_requests: int) -> List[Rec]:
    """The finished window requests to compare: the longest, then
    others drawn from the seed until ``min_tokens`` served tokens."""
    done = [r for r in recs if r.arrival.segment == "window"
            and r.last is not None]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.arrival.prompt) + r.n, r.arrival.rid))
    pick = [done[-1]]
    rng = np.random.default_rng([seed, 7])
    order = rng.permutation(len(done) - 1)
    for i in order:
        if sum(r.n for r in pick) >= min_tokens \
                or len(pick) >= max_requests:
            break
        pick.append(done[int(i)])
    return pick


def served_check(cfg: Dict[str, Any], seed: int, picks: List[Rec],
                 pad_to: int, control: bool) -> Dict[str, Any]:
    """Widest gap of a served token's reference logit below the
    reference's best, over the sample (and the control's, when asked)."""
    fam = spec.family(cfg)
    params = fam.make_params(cfg, seed)
    adapters = {}
    gap, ctrl, n = 0.0, 0.0, 0
    for r in picks:
        t = r.arrival.tenant
        if t not in adapters:
            adapters[t] = fam.make_adapter(cfg, seed, t)
        g, c = fam.served_gaps(
            params, adapters[t], r.arrival.prompt,
            np.asarray(r.req.tokens, np.int32), pad_to, cfg,
            "fp8" if control else None)
        gap = max(gap, float(g.max()))
        ctrl = max(ctrl, float(c.max())) if control else 0.0
        n += len(g)
    out = {"served_gap": gap, "tokens": n, "requests": len(picks)}
    if control:
        out["control_gap"] = ctrl
    return out


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[List[str]] = None) -> float:
    """Worst leaf's |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's."""
    keys = keep if keep is not None else list(ref)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def train_check(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                check: TrainCheck, faults: bool) -> Dict[str, Any]:
    """Follow the checked steps with the reference and compare: each
    step's loss, the first gradient per leaf, the change per leaf.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone under Adam: they are left out of the
    change.  With ``faults``, also read the control (fp8) and a batch
    with half its rows left out in the reference's place."""
    train = mix["train"]
    opt = train["optimizer"]
    fam = spec.family(cfg)
    pool = gen.train_pool(mix, seed, fam.dims(cfg)["V"],
                          int(train["pool"]))
    batches = [{k: jnp.asarray(v[s["index"]][:s["rows"]])
                for k, v in pool.items()} for s in check.batches]
    params = fam.make_params(cfg, seed)
    lora0 = fam.make_adapter(cfg, seed, 0)

    def read(losses, grad, adapter):
        gn = leaf_norms(grad)
        ch = leaf_diff_norms(adapter, lora0)
        return losses, gn, ch

    ref_l, ref_g, ref_c = read(*fam.train_steps(
        params, lora0, batches, cfg, opt))
    med_g = float(np.median(list(ref_g.values())))
    moving = [k for k, v in ref_g.items() if v >= 1e-3 * med_g]

    def gaps(losses, gn, ch):
        return {"loss_gap": max(abs(a - r) / abs(r)
                                for a, r in zip(losses, ref_l)),
                "grad_gap": _leaf_gap(gn, ref_g),
                "change_gap": _leaf_gap(ch, ref_c, moving)}

    out = gaps(check.losses, check.first_grad, check.change)
    out["left_out_leaves"] = len(ref_g) - len(moving)
    if faults:
        ctl = read(*fam.train_steps(params, lora0, batches, cfg, opt,
                                    quant="fp8"))
        out["control"] = gaps(*ctl)
        half = [{k: v[:max(1, v.shape[0] // 2)] for k, v in bt.items()}
                for bt in batches]
        out["half_batch"] = gaps(*read(*fam.train_steps(
            params, lora0, half, cfg, opt)))
    return out


# ------------------------------------------------------------------- run --
def run_once(cell, seed: int, seconds: float, trace: bool, clock,
             t_start: float, counter: CompileCounter,
             engine=None, control: bool = False) -> Dict[str, Any]:
    """Set up, measure and check one run; returns everything the result
    line and the per-layer readers need."""
    cfg, mix = cell.config, cell.traffic
    fam = spec.family(cfg)
    vocab = fam.dims(cfg)["V"]
    t_build = clock()
    prog = build_program(cfg, mix, seed, engine)
    t_warm = clock()
    c0 = (counter.compiles, counter.loads, counter.compile_s)
    warm_info = warm(prog, mix, vocab)
    warm_info.update(
        build_s=t_warm - t_build, warm_s=clock() - t_warm,
        compiles=counter.compiles - c0[0], cache_loads=counter.loads - c0[1],
        compile_s=counter.compile_s - c0[2])
    warm_programs = warm_info["compiles"] + warm_info["cache_loads"]
    arrivals = gen.generate(mix, seed, seconds, vocab)
    t_open = clock() + float(mix["ramp_s"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    res = run_window(prog, mix, arrivals, seconds, clock, t_open,
                     trace_dir, counter)
    res["setup_s"] = t_open - t_start
    res["slo"] = mix["slo"]
    res["warm"] = warm_info
    res["warm_programs"] = warm_programs
    res["metrics"] = window_metrics(res, seconds, mix)
    res["slots"] = prog.batcher.n_slots
    res["model"] = {"family": cfg["family"],
                    "base_params": fam.param_count(cfg),
                    "adapter_targets": sorted(fam.lora_targets(cfg))}
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    res["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    res["trace"] = None
    if trace_dir:
        res["trace"] = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    check = res["train_check"]
    pad_to = prog.max_seq
    # free the program before the reference runs
    del prog
    gc.collect()
    picks = sample_requests(res["recs"], seed,
                            int(mix["check"]["min_tokens"]),
                            int(mix["check"]["max_requests"]))
    t_ref = time.perf_counter()
    res["served"] = served_check(cfg, seed, picks, pad_to, control)
    if check is not None:
        if not check.done():
            res["train"] = {"error": f"{len(check.batches)} train steps "
                            "before the run ended, want 3"}
        else:
            res["train"] = train_check(cfg, mix, seed, check, control)
    res["reference_s"] = time.perf_counter() - t_ref
    return res


def checks(res: Dict[str, Any], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit."""
    out = {"served_gap": {"value": res["served"]["served_gap"],
                          "limit": limits["served_gap"]}}
    tr = res.get("train")
    if tr is not None:
        for k in ("loss_gap", "grad_gap", "change_gap"):
            out[k] = {"value": tr.get(k), "limit": limits[k]}
    return out


def is_correct(res: Dict[str, Any], chk: Dict[str, Dict[str, float]]
               ) -> bool:
    if res["served"]["requests"] == 0:
        return False
    if res.get("train", {}).get("error"):
        return False
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in chk.values())


def control_verdicts(res: Dict[str, Any], limits: Dict[str, float]
                     ) -> Dict[str, bool]:
    """``correct`` as the run would report it with each control or
    fault read in the program's place: the fp8 control's served tokens,
    and the train leg's fp8 control and half-batch fault.  Each should
    come out False."""
    out = {}
    sv = res["served"]
    if "control_gap" in sv:
        r = dict(res, served=dict(sv, served_gap=sv["control_gap"]))
        out["control.served"] = is_correct(r, checks(r, limits))
    tr = res.get("train") or {}
    for name in ("control", "half_batch"):
        if name in tr:
            r = dict(res, train=dict(tr, **tr[name]))
            out[f"{name}.train"] = is_correct(r, checks(r, limits))
    return out
