"""Serving driver (deliverable b): continuous-batching decode runtime —
prompts run through real ``model.prefill`` (one XLA program, no
per-token warm fill), finished sequences are evicted and new requests
admitted mid-flight, and ``--combined`` co-runs LoRA fine-tuning via the
fused ``combined_step`` on every decode tick — the paper's
model-sharing mechanism live.

``--replicas N`` (N > 1) serves the same trace through the
multi-replica fabric instead: one ``ClusterController`` routes
dispatcher subflows across N ``ContinuousBatcher``-backed live
replicas with placement-aware admission (pool headroom + prefix-cache
affinity) and per-replica admission queues; the summary aggregates
per-replica and cluster-total ``ServeStats``.

``--combined --replicas N`` is the paper's headline co-execution live:
the launcher cohorts the replicas into an FL PEFT session over the
SAME fabric — each replica advances an incremental train session one
fused ``combined_step`` per fabric tick (training its SHADOW adapter
while decode reads the published snapshot), the coordinator replans
per-replica train/infer splits between rounds, and aggregation
publishes the merged adapter to every member at round boundaries only.
``--rounds`` sets how many FL rounds to drive, ``--steps-per-round``
their length; within a round, greedy serving output is bit-identical
to serve-only.

Sampling: ``--temperature`` (> 0 enables stochastic decoding; 0 =
greedy, the default), filtered by ``--top-k`` / ``--top-p``, seeded
per request from ``--seed`` so runs are reproducible.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 16 --prompt-len 32 --gen 16
  ... --full         # the architecture's published widths (bf16)
                     # instead of the 2-layer smoke reduction
  ... --combined     # fine-tune while serving (one XLA program)
  ... --paged --block-size 16 --n-blocks 64   # paged KV cache (block
                     # tables; memory scales with live tokens)
  ... --paged --prefix-cache   # share identical prompt prefixes
                     # copy-on-write over the paged pool
  ... --replicas 2   # dispatcher-routed pool of live replicas
  ... --replicas 2 --combined --rounds 2   # FL fine-tuning co-executed
                     # over the live fabric (shadow-adapter publishing)
  ... --adapters 3   # multi-LoRA multi-tenant serving: requests tagged
                     # round-robin across 3 registered tenants, decoded
                     # through the batched segmented LoRA paths
  ... --chunked-prefill 16 --tpot-target 0.004   # token-level
                     # co-scheduling: prompts prefill in 16-token chunks
                     # riding the decode wave, each tick budgeted to the
                     # decode TPOT SLO (leftover slack admits train work)
  ... --paged --n-blocks 48 --oversubscribe 0.9   # oversubscribed KV
                     # pool: reserve near-term need only, preempt on
                     # exhaustion (host swap or drop + re-prefill),
                     # greedy output bit-identical to never-preempted
  ... --temperature 0.8 --top-k 40 --top-p 0.95   # sampled decoding
  ... --replicas 2 --chaos --chaos-crashes 1 --chaos-stalls 1
                     # seeded fault injection against the fabric:
                     # crashes/stalls/OOMs/NaN-rounds on a deterministic
                     # schedule; the run prints failover + retry telemetry
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.core.engine import make_engine
from repro.data.synthetic import SyntheticDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.serving_loop import ContinuousBatcher, GenRequest


def _make_injector(n_replicas: int, chaos: dict):
    """Build a seeded FaultInjector over the fabric's replica ids from
    the --chaos-* knobs."""
    from repro.runtime.fault import FaultInjector
    plan = FaultInjector.random_plan(
        [f"r{i}" for i in range(n_replicas)],
        seed=chaos.get("seed", 0),
        horizon=chaos.get("horizon", 5.0),
        n_crashes=chaos.get("crashes", 1),
        n_stalls=chaos.get("stalls", 1),
        n_ooms=chaos.get("ooms", 0),
        n_nan_rounds=chaos.get("nan_rounds", 0))
    return FaultInjector(plan)


def _print_fault_telemetry(out: dict) -> None:
    ft = out.get("fault_tolerance")
    if not ft:
        return
    print(f"  chaos: {len(ft['injected'])} faults injected, "
          f"{ft['failovers']} failovers, {ft['quarantines']} quarantines, "
          f"{ft['retried_requests']} retries, "
          f"{ft['rejected_requests']} rejected, "
          f"{ft['nan_publishes_blocked']} NaN publishes blocked; "
          f"{out.get('failed_requests', 0)} requests failed")


def run_serving(arch: str, *, smoke: bool = True, n_requests: int = 16,
                prompt_len: int = 32, gen_tokens: int = 16,
                batch_size: int = 8, combined: bool = False,
                train_batch: int = 4, seed: int = 0,
                paged: bool = False, block_size: int = 16,
                n_blocks: int = 0, prefix_cache: bool = False,
                temperature: float = 0.0, top_k: int = 0,
                top_p: float = 1.0, n_adapters: int = 0,
                prefill_chunk: int = 0, tpot_target: float = 0.0,
                oversubscribe: float = 0.0, swap: bool = True,
                verbose: bool = True) -> dict:
    """Serve ``n_requests`` prompts on a ``batch_size``-slot continuous
    batcher; returns throughput + (combined mode) train losses.

    ``n_adapters > 0`` registers that many tenants on an
    ``AdapterRegistry`` and assigns requests round-robin: one decode
    wave then mixes tenants through the batched segmented LoRA paths.
    In combined mode training still steps the co-train tree in place,
    but decode reads the registry's published tenant copies — the
    single-batcher analogue of shadow buffering."""
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.scaled()
    assert cfg.has_decode, f"{arch} is encoder-only; no decode serving"
    engine = make_engine(cfg, lr=3e-3)
    model = engine.model
    params = model.init(jax.random.key(seed))
    registry = None
    if n_adapters > 0:
        from repro.runtime.fabric import make_tenant_adapters
        from repro.runtime.serving_loop import AdapterRegistry
        tenant_trees = make_tenant_adapters(model, n_adapters,
                                            seed=seed + 1)
        registry = AdapterRegistry(model, capacity=n_adapters)
        for t, tree in enumerate(tenant_trees):
            registry.register(f"tenant{t}", tree)
        lora = tenant_trees[0]
    else:
        lora = model.init_lora(jax.random.key(seed + 1))
    opt_state = engine.optimizer.init(lora)
    data = SyntheticDataset("alpaca", vocab_size=cfg.vocab_size,
                            seq_len=prompt_len, seed=seed)

    batcher = ContinuousBatcher(
        engine, params, lora, n_slots=batch_size,
        max_seq=prompt_len + gen_tokens, prompt_pad=prompt_len,
        opt_state=opt_state, paged=paged, block_size=block_size,
        n_blocks=n_blocks or None, prefix_cache=prefix_cache,
        adapters=registry, prefill_chunk=prefill_chunk,
        tpot_target=tpot_target, oversubscribe=oversubscribe,
        swap=swap)
    prompts = data.sample_tokens(n_requests)[:, :prompt_len]
    requests = [GenRequest(request_id=i, prompt=prompts[i],
                           max_new_tokens=gen_tokens,
                           adapter_id=f"tenant{i % n_adapters}"
                           if n_adapters > 0 else None,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed + i)
                for i in range(n_requests)]

    def train_fn():
        import jax.numpy as jnp
        return {k: jnp.asarray(v) for k, v in data.batch(train_batch).items()}

    stats = batcher.run(requests, train_data_fn=train_fn if combined
                        else None)
    # completion time since run start (all requests arrive at t=0, so
    # later admission waves legitimately include queueing time)
    per_req = [r.finished_at for r in requests
               if r.finished_at is not None]
    out = {
        "completed": stats.finished,
        "tokens_generated": stats.generated_tokens,
        "prefill_tokens": stats.prefill_tokens,
        "decode_steps": stats.decode_steps,
        "mean_completion_s": float(np.mean(per_req)) if per_req else 0.0,
        "throughput_tok_s": stats.throughput(),
        "train_losses": batcher.train_losses,
        "cache_bytes": batcher.cache_bytes(),
        "outputs": [list(r.tokens) for r in requests],
    }
    if paged:
        out["peak_used_blocks"] = batcher.allocator.peak_used
        out["pool_blocks"] = batcher.allocator.capacity
    if oversubscribe > 0:
        out["preemptions"] = stats.preemptions
        out["swap_out_blocks"] = stats.swap_out_blocks
        out["swap_in_blocks"] = stats.swap_in_blocks
        out["reprefill_tokens"] = stats.reprefill_tokens
    if prefix_cache:
        out["cached_prefix_tokens"] = stats.cached_prefix_tokens
        out["prefix_cache_hits"] = batcher.prefix_cache.hits
    if registry is not None:
        out["adapter_requests"] = dict(stats.adapter_requests)
        out["adapter_hits"] = registry.hits
        out["adapter_loads"] = registry.loads
        out["adapter_evictions"] = registry.evictions
    if verbose:
        print(f"served {stats.finished}/{n_requests} requests, "
              f"{stats.generated_tokens} tokens in {stats.decode_steps} "
              f"decode steps, {out['throughput_tok_s']:.1f} tok/s"
              + (f" (sampled, T={temperature:g})" if temperature > 0
                 else "")
              + (f"; {stats.cached_prefix_tokens} prompt tokens served "
                 "from the prefix cache" if prefix_cache else "")
              + (f"; co-trained {stats.train_steps} fused steps "
                 f"(loss {batcher.train_losses[0]:.3f} -> "
                 f"{batcher.train_losses[-1]:.3f})"
                 if batcher.train_losses else "")
              + (f"; {n_adapters} tenants "
                 f"{dict(sorted(stats.adapter_requests.items()))}"
                 if registry is not None else "")
              + (f"; {stats.preemptions} preemptions "
                 f"({stats.swap_out_blocks} blocks swapped, "
                 f"{stats.reprefill_tokens} tokens re-prefilled)"
                 if oversubscribe > 0 else ""))
    return out


def run_multi_replica_serving(
        arch: str, *, n_replicas: int = 2, smoke: bool = True,
        n_requests: int = 16, prompt_len: int = 32, gen_tokens: int = 16,
        batch_size: int = 4, seed: int = 0, paged: bool = False,
        block_size: int = 16, n_blocks: int = 0,
        prefix_cache: bool = False, temperature: float = 0.0,
        top_k: int = 0, top_p: float = 1.0, n_adapters: int = 0,
        prefill_chunk: int = 0, tpot_target: float = 0.0,
        oversubscribe: float = 0.0, swap: bool = True,
        chaos: dict = None, verbose: bool = True) -> dict:
    """Serve ``n_requests`` prompts through the dispatcher-routed
    multi-replica fabric; returns the aggregate cluster summary.
    ``n_adapters > 0`` registers that many LoRA tenants on every
    replica and tags requests round-robin, exercising adapter-affinity
    routing and the batched segmented decode paths.  ``chaos`` (a dict
    of seed/horizon/crashes/stalls/ooms/nan_rounds) arms a seeded
    ``FaultInjector`` against the pool."""
    from repro.core.interfaces import Request
    from repro.runtime.fabric import FabricConfig, build_fabric

    fcfg = FabricConfig(prefill_chunk=prefill_chunk,
                        tpot_target=tpot_target,
                        oversubscribe=oversubscribe, swap=swap)
    injector = _make_injector(n_replicas, chaos) if chaos else None
    fabric, cfg = build_fabric(
        arch, n_replicas, smoke=smoke, n_slots=batch_size,
        prompt_len=prompt_len, gen_tokens=gen_tokens, paged=paged,
        block_size=block_size, n_blocks=n_blocks or None,
        prefix_cache=prefix_cache, seed=seed, n_adapters=n_adapters,
        cfg=fcfg, injector=injector)
    data = SyntheticDataset("alpaca", vocab_size=cfg.vocab_size,
                            seq_len=prompt_len, seed=seed)
    prompts = data.sample_tokens(n_requests)[:, :prompt_len]
    stream = cfg.name
    requests = [Request(request_id=i, stream_id=stream, arrival=0.0,
                        deadline=1e9, tokens=gen_tokens,
                        prompt=prompts[i].astype(np.int32),
                        adapter_id=f"tenant{i % n_adapters}"
                        if n_adapters > 0 else None,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, seed=seed + i)
                for i in range(n_requests)]
    out = fabric.run(requests)
    out["completed"] = sum(1 for r in requests
                           if r.completed_at is not None)
    out["outputs"] = [list(r.output_tokens or []) for r in requests]
    if verbose:
        c = out["cluster"]
        print(f"fabric served {out['completed']}/{n_requests} requests "
              f"on {c['n_replicas']} replicas: "
              f"{c['generated_tokens']} tokens, "
              f"aggregate {c['throughput_sum_tok_s']:.1f} tok/s "
              f"({c['throughput_wall_tok_s']:.1f} on the shared device)")
        if n_adapters > 0 and c.get("adapters"):
            parts = ", ".join(f"{aid}: {a['requests']}"
                              for aid, a in c["adapters"].items())
            routed = sum(d["adapter_routed"]
                         for d in out["dispatchers"].values())
            print(f"  tenants ({routed} adapter-affinity routed): "
                  f"{parts}")
        for rid, row in out["replicas"].items():
            print(f"  {rid}: {row['finished']} finished, "
                  f"{row['generated_tokens']} tokens, "
                  f"{row['throughput_tok_s']:.1f} tok/s")
        if chaos:
            _print_fault_telemetry(out)
    return out


def run_combined_fabric_serving(
        arch: str, *, n_replicas: int = 2, smoke: bool = True,
        n_requests: int = 16, prompt_len: int = 32, gen_tokens: int = 16,
        batch_size: int = 4, seed: int = 0, paged: bool = False,
        block_size: int = 16, n_blocks: int = 0,
        prefix_cache: bool = False, train_batch: int = 4,
        rounds: int = 2, steps_per_round: int = 4, train_pool: int = 8,
        temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
        n_adapters: int = 0, timeout: float = 300.0,
        prefill_chunk: int = 0, tpot_target: float = 0.0,
        oversubscribe: float = 0.0, swap: bool = True,
        chaos: dict = None, verbose: bool = True) -> dict:
    """Live co-execution: serve the trace through the multi-replica
    fabric WHILE the launcher drives incremental FL train sessions over
    the same replicas.  ``train_pool`` fixes the fine-tuning corpus to
    that many batches cycled epoch-style (finite finetuning set; loss
    falls visibly across rounds), 0 streams fresh batches.  Returns the
    aggregate cluster summary plus the launcher's per-round
    loss/version history."""
    from repro.core.interfaces import Request
    from repro.runtime.fabric import FabricConfig, build_fabric

    fcfg = FabricConfig(
        enable_finetuning=True, train_batch=train_batch,
        bootstrap_steps=steps_per_round, steps_per_round=steps_per_round,
        min_cohort=min(2, n_replicas),
        prefill_chunk=prefill_chunk, tpot_target=tpot_target,
        oversubscribe=oversubscribe, swap=swap)
    injector = _make_injector(n_replicas, chaos) if chaos else None
    fabric, cfg = build_fabric(
        arch, n_replicas, smoke=smoke, n_slots=batch_size,
        prompt_len=prompt_len, gen_tokens=gen_tokens, paged=paged,
        block_size=block_size, n_blocks=n_blocks or None,
        prefix_cache=prefix_cache, seed=seed, train_pool=train_pool,
        n_adapters=n_adapters, cfg=fcfg, injector=injector)
    data = SyntheticDataset("alpaca", vocab_size=cfg.vocab_size,
                            seq_len=prompt_len, seed=seed)
    prompts = data.sample_tokens(n_requests)[:, :prompt_len]
    stream = cfg.name
    requests = [Request(request_id=i, stream_id=stream, arrival=0.0,
                        deadline=1e9, tokens=gen_tokens,
                        prompt=prompts[i].astype(np.int32),
                        adapter_id=f"tenant{i % n_adapters}"
                        if n_adapters > 0 else None,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, seed=seed + i)
                for i in range(n_requests)]
    out = fabric.run(requests, min_rounds=rounds, timeout=timeout)
    out["completed"] = sum(1 for r in requests
                           if r.completed_at is not None)
    out["outputs"] = [list(r.output_tokens or []) for r in requests]
    if verbose:
        c = out["cluster"]
        print(f"combined fabric served {out['completed']}/{n_requests} "
              f"requests on {c['n_replicas']} replicas while completing "
              f"{out['fl_rounds']} FL rounds: {c['generated_tokens']} "
              f"tokens, aggregate {c['throughput_sum_tok_s']:.1f} tok/s, "
              f"{c['train_steps']} fused train steps")
        for r in out["rounds"]:
            print(f"  round {r['round']}: avg member loss "
                  f"{r['avg_loss']:.4f} -> published v{r['version']} "
                  f"({r['members']} members)")
        if n_adapters > 0 and c.get("adapters"):
            for aid, a in c["adapters"].items():
                print(f"  {aid}: {a['requests']} requests, "
                      f"version {a['version_min']}..{a['version_max']}")
        for rid, row in out["replicas"].items():
            tl = row["train_loss"]
            print(f"  {rid}: v{row['adapter_version']}, "
                  f"{row['finished']} finished, "
                  f"{row['throughput_tok_s']:.1f} tok/s"
                  + (f", train CE {tl:.4f}" if tl is not None else ""))
        if chaos:
            _print_fault_telemetry(out)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="serve the architecture at its published widths "
                         "(default: the reduced smoke config)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=1,
                    help="live replicas; > 1 routes the trace through "
                         "the dispatcher-backed multi-replica fabric")
    ap.add_argument("--combined", action="store_true")
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged pool size (0 = full worst case)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt prefixes copy-on-write "
                         "over the paged pool (requires --paged)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="FL rounds to drive in --combined --replicas "
                         "mode (best effort, bounded by the timeout)")
    ap.add_argument("--steps-per-round", type=int, default=4,
                    help="fused train steps per FL round in --combined "
                         "--replicas mode")
    ap.add_argument("--train-batch", type=int, default=4,
                    help="co-running train batch (combined modes)")
    ap.add_argument("--chunked-prefill", type=int, default=0,
                    help="prefill chunk size in tokens (default 0 = "
                         "monolithic prefill); > 0 splits each prompt "
                         "into fixed-token chunks interleaved with "
                         "decode ticks (paged mode rounds the chunk up "
                         "to a block multiple); greedy output is "
                         "bit-identical to monolithic prefill")
    ap.add_argument("--tpot-target", type=float, default=0.0,
                    help="decode TPOT SLO target in seconds/token "
                         "(default 0 = no tick budget); > 0 budgets "
                         "each tick: decode first, then prefill chunks "
                         "in deadline-slack order, leftover slack "
                         "admits (possibly shrunk) train microbatches")
    ap.add_argument("--oversubscribe", type=float, default=0.0,
                    help="oversubscribed KV pool watermark in (0, 1] "
                         "(default 0 = preemption-free worst-case "
                         "reservations); > 0 reserves only near-term "
                         "need against that fraction of the pool and "
                         "preempts on exhaustion (victims swap to host "
                         "or drop + re-prefill); requires --paged")
    ap.add_argument("--no-swap", dest="swap", action="store_false",
                    help="disable host swap for preempted requests — "
                         "every victim drops its private KV and "
                         "re-prefills on restore (--oversubscribe only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = all)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = no filter)")
    ap.add_argument("--adapters", type=int, default=0,
                    help="LoRA tenants to register and round-robin "
                         "requests across (0 = single-adapter serving)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="arm seeded fault injection against the fabric "
                         "(requires --replicas > 1)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos schedule")
    ap.add_argument("--chaos-horizon", type=float, default=5.0,
                    help="fault schedule horizon in seconds")
    ap.add_argument("--chaos-crashes", type=int, default=1,
                    help="replica crashes to schedule")
    ap.add_argument("--chaos-stalls", type=int, default=1,
                    help="straggler stalls to schedule")
    ap.add_argument("--chaos-ooms", type=int, default=0,
                    help="admission OOMs to schedule")
    ap.add_argument("--chaos-nan-rounds", type=int, default=0,
                    help="NaN-poisoned train rounds to schedule "
                         "(combined mode)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (sharing rides on "
                 "pool block aliasing)")
    if args.oversubscribe and not args.paged:
        ap.error("--oversubscribe requires --paged (preemption swaps "
                 "pool blocks)")
    if args.chaos and args.replicas < 2:
        ap.error("--chaos requires --replicas > 1 (fault tolerance is "
                 "a property of the pool)")
    chaos = None
    if args.chaos:
        chaos = {"seed": args.chaos_seed, "horizon": args.chaos_horizon,
                 "crashes": args.chaos_crashes,
                 "stalls": args.chaos_stalls, "ooms": args.chaos_ooms,
                 "nan_rounds": args.chaos_nan_rounds}
    if args.replicas > 1:
        if args.combined:
            # the full co-execution path: launcher-driven incremental
            # train sessions over the live fabric
            run_combined_fabric_serving(
                args.arch, n_replicas=args.replicas, smoke=args.smoke,
                n_requests=args.requests, prompt_len=args.prompt_len,
                gen_tokens=args.gen, batch_size=args.batch,
                paged=args.paged, block_size=args.block_size,
                n_blocks=args.n_blocks, prefix_cache=args.prefix_cache,
                train_batch=args.train_batch, rounds=args.rounds,
                steps_per_round=args.steps_per_round,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, n_adapters=args.adapters,
                prefill_chunk=args.chunked_prefill,
                tpot_target=args.tpot_target,
                oversubscribe=args.oversubscribe, swap=args.swap,
                seed=args.seed, chaos=chaos)
            return
        run_multi_replica_serving(
            args.arch, n_replicas=args.replicas, smoke=args.smoke,
            n_requests=args.requests, prompt_len=args.prompt_len,
            gen_tokens=args.gen, batch_size=args.batch,
            paged=args.paged, block_size=args.block_size,
            n_blocks=args.n_blocks, prefix_cache=args.prefix_cache,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, n_adapters=args.adapters,
            prefill_chunk=args.chunked_prefill,
            tpot_target=args.tpot_target,
            oversubscribe=args.oversubscribe, swap=args.swap,
            seed=args.seed, chaos=chaos)
        return
    run_serving(args.arch, smoke=args.smoke, n_requests=args.requests,
                prompt_len=args.prompt_len, gen_tokens=args.gen,
                batch_size=args.batch, combined=args.combined,
                train_batch=args.train_batch,
                paged=args.paged, block_size=args.block_size,
                n_blocks=args.n_blocks, prefix_cache=args.prefix_cache,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, n_adapters=args.adapters,
                prefill_chunk=args.chunked_prefill,
                tpot_target=args.tpot_target,
                oversubscribe=args.oversubscribe, swap=args.swap,
                seed=args.seed)


if __name__ == "__main__":
    main()
