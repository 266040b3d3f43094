"""Chip smoke test: serve and co-train qwen1.5-0.5b at its published
widths (24 layers, d_model 1024, vocab 151,936, bf16) on a TPU, through
the same entry points a user calls (``repro.launch.serve``), with random
weights made from a seed.

  phase 0  the device: a TPU must be present, ``REPRO_DECODE_BACKEND``
           unset, and decode attention must resolve to the Pallas kernel
  phase A  one continuous batcher: paged KV pool, two LoRA tenants,
           fused LoRA co-training on every decode tick; the first
           request is checked against a single-sequence prefill+decode
           oracle run on the same chip
  phase B  the co-execution fabric on one chip: two replicas behind the
           dispatcher while one federated round trains their adapters

``--four-chips`` runs only the multi-replica path: phase B's trace
served by four replicas, replica i on chip i, and the same trace on one
replica on chip 0, compared token for token.

Every phase failure raises, so the script exits non-zero.  The seconds
and bytes printed are smoke readings, not benchmark numbers.  The last
line of stdout is one JSON object naming the device.

Usage:  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.engine import make_engine  # noqa: E402
from repro.data.synthetic import SyntheticDataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    run_combined_fabric_serving, run_multi_replica_serving, run_serving,
)
from repro.models.layers import resolve_decode_backend  # noqa: E402
from repro.runtime.fabric import make_tenant_adapters  # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = 128
GEN_TOKENS = 32
N_ADAPTERS = 2
BLOCK_SIZE = 16

# XLA backend compiles never nest, unlike the tracing and lowering
# events (a jit traced inside another jit's trace reports both)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += secs


def _phase(name: str, fn):
    """Run one phase; print its wall and XLA-compile seconds and the
    device's peak bytes so far (smoke readings)."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] smoke reading: {wall:.1f} s wall, of which {comp:.1f}"
          f" s XLA compile; chip 0 peak "
          f"{stats.get('peak_bytes_in_use', 'n/a')} bytes", flush=True)
    return out


def check_device() -> jax.Device:
    devs = jax.devices()
    d0 = devs[0]
    print(f"[phase 0] platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found platform "
                         f"{d0.platform!r}")
    if "REPRO_DECODE_BACKEND" in os.environ:
        raise SystemExit("chip_smoke: REPRO_DECODE_BACKEND is set; the "
                         "smoke must run the backend the chip resolves")
    backend = resolve_decode_backend(None)
    if backend != "pallas":
        raise SystemExit(f"chip_smoke: decode backend resolved to "
                         f"{backend!r}, not 'pallas'")
    return d0


def prompts(vocab: int) -> np.ndarray:
    """The trace's prompts, exactly as ``run_serving`` draws them."""
    data = SyntheticDataset("alpaca", vocab_size=vocab,
                            seq_len=PROMPT_LEN, seed=SEED)
    return data.sample_tokens(N_REQUESTS)[:, :PROMPT_LEN]


def assert_decode_uses_pallas(model, pool_blocks: int) -> None:
    """Lower the paged decode program the batcher ran (multi-tenant
    rows, backend resolved on this chip) and look for the kernel."""
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    params = jax.eval_shape(model.init, jax.random.key(SEED))
    stacked = jax.tree.map(
        lambda s: sds((s.shape[0], N_ADAPTERS) + s.shape[1:], s.dtype),
        model.lora_specs())
    caches = jax.eval_shape(
        lambda: model.init_paged_caches(pool_blocks, BLOCK_SIZE))
    max_seq = PROMPT_LEN + GEN_TOKENS
    width = -(-max_seq // BLOCK_SIZE)
    text = jax.jit(
        model.decode_step_paged,
        static_argnames=("ring_len", "attn_backend")).lower(
        params, stacked, caches, sds((N_REQUESTS, 1), i32),
        sds((N_REQUESTS,), i32), sds((N_REQUESTS, width), i32),
        ring_len=max_seq, adapter_idx=sds((N_REQUESTS,), i32)).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("paged decode program holds no "
                             "tpu_custom_call: the Pallas kernel is "
                             "not on the served path")
    print("[phase A] paged decode program contains tpu_custom_call",
          flush=True)


def oracle_agreement(model, served: list, prompt: np.ndarray) -> None:
    """Teacher-forced single-sequence prefill + decode (contiguous
    cache) over the served stream of request 0: at every position,
    does the oracle's greedy choice equal the served token?  Non-finite
    logits fail; a disagreement is reported with its position and the
    logit gap between the oracle's choice and the served token."""
    params = model.init(jax.random.key(SEED))
    lora = make_tenant_adapters(model, N_ADAPTERS, seed=SEED + 1)[0]
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    logits, pre = prefill(params, lora, {"tokens": jnp.asarray(prompt[None])})
    pool = model.write_prefill_slot(
        model.init_caches(1, PROMPT_LEN + GEN_TOKENS), pre, 0)
    agree, first_div, worst_gap = 0, None, 0.0
    for i, tok in enumerate(served):
        row = np.asarray(logits[0, -1], np.float32)
        if not np.isfinite(row).all():
            raise AssertionError(f"oracle logits non-finite at position {i}")
        top = int(row.argmax())
        if top == tok:
            agree += 1
        else:
            worst_gap = max(worst_gap, float(row[top] - row[tok]))
            if first_div is None:
                first_div = i
        if i + 1 < len(served):
            logits, pool = decode(params, lora, pool,
                                  jnp.asarray([[tok]], jnp.int32),
                                  jnp.asarray([PROMPT_LEN + i], jnp.int32))
    print(f"[phase A] correctness: {agree}/{len(served)} served tokens of "
          f"request 0 equal the single-sequence oracle's greedy choice"
          + ("" if first_div is None else
             f"; first divergence at position {first_div}, largest logit "
             f"gap {worst_gap:.4f}"), flush=True)


def phase_a(cfg) -> None:
    out = run_serving(
        ARCH, smoke=False, n_requests=N_REQUESTS, prompt_len=PROMPT_LEN,
        gen_tokens=GEN_TOKENS, batch_size=N_REQUESTS, combined=True,
        paged=True, block_size=BLOCK_SIZE, n_adapters=N_ADAPTERS,
        seed=SEED)
    if out["completed"] != N_REQUESTS:
        raise AssertionError(f"phase A finished {out['completed']}/"
                             f"{N_REQUESTS} requests")
    if out["tokens_generated"] != N_REQUESTS * GEN_TOKENS:
        raise AssertionError(f"phase A generated {out['tokens_generated']}"
                             f" tokens, want {N_REQUESTS * GEN_TOKENS}")
    losses = out["train_losses"]
    if not losses or not np.isfinite(losses).all():
        raise AssertionError(f"phase A train losses not finite: {losses}")
    toks = np.asarray(out["outputs"])
    if toks.min() < 0 or toks.max() >= cfg.vocab_size:
        raise AssertionError("phase A produced a token outside the vocab")
    print(f"[phase A] {out['completed']} requests, "
          f"{out['tokens_generated']} tokens in {out['decode_steps']} "
          f"decode steps, {len(losses)} fused train steps (loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}), pool "
          f"{out['pool_blocks']} blocks (peak {out['peak_used_blocks']} "
          f"used)", flush=True)
    model = make_engine(cfg).model
    assert_decode_uses_pallas(model, out["pool_blocks"])
    oracle_agreement(model, out["outputs"][0], prompts(cfg.vocab_size)[0])


def _assert_clean_fabric(out: dict, label: str) -> None:
    ft = out["fault_tolerance"]
    if out["completed"] != N_REQUESTS:
        raise AssertionError(f"{label}: {out['completed']}/{N_REQUESTS} "
                             "requests completed")
    bad = {"failovers": ft["failovers"], "quarantines": ft["quarantines"],
           "failed_requests": out["failed_requests"]}
    if any(bad.values()):
        raise AssertionError(f"{label}: fabric was not clean: {bad}; "
                             f"fault log {ft['log']}")


FABRIC_TRACE = dict(smoke=False, n_requests=N_REQUESTS,
                    prompt_len=PROMPT_LEN, gen_tokens=GEN_TOKENS,
                    batch_size=4, paged=True, block_size=BLOCK_SIZE,
                    n_adapters=N_ADAPTERS, seed=SEED, verbose=False)


def phase_b() -> None:
    out = run_combined_fabric_serving(
        ARCH, n_replicas=2, rounds=1, steps_per_round=2, timeout=600.0,
        **FABRIC_TRACE)
    _assert_clean_fabric(out, "phase B")
    if out["fl_rounds"] < 1:
        raise AssertionError("phase B completed no federated round")
    c = out["cluster"]
    print(f"[phase B] {out['completed']} requests on 2 replicas, "
          f"{c['generated_tokens']} tokens, {out['fl_rounds']} FL round(s), "
          f"{c['train_steps']} fused train steps; 0 failovers, "
          "0 quarantines, 0 failed requests", flush=True)


def four_chips() -> None:
    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"chip_smoke --four-chips: {len(devs)} device(s)")
    one = _phase("one replica", lambda: run_multi_replica_serving(
        ARCH, n_replicas=1, **FABRIC_TRACE))
    four = _phase("four replicas", lambda: run_multi_replica_serving(
        ARCH, n_replicas=4, **FABRIC_TRACE))
    _assert_clean_fabric(one, "one replica")
    _assert_clean_fabric(four, "four replicas")
    if one["devices"] != {"r0": [devs[0].id]}:
        raise AssertionError(f"one-replica placement: {one['devices']}")
    want = {f"r{i}": [devs[i].id] for i in range(4)}
    if four["devices"] != want:
        raise AssertionError(f"four-replica placement {four['devices']}, "
                             f"want {want}")
    print(f"[four chips] placement (replica: device ids holding params, "
          f"adapters, optimizer state, KV pool): {four['devices']}",
          flush=True)
    diverged = {i: next((p for p, (x, y) in enumerate(zip(a, b)) if x != y),
                        min(len(a), len(b)))
                for i, (a, b) in enumerate(zip(one["outputs"],
                                               four["outputs"])) if a != b}
    print(f"[four chips] greedy tokens: {N_REQUESTS - len(diverged)}/"
          f"{N_REQUESTS} requests equal to the one-replica run"
          + (f"; first divergent position by request: {diverged}"
             if diverged else ""), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the fabric trace over four replicas, one "
                         "per chip, against one replica on chip 0")
    args = ap.parse_args()
    d0 = check_device()
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.four_chips:
        four_chips()
    else:
        cfg = get_config(ARCH)
        _phase("phase A", lambda: phase_a(cfg))
        _phase("phase B", phase_b)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
