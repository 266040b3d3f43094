"""Slot-based continuous-batching decode runtime with a paged KV cache
(FlexLLM-style token-level co-serving over one shared base model).

A ``ContinuousBatcher`` owns a fixed pool of decode *slots* whose KV
lives in one of two cache layouts:

  contiguous  ``model.init_caches(n_slots, max_seq)`` — every slot owns
              a worst-case ``max_seq`` stripe (the pre-paging design,
              kept as the equivalence baseline);
  paged       ``paged=True``: a global block pool
              ``[L, n_blocks, block_size, Hkv, Dh]``
              (``model.init_paged_caches``) plus per-slot block tables.
              A ``BlockAllocator`` (runtime/paging.py) reserves each
              request's worst case at admission and hands out blocks
              lazily — prompt blocks at admission, one more whenever
              decode crosses a block boundary — so cache memory scales
              with live tokens, not ``n_slots * max_seq``, and admission
              is rejected (queue backpressure, preemption-free) when the
              pool can't cover a request's worst case.  With
              ``oversubscribe=w`` (0 < w <= 1) admission reserves only
              near-term need (prompt blocks + a one-block lookahead)
              against a ``w``-fraction watermark of the pool instead,
              and mid-decode pool exhaustion PREEMPTS a victim slot:
              its private block chain either swaps to host memory
              (batched device->host gather; restored by a batched
              scatter into fresh blocks) or is dropped and re-prefilled
              from host-kept token ids, whichever an EMA cost model
              prices cheaper.  COW-shared / prefix-registered blocks
              are never copied — they stay pool-resident (or revive via
              the ``PrefixCache``).  Restores run ahead of the decode
              wave in deadline-slack order and greedy output stays
              bit-identical to a never-preempted run.

Paged mode can additionally share prompt prefixes copy-on-write
(``prefix_cache=True``): full, immutable prompt blocks are registered
in a hash-indexed ``PrefixCache`` (runtime/paging.py); a request whose
prompt starts with a cached block chain aliases those pool blocks at
refcount+1, prefills ONLY the uncached suffix
(``model.prefill_ragged_suffix`` attends the suffix over prefix K/V
gathered straight from the pool), and copy-on-writes a private block
before any decode write would land in a shared one (sliding-window
ring wraps).  Evicted-but-cached blocks park in an LRU retained pool
and are reclaimed on allocator pressure, so warm prefixes survive
across requests; cache memory then scales with *distinct* live tokens.

The runtime tick is unchanged by the layout:

  admission   free slots take queued requests; the whole wave prefills
              through ONE ragged ``model.prefill_ragged`` program and
              lands in the cache with ONE batched scatter
              (``write_prefill_slots`` / ``write_prefill_blocks``) —
              no per-request write calls;
  decode      every step advances ALL active slots one token with
              per-slot positions (``decode_step`` / ``decode_step_paged``
              with ``pos [B]``); paged decode streams only the bucketed
              live block range, and ``attention_decode`` dispatches to
              the Pallas kernels (kernels/decode_attention.py) on TPU
              with the jnp path as interpreter/CPU fallback;
  eviction    a slot frees the moment its request hits max_new_tokens /
              EOS — its blocks return to the allocator and the next
              queued request is admitted mid-flight;
  co-serving  passing a training batch to ``step`` runs the fused
              ``engine.combined_step`` / ``combined_step_paged`` — LoRA
              finetuning + the decode tick in ONE program over shared
              base weights (the paper's model-sharing semantics, per
              token instead of per batch).  With a shadow staged
              (``train_lora``), the optimizer trains IT while decode
              reads the published ``lora`` snapshot — see the
              ContinuousBatcher docstring.

``static_batch_serve`` is the lock-step baseline (prefill a batch,
decode until every request in the batch finishes, dead slots riding
along) used by benchmarks/ and the equivalence tests.

Scope: non-VLM families; full-attention or cache-covering windows
(``sliding_window == 0 or >= max_seq``) on the contiguous path, plus
ring-over-blocks sliding windows on the paged path (the paged ring
wraps at ``min(max_seq, window)`` exactly like the contiguous ring, so
greedy outputs are identical).  Paged mode needs an attention-only
stack — SSM state is per-slot, not per-block.  Oversubscribed mode
additionally needs full attention (``sliding_window == 0``): a ring
wrap overwrites cache rows in place, so a dropped request could not be
re-prefilled into an equivalent state.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.interfaces import slack_order
from repro.runtime.paging import BlockAllocator, PrefixCache, blocks_for
from repro.runtime.sanitize import adapter_sanitizer, lifecycle_sanitizer

# Every host span of the serving tick opens through this one name: a
# profiler annotation (``collm.*``) that lands in the same trace as the
# device ops, on their clock, and costs about a microsecond with the
# profiler off.  Tests swap it for a recorder.
_span = jax.profiler.TraceAnnotation


def place(tree: Any, device: Optional[Any]) -> Any:
    """Commit every array of ``tree`` to ``device``; ``None`` leaves
    the tree where it is (JAX's default device, uncommitted).  A tree
    already on ``device`` is not copied."""
    return tree if device is None else jax.device_put(tree, device)


@functools.lru_cache(maxsize=16)
def _engine_jits(engine) -> Dict[str, Callable]:
    """One set of jitted step programs per (frozen, hashable) Engine —
    shared across every batcher / baseline run on that engine so fresh
    runtimes never retrace (donation is per-call, sharing is safe)."""
    model = engine.model
    return {
        "decode": jax.jit(model.decode_step, donate_argnums=(2,),
                          static_argnames=("attn_backend",)),
        "decode_paged": jax.jit(
            model.decode_step_paged, donate_argnums=(2,),
            static_argnames=("ring_len", "attn_backend")),
        "prefill_ragged": jax.jit(model.prefill_ragged),
        "prefill_exact": jax.jit(model.prefill),
        "write": jax.jit(model.write_prefill_slot, donate_argnums=(0,)),
        "write_slots": jax.jit(model.write_prefill_slots,
                               donate_argnums=(0,)),
        "write_blocks": jax.jit(model.write_prefill_blocks,
                                donate_argnums=(0,)),
        "prefill_suffix": jax.jit(model.prefill_ragged_suffix),
        "prefill_continue": jax.jit(model.prefill_ragged_continue),
        "write_rows": jax.jit(model.write_prefill_rows,
                              donate_argnums=(0,)),
        "copy_blocks": jax.jit(model.copy_blocks, donate_argnums=(0,)),
        "gather_blocks": jax.jit(model.gather_blocks),
        "scatter_blocks": jax.jit(model.scatter_blocks,
                                  donate_argnums=(0,)),
        "combined": jax.jit(
            engine.combined_step, donate_argnums=(2, 4),
            static_argnames=("attn_backend", "grad_accum",
                             "train_tokens")),
        "combined_paged": jax.jit(
            engine.combined_step_paged, donate_argnums=(2, 4),
            static_argnames=("ring_len", "attn_backend", "grad_accum",
                             "train_tokens")),
        "train": jax.jit(engine.train_step, donate_argnums=(2,),
                         static_argnames=("grad_accum", "train_tokens")),
        "loss": jax.jit(
            lambda p, l, b: engine.model.forward_loss(p, l, b)[0]),
    }


@dataclasses.dataclass
class GenRequest:
    """One generation request: prompt in, sampled tokens out (greedy by
    default — ``temperature <= 0``)."""
    request_id: int
    prompt: np.ndarray                  # [P] int32 token ids
    max_new_tokens: int = 16
    arrival: float = 0.0
    # SLO deadline (same clock as ``arrival``): the chunked-prefill
    # scheduler spends each tick's leftover budget in deadline-slack
    # order (core/interfaces.slack_order, shared with the dispatcher)
    deadline: float = float("inf")
    # multi-tenant serving: which registered adapter this request's
    # tokens flow through (None = the base model / single-adapter mode)
    adapter_id: Optional[str] = None
    # sampling: temperature <= 0 is exact greedy (the argmax fast path,
    # no host logits transfer); top_k/top_p filter before the softmax;
    # ``seed`` makes the sampled stream reproducible per request
    # (defaults to request_id so identical traces replay identically)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    # filled by the runtime
    tokens: List[int] = dataclasses.field(default_factory=list)
    # the caller's ``now`` of the tick whose host pull brought the
    # FIRST generated token back (``LiveReplica`` hands it to the
    # control plane's ``Request.first_token_at``)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # wall-clock (perf_counter) stamps where the work happens — the
    # ``*_at`` stamps carry whatever clock the caller's ``now`` uses,
    # which may be sim time or the start of the tick: ``submit()``,
    # the admission that gave it a slot, the host pull that brought
    # its first token back, and its finish
    submitted_wall: Optional[float] = None
    admitted_wall: Optional[float] = None
    first_token_wall: Optional[float] = None
    finished_wall: Optional[float] = None
    rng: Any = None                     # per-request sampling stream

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def samples(self) -> bool:
        return self.temperature > 0.0


def sample_token(logits: np.ndarray, *, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 rng: Optional[np.random.Generator] = None) -> int:
    """Sample one token id from a ``[V]`` logits row.

    ``temperature <= 0`` (or no rng) is exact greedy argmax.  Otherwise:
    scale by temperature, keep the ``top_k`` highest logits (0 = all),
    then the nucleus — the smallest probability mass >= ``top_p`` —
    and draw from the renormalized distribution.  float64 softmax so
    the host-side distribution is deterministic across platforms."""
    if temperature <= 0.0 or rng is None:
        return int(np.argmax(logits))
    row = np.asarray(logits, np.float64) / temperature
    if 0 < top_k < row.size:
        kth = np.partition(row, -top_k)[-top_k]
        row = np.where(row < kth, -np.inf, row)
    row -= row.max()
    probs = np.exp(row)
    probs /= probs.sum()
    if top_p < 1.0:
        order = np.argsort(-probs, kind="stable")
        csum = np.cumsum(probs[order])
        # smallest prefix whose mass reaches top_p (always >= 1 token)
        cut = int(np.searchsorted(csum, top_p)) + 1
        mask = np.zeros_like(probs, bool)
        mask[order[:cut]] = True
        probs = np.where(mask, probs, 0.0)
        probs /= probs.sum()
    return int(rng.choice(probs.size, p=probs))


@dataclasses.dataclass
class ServeStats:
    admitted: int = 0
    finished: int = 0
    # prompt tokens actually COMPUTED by a prefill program (with prefix
    # sharing on, cached prefixes are skipped and counted separately)
    prefill_tokens: int = 0
    cached_prefix_tokens: int = 0
    generated_tokens: int = 0
    decode_steps: int = 0
    train_steps: int = 0
    wall_time: float = 0.0
    # quality progression telemetry: the adapter version this replica
    # currently serves (bumped by set_adapter/publish_adapter) and the
    # latest train CE loss seen by its fused/plain train steps — NaN
    # until the replica has trained at all
    adapter_version: int = 0
    train_loss: float = float("nan")
    # publish-gate telemetry: rounds whose shadow (or incoming global)
    # tree was non-finite and therefore REJECTED instead of swapped
    # into serving (runtime/fault.py publish-gate contract)
    nan_publishes_blocked: int = 0
    # multi-tenant telemetry: per-adapter finished-request counts and
    # the version each tenant's adapter was serving at last touch (the
    # legacy scalar above tracks only the co-training tenant)
    adapter_requests: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    adapter_versions: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # token-budget scheduler telemetry (tpot_target > 0 only): ticks
    # planned under a budget, measured seconds of work spent vs the
    # summed per-tick target, and ticks whose train microbatch was
    # dropped outright to protect the decode TPOT SLO
    budget_ticks: int = 0
    budget_spent_s: float = 0.0
    budget_target_s: float = 0.0
    train_skipped_ticks: int = 0
    # oversubscribed-pool telemetry: victim slots preempted on pool
    # exhaustion, blocks moved device->host / host->device by the swap
    # paths, and prompt+generated tokens recomputed by drop-restore
    # re-prefills (those ALSO count in prefill_tokens — prefill_tokens
    # is total prefill compute, reprefill_tokens the restore subset)
    preemptions: int = 0
    swap_out_blocks: int = 0
    swap_in_blocks: int = 0
    reprefill_tokens: int = 0
    # per-finished-request latency samples (perf_counter stamps): time
    # to first token (submit -> first token on the host) and seconds
    # per subsequent output token — aggregate_serve_stats folds these
    # into p50/p99
    ttft: List[float] = dataclasses.field(default_factory=list)
    tpot: List[float] = dataclasses.field(default_factory=list)

    def throughput(self) -> float:
        return self.generated_tokens / max(self.wall_time, 1e-9)


class _TickBudget:
    """Per-tick token-budget planner for a decode-TPOT SLO target.

    Keeps EMA cost estimates of the three kinds of work a tick can
    carry — the decode wave, prefill-chunk tokens, train tokens — from
    measured wall times, then plans each tick FlexLLM-style: decode is
    first-class, leftover budget goes to prefill chunks (the caller
    picks rows in deadline-slack order), and whatever slack remains
    admits train tokens.  Unknown costs plan optimistically so each
    work type gets measured once before it is regulated."""

    def __init__(self, target_s: float):
        self.target_s = target_s
        self.decode_tick_s: Optional[float] = None
        self.prefill_tok_s: Optional[float] = None
        self.train_tok_s: Optional[float] = None

    @staticmethod
    def _ema(old: Optional[float], new: float) -> float:
        return new if old is None else 0.75 * old + 0.25 * new

    def observe_decode(self, dt: float) -> None:
        self.decode_tick_s = self._ema(self.decode_tick_s, dt)

    def observe_prefill(self, tokens: int, dt: float) -> None:
        if tokens > 0:
            self.prefill_tok_s = self._ema(self.prefill_tok_s,
                                           dt / tokens)

    def observe_train(self, tokens: int, dt: float) -> None:
        if tokens > 0 and dt > 0:
            self.train_tok_s = self._ema(self.train_tok_s, dt / tokens)

    def prefill_allowance(self, n_decoding: int) -> float:
        """Prefill tokens this tick may spend after decode's share.
        With no decoding slots prefill owns the whole tick — there is
        no TPOT to protect, only TTFT to win."""
        if n_decoding == 0:
            return float("inf")
        rem = self.target_s - (self.decode_tick_s or 0.0)
        if rem <= 0:
            return 0.0
        if self.prefill_tok_s is None:
            return float("inf")
        return rem / self.prefill_tok_s

    def train_tokens(self, b: int, s: int,
                     prefill_spent_s: float) -> Optional[int]:
        """Token cap for a [B, S] train microbatch in this tick's
        remaining slack: 0 = run the full batch, a positive cap shrinks
        it, None = skip training this tick.  Bucketed to {full, half,
        skip} so the fused program compiles at most twice."""
        rem = self.target_s - (self.decode_tick_s or 0.0) \
            - prefill_spent_s
        if self.train_tok_s is None:
            # unknown train cost: never stack an unmeasured train
            # program on a tick carrying serving work — one mispriced
            # probe can blow several ticks' budget.  Fully idle ticks
            # (no decode wave, no prefill) train unconditionally via
            # the caller, so the cost gets measured the moment serving
            # drains and later ticks can price half/full correctly.
            return None
        if rem >= b * s * self.train_tok_s:
            return 0
        half = (b // 2) * s
        if b >= 2 and rem >= half * self.train_tok_s:
            return half
        return None


class _SwapCost:
    """EMA cost model for the per-victim preemption choice, priced like
    ``_TickBudget``: measured seconds per byte of a device<->host block
    copy vs seconds per re-prefilled token.  Swap preserves state
    exactly, so unknown costs prefer swap — each path gets measured
    before it is regulated, and the safe choice is the default."""

    def __init__(self) -> None:
        self.swap_byte_s: Optional[float] = None
        self.prefill_tok_s: Optional[float] = None

    @staticmethod
    def _ema(old: Optional[float], new: float) -> float:
        return new if old is None else 0.75 * old + 0.25 * new

    def observe_swap(self, nbytes: int, dt: float) -> None:
        if nbytes > 0 and dt > 0:
            self.swap_byte_s = self._ema(self.swap_byte_s, dt / nbytes)

    def observe_prefill(self, tokens: int, dt: float) -> None:
        if tokens > 0 and dt > 0:
            self.prefill_tok_s = self._ema(self.prefill_tok_s,
                                           dt / tokens)

    def prefer_swap(self, tail_bytes: int, reprefill_tokens: int) -> bool:
        """Swap round trip (out + in) cheaper than recomputing the
        dropped rows?"""
        if self.swap_byte_s is None or self.prefill_tok_s is None:
            return True
        return 2.0 * tail_bytes * self.swap_byte_s \
            <= reprefill_tokens * self.prefill_tok_s


@dataclasses.dataclass
class _Swapped:
    """A preempted request parked off its slot.  ``kept`` blocks (the
    COW-shared / prefix-registered chain prefix) stay pool-resident
    with our reference held; the private tail either lives host-side in
    ``host_kv`` (mode "swap") or was dropped and will be recomputed
    from the request's host-kept token ids (mode "reprefill").  The
    pinned adapter reference is kept across the preemption so restore
    can never fail on adapter residency."""
    req: GenRequest
    adapter_id: Optional[str]
    mode: str                     # "swap" | "reprefill"
    kept: List[int]               # pool-resident chain prefix (refs held)
    host_kv: Any                  # (k, v) host arrays, swap mode only
    n_tail: int                   # private blocks to restore
    pos: int                      # decode frontier: next write position
    tok: int                      # next token to feed
    cached: int                   # prefix-cache hit tokens at admission


class AdapterError(RuntimeError):
    """Misuse of the AdapterRegistry (unknown id, double free, ...)."""


class OutOfAdapterSlots(AdapterError):
    """Every device slot is pinned by in-flight requests."""


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_adapter_slot(stack, tree, slot):
    """Overwrite device slot ``slot`` of a stacked multi-adapter tree
    (leaves [L, A, din, r]) with a single-adapter tree's leaves — one
    traced program for every slot index."""
    return jax.tree.map(
        lambda stk, leaf: stk.at[:, slot].set(leaf.astype(stk.dtype)),
        stack, tree)


class AdapterRegistry:
    """Per-replica multi-tenant adapter residency: every registered
    tenant keeps a HOST copy of its LoRA tree; up to ``capacity`` of
    them are DEVICE-resident in one stacked tree (leaves
    ``[L, capacity, din, r]``) that the decode wave indexes per row
    (``segmented`` paths in models/).

    Residency is refcounted like the paged pool's ``BlockAllocator``:
    ``acquire`` pins a tenant's slot for the lifetime of a request
    (loading it from host into a free slot on a miss), ``release``
    unpins it, and refcount-0 residents park in an LRU retained list —
    still servable at hit cost zero — until a miss needs their slot
    (cold-adapter eviction).  ``update`` rewrites a resident tenant's
    slot in place, which is what makes ``publish_adapter`` an atomic
    swap under co-training: in-flight rows keep reading the slot and
    simply see the new version on their next tick, exactly like the
    single-tenant pointer swap.

    Free/evicted slots are zero-filled at init and overwritten on load,
    so the stacked tensors stay finite — a requirement of the fused
    segmented kernel, whose concatenated B contraction touches every
    slot's columns (masked rows contribute exact zeros, not NaN)."""

    def __init__(self, model, capacity: int, device: Optional[Any] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        # the stacked slots live on the serving replica's device; host
        # trees registered from elsewhere (failover) move there on load
        self.device = device
        specs = model.lora_specs()
        with jax.default_device(device):
            self._stack = place(jax.tree.map(
                lambda s: jnp.zeros((s.shape[0], capacity) + s.shape[1:],
                                    s.dtype), specs), device)
        self._host: Dict[str, Any] = {}
        self._version: Dict[str, int] = {}
        self._slot: Dict[str, int] = {}        # resident tenants only
        self._refs: Dict[str, int] = {}        # resident tenants only
        self._free: List[int] = list(range(capacity))
        # refcount-0 residents, oldest first (the LRU retained pool)
        self._lru: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self.hits = 0
        self.loads = 0
        self.evictions = 0
        # shadow residency/refcount/version mirror, armed by
        # REPRO_SANITIZE=1 (None otherwise)
        self.san = adapter_sanitizer()

    # ---------------------------------------------------------- tenants --
    def register(self, adapter_id: str, tree: Any,
                 version: int = 0) -> None:
        """Add (or overwrite) a tenant's host-resident adapter tree."""
        if adapter_id in self._slot:
            raise AdapterError(
                f"{adapter_id}: already registered and resident — use "
                "update() to change a live tenant's weights")
        self._host[adapter_id] = tree
        self._version[adapter_id] = version
        if self.san is not None:
            self.san.on_register(adapter_id, version)

    def unregister(self, adapter_id: str) -> None:
        if self.refcount(adapter_id) > 0:
            raise AdapterError(
                f"{adapter_id}: unregister with {self.refcount(adapter_id)} "
                "in-flight refs")
        if self.san is not None:
            self.san.on_unregister(adapter_id)
        if adapter_id in self._slot:
            self._free.append(self._slot.pop(adapter_id))
            self._refs.pop(adapter_id, None)
            self._lru.pop(adapter_id, None)
        self._host.pop(adapter_id, None)
        self._version.pop(adapter_id, None)

    def is_registered(self, adapter_id: str) -> bool:
        return adapter_id in self._host

    def registered(self) -> List[str]:
        return sorted(self._host)

    def host_tree(self, adapter_id: str) -> Any:
        return self._host[adapter_id]

    def version(self, adapter_id: str) -> int:
        return self._version.get(adapter_id, 0)

    # -------------------------------------------------------- residency --
    def refcount(self, adapter_id: str) -> int:
        return self._refs.get(adapter_id, 0)

    def slot_index(self, adapter_id: str) -> int:
        """Device slot of a resident tenant, -1 otherwise."""
        return self._slot.get(adapter_id, -1)

    def resident_ids(self) -> tuple:
        return tuple(sorted(self._slot))

    def can_acquire(self, adapter_id: str) -> bool:
        if not self.is_registered(adapter_id):
            return False
        return adapter_id in self._slot or bool(self._free) \
            or bool(self._lru)

    def acquire(self, adapter_id: str) -> int:
        """Pin ``adapter_id``'s device slot (+1 ref), loading it from
        host on a miss — evicting the LRU cold tenant if no slot is
        free.  Raises ``OutOfAdapterSlots`` when every slot is pinned."""
        if not self.is_registered(adapter_id):
            raise AdapterError(f"{adapter_id}: not registered")
        slot = self._slot.get(adapter_id)
        if slot is not None:
            self.hits += 1
            self._lru.pop(adapter_id, None)
            self._refs[adapter_id] = self._refs.get(adapter_id, 0) + 1
            if self.san is not None:
                self.san.on_acquire(adapter_id)
            return slot
        if self._free:
            slot = self._free.pop()
        elif self._lru:
            cold, slot = self._lru.popitem(last=False)
            if self.san is not None:
                self.san.on_evict(cold)
            del self._slot[cold]
            self._refs.pop(cold, None)
            self.evictions += 1
        else:
            raise OutOfAdapterSlots(
                f"{adapter_id}: all {self.capacity} adapter slots are "
                "pinned by in-flight requests")
        self._stack = _write_adapter_slot(
            self._stack, place(self._host[adapter_id], self.device),
            jnp.asarray(slot, jnp.int32))
        self.loads += 1
        self._slot[adapter_id] = slot
        self._refs[adapter_id] = 1
        if self.san is not None:
            self.san.on_acquire(adapter_id)
        return slot

    def release(self, adapter_id: str) -> None:
        refs = self._refs.get(adapter_id, 0)
        if refs <= 0:
            raise AdapterError(f"{adapter_id}: release without acquire")
        if self.san is not None:
            self.san.on_release(adapter_id)
        refs -= 1
        self._refs[adapter_id] = refs
        if refs == 0:
            # stays resident (warm) until a miss needs the slot
            self._lru[adapter_id] = self._slot[adapter_id]

    def update(self, adapter_id: str, tree: Any,
               version: Optional[int] = None) -> None:
        """Swap a tenant's weights: host copy always, device slot in
        place when resident — the atomic publish under co-training
        (in-flight rows read the new weights on their next tick)."""
        if not self.is_registered(adapter_id):
            raise AdapterError(f"{adapter_id}: not registered")
        # registry-seam publish gate: refusing a non-finite tree here
        # keeps every resident slot servable even if a caller skipped
        # the LiveReplica-level gates
        from repro.runtime.replica import tree_finite
        if not tree_finite(tree):
            raise AdapterError(
                f"{adapter_id}: refusing non-finite adapter publish")
        if self.san is not None:
            self.san.begin_publish(adapter_id, version)
        self._host[adapter_id] = tree
        if version is not None:
            self._version[adapter_id] = version
        slot = self._slot.get(adapter_id)
        if slot is not None:
            self._stack = _write_adapter_slot(
                self._stack, place(tree, self.device),
                jnp.asarray(slot, jnp.int32))
        if self.san is not None:
            self.san.end_publish(adapter_id, version)

    def device_lora(self) -> Any:
        """The stacked device tree the segmented decode paths consume."""
        return self._stack


class ContinuousBatcher:
    """Fixed-slot continuous batching over one model replica.

    Owns the adapter + optimizer state so the fused combined path can
    donate/update them in place; ``LiveReplica`` delegates its adapter
    accessors here.  With ``paged=True`` it also owns the block
    allocator and per-slot block tables (see module docstring).

    Shadow-adapter double buffering: ``self.lora`` is the PUBLISHED
    snapshot — every prefill/decode reads it.  When ``self.train_lora``
    is set (a train session's shadow tree), the fused combined step
    trains THAT tree while decoding with the snapshot, so a whole round
    of optimizer updates never perturbs in-flight generation; greedy
    outputs stay bit-identical to serve-only until the owner swaps the
    shadow in (``LiveReplica.publish_adapter``) at a round boundary.
    With ``train_lora`` unset, training updates ``self.lora`` in place
    (the single-replica ``--combined`` behaviour, continuous
    adaptation per tick).

    Multi-tenant mode: pass an ``AdapterRegistry`` as ``adapters`` and
    route requests by ``GenRequest.adapter_id``.  Every prefill/decode
    then reads the registry's STACKED device tree with a per-row slot
    index (the segmented model paths), so one wave mixes tenants;
    admission pins each request's adapter (refcount+1, loading it on a
    miss) and eviction unpins it.  ``adapter_id=None`` rows serve the
    bare base model (slot -1).  The co-training pair is orthogonal:
    ``self.lora``/``train_lora`` stay the published/shadow trees of the
    co-train tenant, and the owner mirrors publishes into the registry
    (``LiveReplica.publish_adapter``).
    """

    def __init__(self, engine, params, lora, *, n_slots: int = 8,
                 max_seq: int = 128, prompt_pad: int = 32,
                 opt_state: Any = None, eos_id: Optional[int] = None,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 attn_backend: Optional[str] = None,
                 adapters: Optional[AdapterRegistry] = None,
                 prefill_chunk: int = 0, tpot_target: float = 0.0,
                 oversubscribe: float = 0.0, swap: bool = True,
                 device: Optional[Any] = None):
        cfg = engine.model.cfg
        if n_slots < 1:
            # run() makes progress only through slots; zero would spin
            # forever on a non-empty queue
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if not cfg.has_decode:
            raise NotImplementedError(
                f"{cfg.name}: encoder-only, no decode serving")
        if cfg.family.value == "vlm":
            raise NotImplementedError(
                f"{cfg.name}: VLM cross-KV slot plumbing (units-leading "
                "cache layout + per-request vision inputs) is a ROADMAP "
                "item; use the prefill/decode API directly")
        if cfg.sliding_window > 0 and prompt_pad > cfg.sliding_window:
            # ring handoff is sound as long as the whole prompt fits the
            # window: prefill K/V land in the ring verbatim and decode
            # wraps exactly like the seed's ring-buffer parity test
            raise ValueError(
                f"{cfg.name}: prompt_pad {prompt_pad} exceeds the "
                f"attention window {cfg.sliding_window}; windowed "
                "prompt eviction at admission is not implemented")
        if adapters is not None and cfg.has_ssm:
            raise NotImplementedError(
                f"{cfg.name}: multi-tenant adapter serving needs the "
                "ragged attention paths (SSM prefill is exact-length "
                "per request)")
        self.engine = engine
        self.model = engine.model
        self.cfg = cfg
        # replica placement: params, adapter, optimizer state and KV
        # pool all live on ``device`` (None = JAX's default device), so
        # every step program of this batcher runs there
        self.device = device
        self.params = place(params, device)
        self.lora = place(lora, device)
        self.opt_state = place(opt_state, device)
        self.adapters = adapters
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.prompt_pad = min(prompt_pad, max_seq)
        self.eos_id = eos_id
        # static decode-attention backend (None -> Pallas on TPU, jnp
        # elsewhere); the env override is read ONCE here, host-side, so
        # jitted programs cache per backend instead of per env state
        self.attn_backend = attn_backend \
            or os.environ.get("REPRO_DECODE_BACKEND") or None

        # logical cache length per slot: sliding-window archs ring-wrap
        # at the window, everyone else uses the full budget
        self.ring_len = min(max_seq, cfg.sliding_window) \
            if cfg.sliding_window > 0 else max_seq
        self.paged = paged
        if paged:
            if cfg.has_ssm or not cfg.has_attention:
                raise NotImplementedError(
                    f"{cfg.name}: paged KV serving needs an "
                    "attention-only stack (SSM/conv state is per-slot, "
                    "not per-block)")
            self.block_size = block_size
            self.blocks_per_slot = blocks_for(self.ring_len, block_size)
            if n_blocks is None:
                # full worst case + scratch block 0: paged-but-safe
                # default; callers shrink it to realize memory savings
                n_blocks = 1 + n_slots * self.blocks_per_slot
            if n_blocks < 1 + self.blocks_per_slot:
                raise ValueError(
                    f"n_blocks {n_blocks} cannot cover one worst-case "
                    f"request ({self.blocks_per_slot} blocks + scratch); "
                    "admission would deadlock")
            self.n_blocks = n_blocks
            self.allocator = BlockAllocator(n_blocks, block_size)
            # copy-on-write prefix sharing: identical block-aligned
            # prompt prefixes alias pool blocks at refcount+1 and skip
            # their prefill compute (see module docstring)
            if prefix_cache:
                from repro.models.transformer import use_dense_prefill
                if not use_dense_prefill(cfg, self.prompt_pad):
                    raise NotImplementedError(
                        f"{cfg.name}: prefix sharing needs the dense "
                        "prefill path — suffix prefill mirrors its "
                        "softmax formulation bit-for-bit, while "
                        "blockwise/unrolled prefill accumulates online "
                        "and would break cache-on/off greedy identity")
            self.prefix_cache = PrefixCache(self.allocator) \
                if prefix_cache else None
            with jax.default_device(device):
                self.caches = place(self.model.init_paged_caches(
                    n_blocks, block_size), device)
            # all-zero rows park inactive slots on scratch block 0
            self.block_tables = np.zeros((n_slots, self.blocks_per_slot),
                                         np.int32)
            self.slot_blocks: List[List[int]] = [[] for _ in
                                                 range(n_slots)]
            # worst-case blocks still reserved (not yet taken) per slot
            self.slot_reserved = np.zeros(n_slots, np.int32)
            # device copy of the live table slice, refreshed only when
            # tables actually change (admission/growth/eviction) — most
            # ticks reuse it instead of re-uploading
            self._dev_tables: Optional[jax.Array] = None
            self._dev_tables_width = 0
        else:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache requires paged=True (sharing rides "
                    "on pool block aliasing)")
            self.prefix_cache = None
            with jax.default_device(device):
                self.caches = place(
                    self.model.init_caches(n_slots, max_seq), device)
        # --------------------------------------- oversubscribed pool --
        # oversubscribe = w (0 < w <= 1): admission reserves only
        # near-term need against a w-fraction watermark of the pool;
        # mid-decode exhaustion preempts victims (swap-out to host or
        # drop + re-prefill).  0 keeps the preemption-free default.
        self.oversubscribe = float(oversubscribe)
        self.swap = bool(swap)
        if self.oversubscribe > 0:
            if not paged:
                raise ValueError(
                    "oversubscribe requires paged=True (preemption "
                    "moves pool blocks, not contiguous slot stripes)")
            if not (0 < self.oversubscribe <= 1):
                raise ValueError(
                    f"oversubscribe must be in (0, 1], got "
                    f"{self.oversubscribe}")
            if cfg.sliding_window > 0:
                raise NotImplementedError(
                    f"{cfg.name}: oversubscribed preemption needs full "
                    "attention — a sliding-window ring wrap overwrites "
                    "cache rows in place, so a dropped request cannot "
                    "be re-prefilled into an equivalent state")
            from repro.models.transformer import use_dense_prefill
            if not use_dense_prefill(cfg, self.prompt_pad):
                raise NotImplementedError(
                    f"{cfg.name}: drop-restore re-prefill rides the "
                    "suffix-continuation programs, which mirror the "
                    "dense prefill path bit-for-bit")
            # (1 - w) * capacity blocks stay unreservable at admission:
            # headroom for decode growth and swap-in restores
            self._headroom_blocks = self.allocator.capacity \
                - int(self.oversubscribe * self.allocator.capacity)
            self.swap_cost: Optional[_SwapCost] = _SwapCost()
        else:
            self._headroom_blocks = 0
            self.swap_cost = None
        # preempted requests parked off their slots, restored (swap-in
        # or re-prefill) ahead of admission in deadline-slack order
        self._swapped: List[_Swapped] = []
        # ------------------------------------------- chunked prefill --
        # prefill_chunk > 0: prompts prefill in fixed token-budget
        # chunks across successive ticks (chunk K attends over chunks
        # 1..K-1's K/V via the suffix/continuation programs), so
        # partially-prefilled slots coexist with decoding slots
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk > 0:
            if cfg.has_ssm or not cfg.has_attention:
                raise NotImplementedError(
                    f"{cfg.name}: chunked prefill needs an "
                    "attention-only stack (SSM state threads through "
                    "every token in order)")
            from repro.models.transformer import use_dense_prefill
            if not use_dense_prefill(cfg, self.prompt_pad):
                raise NotImplementedError(
                    f"{cfg.name}: chunked prefill needs the dense "
                    "prefill path — the continuation programs mirror "
                    "its softmax formulation bit-for-bit, while "
                    "blockwise/unrolled prefill accumulates online and "
                    "would break chunked-vs-monolithic greedy identity")
            if paged:
                # chunk boundaries must stay block-aligned mid-prefill:
                # write_prefill_blocks scatters whole blocks, so round
                # the chunk up to a block multiple (only a prompt's
                # FINAL chunk may be ragged)
                self.prefill_chunk = self.block_size * blocks_for(
                    self.prefill_chunk, self.block_size)
        # chunk width of one _advance_prefill wave: the chunking knob
        # when set; otherwise (oversubscribed drop-restores still
        # re-prefill through _advance_prefill) a block-aligned
        # prompt_pad so one restore chunk covers a typical prompt
        if self.prefill_chunk > 0:
            self._prefill_pad = self.prefill_chunk
        elif paged:
            self._prefill_pad = self.block_size * blocks_for(
                self.prompt_pad, self.block_size)
        else:
            self._prefill_pad = self.prompt_pad
        self.tpot_target = float(tpot_target)
        self.budget = _TickBudget(self.tpot_target) \
            if self.tpot_target > 0 else None
        # per-slot prefill progress: prompt tokens already in cache
        # (== len(prompt) once the slot is decoding) and how many of
        # those were prefix-cache hits rather than computed chunks
        self.slot_prefilled = np.zeros(n_slots, np.int32)
        self.slot_cached = np.zeros(n_slots, np.int32)
        # prefill goal per slot: len(prompt) normally; a drop-restore
        # re-prefills prompt + already-generated tokens, so its goal is
        # the restore sequence length (slot_seq overrides the token
        # source, slot_restore_tok re-installs the decode frontier
        # token on the final chunk instead of sampling a new one)
        self.slot_goal = np.zeros(n_slots, np.int32)
        self.slot_seq: List[Optional[np.ndarray]] = [None] * n_slots
        self.slot_restore_tok = np.full(n_slots, -1, np.int32)
        # what the latest step() actually trained (the token-budget
        # scheduler may shrink or skip a tick's microbatch) — the
        # replica's session bookkeeping reads these instead of assuming
        # one full train step per tick
        self.last_tick_trained = False
        self.last_tick_train_rows = 0
        self.queue: Deque[GenRequest] = collections.deque()
        self.slot_req: List[Optional[GenRequest]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)   # next write position
        self.slot_tok = np.zeros(n_slots, np.int32)   # next token to feed
        # registry mode: the adapter id each slot's request pinned at
        # admission (None = base-only row, decode slot index -1)
        self.slot_aid: List[Optional[str]] = [None] * n_slots
        # request-lifecycle FSM shadow, armed by REPRO_SANITIZE=1
        # (None otherwise — hooks cost one is-not-None test)
        self._lsan = lifecycle_sanitizer()
        self.stats = ServeStats()
        self.train_losses: List[float] = []
        # shadow adapter for double-buffered train sessions (None = train
        # self.lora in place) + the microbatch split the session wants
        self.train_lora: Optional[Any] = None
        self.train_grad_accum: int = 1
        # host copies of the latest train step's scalar metrics (ce_loss,
        # micro_grad_sqnorm, grad_sqnorm) — the noise-scale estimator's
        # inputs
        self.last_train_metrics: Dict[str, float] = {}

        jits = _engine_jits(engine)
        self._jit_decode = jits["decode"]
        self._jit_decode_paged = jits["decode_paged"]
        self._jit_prefill_ragged = jits["prefill_ragged"]
        self._jit_prefill_exact = jits["prefill_exact"]
        self._jit_write = jits["write"]
        self._jit_write_slots = jits["write_slots"]
        self._jit_write_blocks = jits["write_blocks"]
        self._jit_prefill_suffix = jits["prefill_suffix"]
        self._jit_prefill_continue = jits["prefill_continue"]
        self._jit_write_rows = jits["write_rows"]
        self._jit_copy_blocks = jits["copy_blocks"]
        self._jit_gather_blocks = jits["gather_blocks"]
        self._jit_scatter_blocks = jits["scatter_blocks"]
        self._jit_combined = jits["combined"]
        self._jit_combined_paged = jits["combined_paged"]
        self._jit_train = jits["train"]

    # ------------------------------------------------------------ ingestion -
    def submit(self, req: GenRequest) -> None:
        req.prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        assert len(req.prompt) <= self.prompt_pad, \
            f"prompt len {len(req.prompt)} > prompt_pad {self.prompt_pad}"
        if req.adapter_id is not None:
            if self.adapters is None:
                raise AdapterError(
                    f"request {req.request_id} names adapter "
                    f"{req.adapter_id!r} but this batcher has no "
                    "AdapterRegistry")
            if not self.adapters.is_registered(req.adapter_id):
                raise AdapterError(
                    f"request {req.request_id}: adapter "
                    f"{req.adapter_id!r} is not registered")
        # a slot holds prompt + generation; clamp so writes stay in-pool
        budget = self.max_seq - len(req.prompt)
        req.max_new_tokens = max(1, min(req.max_new_tokens, budget))
        if self._lsan is not None:
            self._lsan.on_submit(req)
        req.submitted_wall = time.perf_counter()
        self.queue.append(req)

    def active_slots(self) -> List[int]:
        return [i for i in range(self.n_slots)
                if self.slot_req[i] is not None]

    def _is_prefilling(self, i: int) -> bool:
        """Slot ``i`` holds a request whose prefill goal (prompt, or
        prompt + generated tokens for a drop-restore) is not fully in
        cache yet — parked out of the decode wave."""
        req = self.slot_req[i]
        return req is not None \
            and int(self.slot_prefilled[i]) < int(self.slot_goal[i])

    def _slot_seq(self, i: int) -> np.ndarray:
        """The token sequence slot ``i``'s prefill consumes: the
        request's prompt, unless a drop-restore installed a longer
        restore sequence (prompt + already-generated tokens)."""
        seq = self.slot_seq[i]
        return seq if seq is not None else self.slot_req[i].prompt

    def decoding_slots(self) -> List[int]:
        return [i for i in self.active_slots()
                if not self._is_prefilling(i)]

    def prefilling_slots(self) -> List[int]:
        return [i for i in self.active_slots() if self._is_prefilling(i)]

    def idle(self) -> bool:
        return not self.queue and not self.active_slots() \
            and not self._swapped

    @property
    def n_preempted(self) -> int:
        """Requests currently parked off-device by preemption (swap or
        drop) — the replica's thrashing signal for the dispatcher."""
        return len(self._swapped)

    # ------------------------------------------------------------ admission -
    def _worst_blocks(self, req: GenRequest) -> int:
        """Worst-case block count over the request's lifetime: prompt
        plus ``max_new_tokens - 1`` decode writes (the last sampled
        token is never fed back), capped by the ring length.  Under
        prefix sharing, full-attention requests reserve only the
        non-matched remainder (aliased blocks are already-used pool
        capacity); sliding-window requests reserve the full worst case
        because a ring wrap may copy-on-write every aliased block."""
        tokens = min(len(req.prompt) + req.max_new_tokens - 1,
                     self.ring_len)
        return blocks_for(tokens, self.block_size)

    # ---------------------------------------------------- adapter routing --
    def _serve_lora(self) -> Any:
        """The tree every prefill/decode reads: the registry's stacked
        device tree in multi-tenant mode, the single published adapter
        otherwise."""
        return self.adapters.device_lora() if self.adapters is not None \
            else self.lora

    def _wave_adapter_idx(self, reqs: List[GenRequest]):
        """Per-row registry slots for a prefill wave (requests were
        pinned at admission, so slots are stable); None without a
        registry."""
        if self.adapters is None:
            return None
        return jnp.asarray(
            [self.adapters.slot_index(r.adapter_id)
             if r.adapter_id is not None else -1 for r in reqs],
            jnp.int32)

    def _record_finish(self, req: GenRequest, now: float) -> None:
        if self._lsan is not None:
            self._lsan.on_finish(req)
        req.finished_at = now
        req.finished_wall = time.perf_counter()
        self.stats.finished += 1
        first = req.first_token_wall
        if first is not None and req.submitted_wall is not None:
            self.stats.ttft.append(first - req.submitted_wall)
            if len(req.tokens) > 1:
                self.stats.tpot.append(
                    (req.finished_wall - first) / (len(req.tokens) - 1))
        if req.adapter_id is not None:
            self.stats.adapter_requests[req.adapter_id] = \
                self.stats.adapter_requests.get(req.adapter_id, 0) + 1
            if self.adapters is not None:
                self.stats.adapter_versions[req.adapter_id] = \
                    self.adapters.version(req.adapter_id)

    def _prefill_wave(self, reqs: List[GenRequest],
                      plans: Optional[List] = None):
        """Prefill an admission wave; returns (first_tokens [W] np,
        [(prefill_caches, src_row)]).  Attention stacks: ONE ragged
        (right-padded) prefill program for the whole wave and ONE
        batched argmax sync for the wave's first tokens.  SSM/hybrid:
        state threads through pads, so exact-length per-request prefill
        (one compile per distinct prompt length).  With prefix-cache
        hits in the wave (``plans`` rows carry matched block chains),
        ONE suffix program computes only each row's uncached tokens,
        attending over the cached prefix K/V gathered from the pool."""
        if self.cfg.has_ssm:
            outs = [self._jit_prefill_exact(
                self.params, self.lora,
                {"tokens": jnp.asarray(r.prompt[None])}) for r in reqs]
            last = [logits[0, -1] for logits, _ in outs]
            # stack the wave's last-position logits on device so the
            # wave costs ONE argmax transfer, not one per request
            firsts = np.asarray(  # lint: host-sync-ok one batched argmax pull per prefill wave
                jnp.argmax(jnp.stack(last), axis=-1), np.int32)
            return firsts, [(pre, 0) for _, pre in outs], last
        lens = np.array([len(r.prompt) for r in reqs], np.int32)
        matched = [m for m, _ in plans] if plans else [[] for _ in reqs]
        if any(matched):
            bs = self.block_size
            pre_lens = np.array([len(m) * bs for m in matched], np.int32)
            suf_lens = lens - pre_lens
            # suffix width bucketed to block multiples, prefix width to
            # a power of two over the wave max (extra columns are
            # scratch-padded and masked): a handful of jit variants,
            # not one per distinct matched-chain length
            suf_pad = bs * blocks_for(int(suf_lens.max()), bs)
            npre = max(len(m) for m in matched)
            npre = min(1 << (npre - 1).bit_length(),
                       blocks_for(self.prompt_pad, bs))
            padded = np.zeros((len(reqs), suf_pad), np.int32)
            # scratch block 0 pads unmatched rows; their lanes are
            # masked by pre_lens inside the program
            pre_tables = np.zeros((len(reqs), npre), np.int32)
            for j, r in enumerate(reqs):
                padded[j, :suf_lens[j]] = r.prompt[pre_lens[j]:]
                pre_tables[j, :len(matched[j])] = matched[j]
            logits, pre = self._jit_prefill_suffix(
                self.params, self._serve_lora(),
                {"tokens": jnp.asarray(padded)},
                jnp.asarray(suf_lens), jnp.asarray(pre_lens),
                self.caches, jnp.asarray(pre_tables),
                self._wave_adapter_idx(reqs))
            firsts = np.asarray(  # lint: host-sync-ok one batched argmax pull per prefill wave
                jnp.argmax(logits[:, -1], axis=-1), np.int32)
            return firsts, [(pre, j) for j in range(len(reqs))], \
                logits[:, -1]
        padded = np.zeros((len(reqs), self.prompt_pad), np.int32)
        for j, r in enumerate(reqs):
            padded[j, :lens[j]] = r.prompt
        logits, pre = self._jit_prefill_ragged(
            self.params, self._serve_lora(),
            {"tokens": jnp.asarray(padded)}, jnp.asarray(lens),
            adapter_idx=self._wave_adapter_idx(reqs))
        firsts = np.asarray(  # lint: host-sync-ok one batched argmax pull per prefill wave
            jnp.argmax(logits[:, -1], axis=-1), np.int32)
        return firsts, [(pre, j) for j in range(len(reqs))], logits[:, -1]

    def admit(self, now: float = 0.0) -> List[GenRequest]:
        """Fill free slots from the queue; returns requests that finished
        at admission (max_new_tokens == 1 / instant EOS).  Paged mode
        admits FCFS only while the allocator can cover the head
        request's worst case — otherwise the queue waits for an
        eviction (preemption-free backpressure).  With the prefix cache
        on, the head request's longest cached block-aligned prefix is
        aliased at refcount+1 (reviving retained blocks as needed),
        only the uncached suffix is prefilled, and the request's
        newly written full prompt blocks are registered for the next
        admission."""
        finished: List[GenRequest] = []
        free = [i for i in range(self.n_slots)
                if self.slot_req[i] is None]
        reqs: List[GenRequest] = []
        # per admitted request: (matched block chain, blocks reserved)
        plans: List = []
        picked: List[int] = []      # queue indices claimed this wave
        idx = 0
        while len(reqs) < len(free) and idx < len(self.queue):
            head = self.queue[idx]
            if self.adapters is not None and head.adapter_id is not None \
                    and not self.adapters.can_acquire(head.adapter_id):
                # every slot of THIS tenant's adapter is pinned by
                # in-flight requests — skip past it within the arrival
                # wave (it keeps its queue position for the next one)
                # instead of head-of-line blocking the whole FCFS scan
                idx += 1
                continue
            if self.paged:
                matched = self.prefix_cache.match(
                    head.prompt, namespace=head.adapter_id) \
                    if self.prefix_cache is not None else []
                worst = self._worst_blocks(head)

                # sliding windows wrap decode writes back into prompt
                # blocks, so every aliased block may need a COW block;
                # full attention never writes an aliased block.  Over-
                # subscribed admission reserves only near-term need —
                # the prompt's uncached blocks plus a one-block decode
                # lookahead; growth past that is _ensure_headroom's
                # job (reserve-or-preempt at the block boundary).
                def need_for(m):
                    full = worst if self.cfg.sliding_window > 0 \
                        else worst - len(m)
                    if self.oversubscribe <= 0:
                        return full
                    near = blocks_for(
                        len(head.prompt) - len(m) * self.block_size,
                        self.block_size) + 1
                    return min(full, near)

                # a match can be too expensive to honor: reviving
                # retained blocks costs pool capacity ON TOP of the
                # worst-case reservation under sliding windows.  Trim
                # the aliased prefix until it fits — a cold admission
                # (no match) always fits one worst-case request, so
                # warm hits can never deadlock an idle pool.  The
                # oversubscription watermark holds (1 - w) * capacity
                # out of admission's reach so growth and swap-in
                # restores always find headroom (0 when off).
                while matched and self.allocator.available() \
                        < need_for(matched) \
                        + self.allocator.n_would_revive(matched) \
                        + self._headroom_blocks:
                    matched.pop()
                need = need_for(matched)
                if self.allocator.available() \
                        < need + self.allocator.n_would_revive(matched) \
                        + self._headroom_blocks:
                    # pool backpressure stays strict FCFS: nothing
                    # behind the head may jump an exhausted pool
                    break
                self.allocator.acquire(matched)
                self.allocator.reserve(need)
                if self.prefix_cache is not None:
                    self.prefix_cache.count_admitted(
                        head.prompt, len(matched),
                        namespace=head.adapter_id)
                plans.append((matched, need))
            if self._lsan is not None:
                self._lsan.on_admit(head)
            if self.adapters is not None and head.adapter_id is not None:
                # pin the tenant's device slot for the request lifetime
                # (loads from host on a miss; can_acquire gated above)
                self.adapters.acquire(head.adapter_id)
            head.admitted_wall = time.perf_counter()
            reqs.append(head)
            picked.append(idx)
            idx += 1
        for j in reversed(picked):
            del self.queue[j]
        if not reqs:
            return finished
        if self.prefill_chunk > 0:
            # chunked mode only ASSIGNS slots here; chunk 1 (and every
            # continuation) runs through _advance_prefill under the
            # tick's token budget
            self._assign_chunked(free, reqs, plans, now)
            return finished
        with _span("collm.prefill_wave", rows=len(reqs),
                   tokens=sum(len(r.prompt) for r in reqs),
                   ids=" ".join(str(r.request_id) for r in reqs)):
            return self._admit_wave(free, reqs, plans, now)

    def _admit_wave(self, free: List[int], reqs: List[GenRequest],
                    plans: List, now: float) -> List[GenRequest]:
        """Prefill the requests ``admit`` picked, stamp their first
        tokens, bind them to ``free`` slots and land the wave's K/V in
        the cache; returns the requests done at admission."""
        finished: List[GenRequest] = []
        firsts, entries, last_logits = self._prefill_wave(
            reqs, plans if self.paged else None)
        # one batched scatter per wave on the ragged-attention paths;
        # rows flagged with an out-of-range id are dropped (requests
        # that finished at admission)
        batched = not self.cfg.has_ssm
        wave_pre = entries[0][0] if batched else None
        if self.paged:
            # wave table width follows the prefill width: full prompts
            # on a cold wave, just the suffix when prefixes were cached
            nbp = blocks_for(wave_pre["kv"][0].shape[2], self.block_size)
            wave_tables = np.full((len(reqs), nbp), self.n_blocks,
                                  np.int32)
        elif batched:
            wave_slots = np.full(len(reqs), self.n_slots, np.int32)
        admitted_rows = 0
        for k, (slot, req, first, (pre_caches, src)) in enumerate(zip(
                free, reqs, firsts, entries)):
            first = int(first)
            if req.samples:
                # the wave's k-th logits row belongs to the k-th request
                # on every prefill path (SSM stacks per request)
                req.rng = np.random.default_rng(
                    req.seed if req.seed is not None else req.request_id)
                first = sample_token(
                    np.asarray(last_logits[k]),
                    temperature=req.temperature, top_k=req.top_k,
                    top_p=req.top_p, rng=req.rng)
            matched, reserved = plans[k] if self.paged else ([], 0)
            n_cached = len(matched) * (self.block_size if self.paged
                                       else 0)
            req.tokens.append(first)
            req.first_token_wall = time.perf_counter()
            req.first_token_at = now
            self.stats.admitted += 1
            self.stats.prefill_tokens += len(req.prompt) - n_cached
            self.stats.cached_prefix_tokens += n_cached
            self.stats.generated_tokens += 1
            if len(req.tokens) >= req.max_new_tokens \
                    or first == self.eos_id:
                # done at admission: never occupies the slot, so skip
                # the cache write entirely and drop the aliased prefix
                self._record_finish(req, now)
                if self.adapters is not None \
                        and req.adapter_id is not None:
                    self.adapters.release(req.adapter_id)
                if self.paged:
                    self.allocator.release(reserved)
                    if matched:
                        self.allocator.free(matched)
                finished.append(req)
                continue
            if self.paged:
                need = blocks_for(len(req.prompt) - n_cached,
                                  self.block_size)
                ids = self.allocator.take(need)
                self.slot_blocks[slot] = list(matched) + ids
                self.slot_reserved[slot] = reserved - need
                self.block_tables[slot, :] = 0
                self.block_tables[slot, :len(matched) + need] = \
                    self.slot_blocks[slot]
                wave_tables[src, :need] = ids
                # register the freshly written full prompt blocks —
                # except for a request whose decode will ring-wrap back
                # into them: those blocks are doomed to be overwritten
                # mid-flight, and an owner forced to COW its own
                # registered blocks would outrun its reservation
                wraps = len(req.prompt) + req.max_new_tokens - 1 \
                    > self.ring_len
                if self.prefix_cache is not None and not wraps:
                    self.prefix_cache.register(
                        req.prompt, self.slot_blocks[slot], len(matched),
                        namespace=req.adapter_id)
                self._dev_tables = None
            elif batched:
                wave_slots[src] = slot
            else:
                self.caches = self._jit_write(self.caches, pre_caches,
                                              slot, src)
            admitted_rows += 1
            self.slot_req[slot] = req
            self.slot_aid[slot] = req.adapter_id
            self.slot_pos[slot] = len(req.prompt)
            self.slot_tok[slot] = first
            self.slot_prefilled[slot] = len(req.prompt)
            self.slot_goal[slot] = len(req.prompt)
            self.slot_cached[slot] = n_cached
        if admitted_rows and self.paged:
            self.caches = self._jit_write_blocks(
                self.caches, wave_pre, jnp.asarray(wave_tables))
        elif admitted_rows and batched:
            self.caches = self._jit_write_slots(
                self.caches, wave_pre, jnp.asarray(wave_slots))
        return finished

    # ------------------------------------------------- chunked prefill -
    def _assign_chunked(self, free: List[int], reqs: List[GenRequest],
                        plans: List, now: float) -> None:
        """Chunked admission: bind each selected request to a slot in
        PREFILLING state (no prefill program runs here).  The slot is
        parked out of the decode wave — ``slot_prefilled < len(prompt)``
        — until ``_advance_prefill`` lands its final chunk."""
        for k, (slot, req) in enumerate(zip(free, reqs)):
            matched, reserved = plans[k] if self.paged else ([], 0)
            n_cached = len(matched) * (self.block_size if self.paged
                                       else 0)
            self.stats.admitted += 1
            self.stats.cached_prefix_tokens += n_cached
            self.slot_req[slot] = req
            self.slot_aid[slot] = req.adapter_id
            self.slot_prefilled[slot] = n_cached
            self.slot_goal[slot] = len(req.prompt)
            self.slot_cached[slot] = n_cached
            # parked: the decode wave's write for this row is garbage
            # aimed at position ``slot_prefilled`` (contiguous — the
            # next chunk overwrites it before it can be attended) or at
            # scratch block 0 (paged — the dev-table row is zeroed)
            self.slot_pos[slot] = n_cached
            self.slot_tok[slot] = 0
            if self.paged:
                self.slot_blocks[slot] = list(matched)
                self.slot_reserved[slot] = reserved
                self.block_tables[slot, :] = 0
                self.block_tables[slot, :len(matched)] = matched
                self._dev_tables = None

    def _advance_prefill(self, now: float, allowance: float):
        """Spend up to ``allowance`` prefill tokens on the most urgent
        partially-prefilled slots (deadline-slack order), one chunk per
        slot, as ONE wave program + ONE batched cache write.  A slot
        whose final chunk lands gets its first token from the wave's
        logits and joins the decode wave this same tick.  Returns
        (requests finished at prefill completion, measured seconds)."""
        done: List[GenRequest] = []
        pref = self.prefilling_slots()
        if not pref or allowance <= 0:
            return done, 0.0
        order = slack_order(pref, now,
                            key=lambda i: self.slot_req[i].deadline)
        rows: List = []             # (slot, chunk_len)
        used = 0
        for i in order:
            c = min(int(self.slot_goal[i]) - int(self.slot_prefilled[i]),
                    self._prefill_pad)
            if rows and used + c > allowance:
                break               # first chunk always makes progress
            rows.append((i, c))
            used += c
            if used >= allowance:
                break
        t0 = time.perf_counter()
        w = len(rows)
        slots_arr = [i for i, _ in rows]
        slots_np = np.asarray(slots_arr, np.int32)
        wave_reqs = [self.slot_req[i] for i in slots_arr]
        chunk_lens = np.array([c for _, c in rows], np.int32)
        pre_lens = self.slot_prefilled[slots_np]    # host counters
        pad = self._prefill_pad
        tokens = np.zeros((w, pad), np.int32)
        for j, (i, c) in enumerate(rows):
            p = int(self.slot_prefilled[i])
            tokens[j, :c] = self._slot_seq(i)[p:p + c]
        if self.paged:
            bs = self.block_size
            # prefix tables: each slot's blocks so far, width bucketed
            # to a power of two (extra lanes are scratch, masked by
            # pre_lens inside the program)
            npre = max(max(len(self.slot_blocks[i])
                           for i in slots_arr), 1)
            npre = min(1 << (npre - 1).bit_length(),
                       self.blocks_per_slot)
            pre_tables = np.zeros((w, npre), np.int32)
            for j, i in enumerate(slots_arr):
                blk = self.slot_blocks[i]
                pre_tables[j, :len(blk)] = blk
            logits, pre = self._jit_prefill_suffix(
                self.params, self._serve_lora(),
                {"tokens": jnp.asarray(tokens)},
                jnp.asarray(chunk_lens), jnp.asarray(pre_lens),
                self.caches, jnp.asarray(pre_tables),
                self._wave_adapter_idx(wave_reqs))
            # land the chunk in fresh blocks against each slot's
            # admission-time reservation (chunks are block-aligned, so
            # sum-over-chunks == the monolithic block count)
            nbp = blocks_for(pad, bs)
            wave_tables = np.full((w, nbp), self.n_blocks, np.int32)
            for j, (i, c) in enumerate(rows):
                need = blocks_for(c, bs)
                assert self.slot_reserved[i] >= need, \
                    f"slot {i}: chunk beyond admission reservation"
                ids = self.allocator.take(need)
                self.slot_reserved[i] -= need
                base = len(self.slot_blocks[i])
                self.slot_blocks[i].extend(ids)
                self.block_tables[i, base:base + need] = ids
                wave_tables[j, :need] = ids
            self._dev_tables = None
            self.caches = self._jit_write_blocks(
                self.caches, pre, jnp.asarray(wave_tables))
        else:
            logits, pre = self._jit_prefill_continue(
                self.params, self._serve_lora(),
                {"tokens": jnp.asarray(tokens)},
                jnp.asarray(chunk_lens), jnp.asarray(pre_lens),
                self.caches, jnp.asarray(slots_arr, dtype=jnp.int32),
                adapter_idx=self._wave_adapter_idx(wave_reqs))
            self.caches = self._jit_write_rows(
                self.caches, pre, slots_np, pre_lens, chunk_lens)
        final_rows = [j for j, (i, c) in enumerate(rows)
                      if int(self.slot_prefilled[i]) + c
                      >= int(self.slot_goal[i])]
        nxt = None
        host_rows = None
        if final_rows:
            nxt = np.asarray(  # lint: host-sync-ok one batched argmax pull per chunk wave
                jnp.argmax(logits[:, -1], axis=-1), np.int32)
            if any(wave_reqs[j].samples for j in final_rows):
                host_rows = np.asarray(logits[:, -1])  # lint: host-sync-ok one batched logits pull per sampling chunk wave
        for j, (i, c) in enumerate(rows):
            req = wave_reqs[j]
            p = int(self.slot_prefilled[i]) + c
            self.slot_prefilled[i] = p
            self.stats.prefill_tokens += c
            if p < int(self.slot_goal[i]):
                self.slot_pos[i] = p    # stay parked at the frontier
                continue
            if int(self.slot_restore_tok[i]) >= 0:
                # drop-restore final chunk: every generated token was
                # already emitted before preemption — re-install the
                # decode frontier (next position + stored feed token)
                # instead of sampling a new one
                self.slot_pos[i] = int(self.slot_goal[i])
                self.slot_tok[i] = int(self.slot_restore_tok[i])
                self.slot_restore_tok[i] = -1
                self.slot_seq[i] = None
                continue
            # final chunk: the wave's logits row IS the full prompt's
            # last-token logits (bit-identical to monolithic prefill)
            first = int(nxt[j])
            if req.samples:
                req.rng = np.random.default_rng(
                    req.seed if req.seed is not None else req.request_id)
                first = sample_token(
                    host_rows[j], temperature=req.temperature,
                    top_k=req.top_k, top_p=req.top_p, rng=req.rng)
            req.tokens.append(first)
            req.first_token_wall = time.perf_counter()
            req.first_token_at = now
            self.stats.generated_tokens += 1
            if self.paged:
                wraps = len(req.prompt) + req.max_new_tokens - 1 \
                    > self.ring_len
                if self.prefix_cache is not None and not wraps:
                    self.prefix_cache.register(
                        req.prompt, self.slot_blocks[i],
                        int(self.slot_cached[i]) // self.block_size,
                        namespace=req.adapter_id)
            if len(req.tokens) >= req.max_new_tokens \
                    or first == self.eos_id:
                self._record_finish(req, now)
                self._evict(i)
                done.append(req)
                continue
            self.slot_pos[i] = len(req.prompt)
            self.slot_tok[i] = first
        dt = time.perf_counter() - t0
        if self.budget is not None:
            self.budget.observe_prefill(used, dt)
        if self.swap_cost is not None:
            self.swap_cost.observe_prefill(used, dt)
        return done, dt

    # ---------------------------------------------- preemption / swap -
    def _block_bytes(self) -> int:
        """Host bytes one pool block occupies across every cache leaf
        (the swap cost model's unit)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.caches)) \
            // self.n_blocks

    def _pick_victim(self, protect: int, now: float) -> Optional[int]:
        """Victim slot for one preemption: among active slots that
        would actually return pool capacity (a sole-referenced block to
        free, or an unused reservation), the one with the MOST deadline
        slack — cost-to-restore (fewest live rows) breaks ties.
        ``slack_order`` puts the most urgent first, so the victim is
        the tail of the order."""
        cands = []
        for j in self.active_slots():
            if j == protect or self.slot_req[j] is None:
                continue
            gain = int(self.slot_reserved[j]) + sum(
                1 for b in self.slot_blocks[j]
                if self.allocator.ref(b) == 1)
            if gain > 0:
                cands.append(j)
        if not cands:
            return None
        # stable pre-sort by restore cost so slack ties resolve to the
        # cheapest victim once the most-slack tail is taken
        cands.sort(key=lambda j: -int(self.slot_pos[j]))
        order = slack_order(cands, now,
                            key=lambda j: self.slot_req[j].deadline)
        return order[-1]

    def _preempt(self, i: int, now: float) -> None:
        """Preempt slot ``i``: park its request off the device and
        return its private pool capacity.  The COW-shared /
        prefix-registered chain prefix stays pool-resident with our
        references held; the private tail either swaps to host (ONE
        batched device->host gather) or is dropped for re-prefill from
        the request's host-kept token ids, whichever the EMA cost model
        prices cheaper.  The pinned adapter reference is kept across
        the preemption so restore can never fail on adapter
        residency."""
        req = self.slot_req[i]
        chain = self.slot_blocks[i]
        bs = self.block_size

        def resident(b: int) -> bool:
            return self.allocator.ref(b) > 1 \
                or (self.prefix_cache is not None
                    and self.prefix_cache.is_registered(b))

        kept = 0
        while kept < len(chain) and resident(chain[kept]):
            kept += 1
        tail = chain[kept:]
        # under full attention resident blocks always form a chain
        # PREFIX (decode never writes shared/registered blocks and
        # registration covers full prompt blocks only) — but verify:
        # an interior resident block forces the drop path, whose
        # ``free`` handles shared and registered blocks correctly
        mode = "swap"
        if self._is_prefilling(i) or not tail \
                or any(resident(b) for b in tail) or not self.swap:
            mode = "reprefill"
        elif self.swap_cost is not None and not self.swap_cost.prefer_swap(
                len(tail) * self._block_bytes(),
                int(self.slot_pos[i]) - kept * bs):
            mode = "reprefill"
        if mode == "swap":
            t0 = time.perf_counter()
            width = 1 << max(len(tail) - 1, 0).bit_length()
            ids = np.zeros(width, np.int32)  # pads gather scratch rows
            ids[:len(tail)] = tail
            host = jax.device_get(  # lint: host-sync-ok one batched device->host block copy per swap-out
                self._jit_gather_blocks(self.caches, ids))
            hk, hv = host["kv"]
            host_kv = (hk[:, :len(tail)], hv[:, :len(tail)])
            self.allocator.swap_out(tail)
            if self.swap_cost is not None:
                self.swap_cost.observe_swap(
                    len(tail) * self._block_bytes(),
                    time.perf_counter() - t0)
            entry = _Swapped(
                req=req, adapter_id=self.slot_aid[i], mode="swap",
                kept=chain[:kept], host_kv=host_kv, n_tail=len(tail),
                pos=int(self.slot_pos[i]), tok=int(self.slot_tok[i]),
                cached=int(self.slot_cached[i]))
            self.stats.swap_out_blocks += len(tail)
        else:
            # drop the whole chain: shared blocks lose our alias,
            # registered sole-ref blocks park in the retained pool and
            # revive through the PrefixCache at restore
            if chain:
                self.allocator.free(chain)
            entry = _Swapped(
                req=req, adapter_id=self.slot_aid[i], mode="reprefill",
                kept=[], host_kv=None, n_tail=0,
                pos=int(self.slot_pos[i]), tok=int(self.slot_tok[i]),
                cached=0)
        self._swapped.append(entry)
        self.stats.preemptions += 1
        # clear the slot WITHOUT finishing the request (it stays ACTIVE
        # in the lifecycle FSM — restore is not a re-admission) and
        # WITHOUT releasing its adapter pin
        self.allocator.release(int(self.slot_reserved[i]))
        self.slot_reserved[i] = 0
        self.slot_req[i] = None
        self.slot_aid[i] = None
        self.slot_blocks[i] = []
        self.slot_pos[i] = 0
        self.slot_tok[i] = 0
        self.slot_prefilled[i] = 0
        self.slot_cached[i] = 0
        self.slot_goal[i] = 0
        self.slot_seq[i] = None
        self.slot_restore_tok[i] = -1
        self.block_tables[i, :] = 0
        self._dev_tables = None

    def _ensure_headroom(self, active: List[int],
                         now: float) -> List[int]:
        """Oversubscribed decode: every slot crossing a block boundary
        this tick must hold a reservation for the fresh block BEFORE
        ``_grow_tables`` takes it.  On pool exhaustion, preempt victims
        (most deadline slack first) until the reservation fits; as a
        last resort the needy slot preempts itself.  Returns the active
        set minus any preempted slots."""
        active = list(active)
        for i in list(active):
            if self.slot_req[i] is None or i not in active:
                continue
            wr = int(self.slot_pos[i]) % self.ring_len
            if wr // self.block_size < len(self.slot_blocks[i]) \
                    or int(self.slot_reserved[i]) > 0:
                continue
            while not self.allocator.can_reserve(1):
                victim = self._pick_victim(protect=i, now=now)
                if victim is None:
                    victim = i   # last resort: the needy slot itself
                self._preempt(victim, now)
                if victim in active:
                    active.remove(victim)
                if victim == i:
                    break
            if self.slot_req[i] is not None:
                self.allocator.reserve(1)
                self.slot_reserved[i] += 1
        return active

    def _demote(self, e: _Swapped) -> None:
        """Give up a parked entry's remaining pool footprint: drop the
        kept-chain references (registered blocks park retained, shared
        ones lose our alias) and discard any host KV — the entry will
        restore through the reprefill path instead."""
        if e.kept:
            self.allocator.free(e.kept)
            e.kept = []
        e.host_kv = None
        e.n_tail = 0
        e.mode = "reprefill"
        e.cached = 0

    def _demote_one(self, prefer_not: int) -> bool:
        """Demote one demotable parked entry (preferring any entry but
        ``prefer_not``, which is the one being forced in).  False when
        nothing is left to demote."""
        cand = None
        for k, e in enumerate(self._swapped):
            if e.mode == "swap" or e.kept:
                if k != prefer_not:
                    cand = k
                elif cand is None:
                    cand = k
        if cand is None:
            return False
        self._demote(self._swapped[cand])
        return True

    def _try_restore(self, e: _Swapped, slot: int, now: float) -> bool:
        """Re-enter one parked request into free slot ``slot``.  Swap
        mode: fresh blocks + ONE batched host->device scatter; decode
        resumes exactly where it stopped.  Reprefill mode: back into
        PREFILLING state over prompt + generated tokens (the suffix
        programs recompute the dropped KV bit-identically; the final
        chunk re-installs the stored frontier token).  Returns False —
        with no side effects — when the pool cannot cover it yet."""
        req = e.req
        if e.mode == "swap":
            if not self.allocator.can_reserve(e.n_tail):
                return False
            ids = self.allocator.swap_in(e.n_tail)
            width = 1 << max(e.n_tail - 1, 0).bit_length()
            pad_ids = np.full(width, self.n_blocks, np.int32)
            pad_ids[:e.n_tail] = ids     # pads are dropped (mode=drop)
            hk, hv = e.host_kv
            if width != e.n_tail:
                zk = np.zeros(hk.shape[:1] + (width,) + hk.shape[2:],
                              hk.dtype)
                zv = np.zeros(hv.shape[:1] + (width,) + hv.shape[2:],
                              hv.dtype)
                zk[:, :e.n_tail] = hk
                zv[:, :e.n_tail] = hv
                hk, hv = zk, zv
            self.caches = self._jit_scatter_blocks(
                self.caches, pad_ids, (hk, hv))
            self.slot_blocks[slot] = list(e.kept) + ids
            self.slot_reserved[slot] = 0
            self.slot_prefilled[slot] = len(req.prompt)
            self.slot_goal[slot] = len(req.prompt)
            self.slot_cached[slot] = e.cached
            self.slot_pos[slot] = e.pos
            self.slot_tok[slot] = e.tok
            self.slot_seq[slot] = None
            self.slot_restore_tok[slot] = -1
            self.stats.swap_in_blocks += e.n_tail
        else:
            # drop-restore: re-prefill prompt + all generated tokens
            # but the last, whose KV is never needed (it is the next
            # token to FEED) — slot_restore_tok re-installs it
            seq = req.prompt if not req.tokens else np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            matched = self.prefix_cache.match(
                req.prompt, namespace=e.adapter_id) \
                if self.prefix_cache is not None else []
            bs = self.block_size
            worst = self._worst_blocks(req)

            def need_for(m):
                return min(worst - len(m),
                           blocks_for(len(seq) - len(m) * bs, bs) + 1)

            while matched and self.allocator.available() \
                    < need_for(matched) \
                    + self.allocator.n_would_revive(matched):
                matched.pop()
            need = need_for(matched)
            if self.allocator.available() \
                    < need + self.allocator.n_would_revive(matched):
                return False
            self.allocator.acquire(matched)
            self.allocator.reserve(need)
            n_cached = len(matched) * bs
            self.slot_blocks[slot] = list(matched)
            self.slot_reserved[slot] = need
            self.slot_prefilled[slot] = n_cached
            self.slot_goal[slot] = len(seq)
            self.slot_cached[slot] = n_cached
            self.slot_pos[slot] = n_cached
            self.slot_tok[slot] = 0
            self.slot_seq[slot] = seq if req.tokens else None
            self.slot_restore_tok[slot] = req.tokens[-1] \
                if req.tokens else -1
            self.stats.reprefill_tokens += len(seq) - n_cached
        self.slot_req[slot] = req
        self.slot_aid[slot] = e.adapter_id
        self.block_tables[slot, :] = 0
        blks = self.slot_blocks[slot]
        self.block_tables[slot, :len(blks)] = blks
        self._dev_tables = None
        return True

    def _restore(self, now: float) -> None:
        """Bring preempted requests back into free slots ahead of
        admission, most urgent (smallest deadline slack) first; entries
        the pool cannot cover yet stay parked.  If NOTHING else can run
        — no active slot, and the queue is empty or its head cannot be
        admitted either — capacity is forcibly reclaimed from the other
        parked entries' kept chains (demotion to reprefill) so the most
        urgent restore always goes through: the batcher can never
        livelock on its own parked work."""
        free = [i for i in range(self.n_slots)
                if self.slot_req[i] is None]
        if not free:
            return
        order = slack_order(
            list(range(len(self._swapped))), now,
            key=lambda k: self._swapped[k].req.deadline)
        restored: set = set()
        for k in order:
            if not free:
                break
            if self._try_restore(self._swapped[k], free[0], now):
                free.pop(0)
                restored.add(k)
        if not restored and free and not self.active_slots():
            blocked_queue = False
            if self.queue:
                # conservative cold-admission check (a prefix match
                # only shrinks the head's need, so "fits" is exact)
                head = self.queue[0]
                need = min(self._worst_blocks(head),
                           blocks_for(len(head.prompt),
                                      self.block_size) + 1)
                blocked_queue = self.allocator.available() \
                    < need + self._headroom_blocks
            if not self.queue or blocked_queue:
                k = order[0]
                while not self._try_restore(self._swapped[k], free[0],
                                            now):
                    if not self._demote_one(k):
                        break
                if self.slot_req[free[0]] is not None:
                    restored.add(k)
        if restored:
            self._swapped = [e for k, e in enumerate(self._swapped)
                             if k not in restored]

    # --------------------------------------------------------------- decode -
    def _grow_tables(self, active: List[int]) -> None:
        """Make the block each slot's next write lands in writable:
        allocate it if the table doesn't cover it yet (the 'grow one
        block at a time' step, always against the slot's admission-time
        reservation); under prefix sharing, a covered-but-shared block
        (refcount > 1 — a ring wrap re-entering an aliased prompt
        block) is copy-on-written to a private block first, and a
        registered refcount-1 block is unregistered from the prefix
        cache so its cached entry never goes stale in place."""
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for i in active:
            wr = int(self.slot_pos[i]) % self.ring_len
            bidx = wr // self.block_size
            if bidx >= len(self.slot_blocks[i]):
                assert self.slot_reserved[i] > 0, \
                    f"slot {i}: growth beyond admission reservation"
                (bid,) = self.allocator.take(1)
                self.slot_reserved[i] -= 1
                self.slot_blocks[i].append(bid)
                self.block_tables[i, bidx] = bid
                self._dev_tables = None
            elif self.prefix_cache is not None:
                bid = self.slot_blocks[i][bidx]
                if self.allocator.ref(bid) > 1:
                    assert self.slot_reserved[i] > 0, \
                        f"slot {i}: copy-on-write beyond reservation"
                    (nb,) = self.allocator.take(1)
                    self.slot_reserved[i] -= 1
                    cow_src.append(bid)
                    cow_dst.append(nb)
                    self.allocator.free([bid])   # drop our alias
                    self.slot_blocks[i][bidx] = nb
                    self.block_tables[i, bidx] = nb
                    self._dev_tables = None
                elif self.prefix_cache.is_registered(bid):
                    self.prefix_cache.unregister_block(bid)
        if cow_src:
            # one batched device copy per tick; pad to a small bucket
            # of widths so the jit cache stays bounded (0 -> 0 copies
            # the scratch block onto itself: harmless)
            width = 1 << (len(cow_src) - 1).bit_length()
            pad = width - len(cow_src)
            src = np.asarray(cow_src + [0] * pad, np.int32)
            dst = np.asarray(cow_dst + [0] * pad, np.int32)
            self.caches = self._jit_copy_blocks(self.caches, src, dst)

    def _table_width(self, active: List[int]) -> int:
        """Bucketed live-table width: the decode program only streams
        blocks up to the longest active slot, rounded up to a small
        bucket (1, 2, then multiples of 2) so the jit cache stays at a
        handful of variants instead of one per length."""
        need = max(len(self.slot_blocks[i]) for i in active)
        width = need if need <= 2 else 2 * (-(-need // 2))
        return min(width, self.blocks_per_slot)

    def step(self, train_batch: Optional[Dict[str, Any]] = None,
             now: float = 0.0) -> List[GenRequest]:
        """One runtime tick under the token budget: admit, spend the
        decode-TPOT slack on prefill chunks (deadline-slack order),
        advance every DECODING slot one token, and fit a train
        microbatch (full / halved / skipped) into whatever budget
        remains.  Without chunking/budget knobs this reduces to the
        original admit + full-wave tick.  Returns the requests that
        finished this tick.

        The tick and each of its phases run under a ``collm.*`` host
        span (``_span``); ``collm.step`` carries the decode wave's live
        rows and whether the tick trained."""
        if train_batch is not None and self.opt_state is None:
            raise ValueError(
                "step(train_batch=...) requires opt_state (pass it to "
                "the ContinuousBatcher constructor)")
        with _span("collm.step") as span:
            finished, rows = self._tick(train_batch, now)
            span.set_metadata(rows=rows,
                              trained=int(self.last_tick_trained))
        return finished

    def _tick(self, train_batch: Optional[Dict[str, Any]], now: float):
        """``step``'s body; returns (finished requests, decode rows)."""
        budget = self.budget
        self.last_tick_trained = False
        self.last_tick_train_rows = 0
        if self._swapped:
            with _span("collm.restore"):
                self._restore(now)
        with _span("collm.admit"):
            finished = self.admit(now)
        prefill_spent = 0.0
        chunked = self.prefill_chunk > 0 or self.oversubscribe > 0
        if chunked and self.prefilling_slots():
            allowance = float("inf") if budget is None else \
                budget.prefill_allowance(len(self.decoding_slots()))
            with _span("collm.advance_prefill"):
                done, prefill_spent = self._advance_prefill(now,
                                                            allowance)
            finished.extend(done)
        active = self.decoding_slots() if chunked \
            else self.active_slots()
        if self.oversubscribe > 0 and active:
            # preempt-or-reserve BEFORE _grow_tables takes fresh blocks
            with _span("collm.headroom"):
                active = self._ensure_headroom(active, now)
        if not active:
            if train_batch is not None:
                ref = train_batch.get("tokens",
                                      train_batch.get("embeds"))
                b, s = int(ref.shape[0]), int(ref.shape[1])
                tt: Optional[int] = 0
                if budget is not None and self.prefilling_slots():
                    # mid-prefill slots are waiting on TTFT — only
                    # train in whatever slack this tick has left
                    tt = budget.train_tokens(b, s, prefill_spent)
                if tt is None:
                    self.stats.train_skipped_ticks += 1
                else:
                    t0 = time.perf_counter()
                    with _span("collm.train"):
                        self._plain_train(train_batch, train_tokens=tt)
                    rows = b if tt == 0 else max(1, min(b, tt // s))
                    if budget is not None:
                        budget.observe_train(
                            rows * s, time.perf_counter() - t0)
                    self.last_tick_trained = True
                    self.last_tick_train_rows = rows
            self._record_budget(prefill_spent)
            return finished, 0
        tables = None
        if self.paged:
            with _span("collm.grow_tables"):
                tables = self._wave_tables(active)
        if self._lsan is not None:
            self._sanitize_wave(active)
        # budget the tick's leftover slack into the train microbatch:
        # full batch / half batch / skipped (tt=None), a static knob so
        # the fused program compiles at most twice per shape
        tt: Optional[int] = 0
        train_rows = 0
        if train_batch is not None:
            ref = train_batch.get("tokens", train_batch.get("embeds"))
            b, s = int(ref.shape[0]), int(ref.shape[1])
            if budget is not None:
                tt = budget.train_tokens(b, s, prefill_spent)
            if tt is None:
                self.stats.train_skipped_ticks += 1
            else:
                train_rows = b if tt == 0 else max(1, min(b, tt // s))
        t0 = time.perf_counter()
        with _span("collm.dispatch"):
            logits = self._dispatch(active, tables, train_batch, tt)
        if self.last_tick_trained:
            self.last_tick_train_rows = train_rows
        self.stats.decode_steps += 1
        with _span("collm.token_pull"):
            nxt = np.asarray(  # lint: host-sync-ok one batched argmax pull per decode wave
                jnp.argmax(logits[:, -1], axis=-1), np.int32)
        dt = time.perf_counter() - t0
        if budget is not None:
            if self.last_tick_trained:
                # the fused tick's train share is what exceeded the
                # known decode cost (conservative before it's known)
                budget.observe_train(
                    train_rows * s,
                    max(dt - (budget.decode_tick_s or 0.0), 0.0))
            else:
                budget.observe_decode(dt)
        self._record_budget(prefill_spent + dt)
        if any(self.slot_req[i].samples for i in active):
            # ONE batched host fetch of the last-position logits for the
            # whole tick; greedy-only ticks keep the transfer-free
            # device argmax path
            with _span("collm.sample"):
                nxt = nxt.copy()    # device-backed arrays are read-only
                host_rows = np.asarray(logits[:, -1])  # lint: host-sync-ok one batched logits pull per sampling tick
                for i in active:
                    req = self.slot_req[i]
                    if req.samples:
                        nxt[i] = sample_token(
                            host_rows[i],
                            temperature=req.temperature,
                            top_k=req.top_k, top_p=req.top_p,
                            rng=req.rng)
        with _span("collm.emit"):
            for i in active:
                req = self.slot_req[i]
                req.tokens.append(int(nxt[i]))
                self.stats.generated_tokens += 1
                self.slot_pos[i] += 1
                self.slot_tok[i] = nxt[i]
                if len(req.tokens) >= req.max_new_tokens \
                        or int(nxt[i]) == self.eos_id:
                    self._record_finish(req, now)
                    self._evict(i)
                    finished.append(req)
        return finished, len(active)

    def _wave_tables(self, active: List[int]) -> jax.Array:
        """Paged decode: grow each active slot's table to cover its next
        write and return the device block tables at the wave's bucketed
        width (re-sent only when a table or the width changed)."""
        self._grow_tables(active)
        width = self._table_width(active)
        if self._dev_tables is None \
                or self._dev_tables_width != width:
            tbl = self.block_tables[:, :width]
            pref = self.prefilling_slots()
            if pref:
                # park mid-prefill slots on scratch block 0: the paged
                # write index CLAMPS out-of-range table lookups, so a
                # live row here would let the parked slot's garbage
                # decode write corrupt a real block
                tbl = tbl.copy()
                tbl[pref, :] = 0
            self._dev_tables = jax.device_put(tbl, self.device)
            self._dev_tables_width = width
        return self._dev_tables

    def _dispatch(self, active: List[int], tables: Optional[jax.Array],
                  train_batch: Optional[Dict[str, Any]],
                  tt: Optional[int]) -> jax.Array:
        """Send the decode wave's inputs and run the decode program or,
        with a planned train leg (``tt`` not None), the fused decode +
        train program; returns the wave's logits (on the device)."""
        toks = jnp.asarray(self.slot_tok[:, None])
        pos = jnp.asarray(self.slot_pos)
        # registry mode: per-slot device adapter slots for the segmented
        # decode paths (inactive / base-only rows select -1 -> bitwise
        # base output); without a registry the kwargs stay absent so the
        # single-adapter traces are untouched
        if self.adapters is not None:
            idx = np.full(self.n_slots, -1, np.int32)
            for i in active:
                aid = self.slot_aid[i]
                if aid is not None:
                    idx[i] = self.adapters.slot_index(aid)
            serve_idx = jnp.asarray(idx)
            dec_kw = {"adapter_idx": serve_idx}
            comb_kw = {"serve_adapter_idx": serve_idx}
        else:
            dec_kw = {}
            comb_kw = {}
        if train_batch is not None and tt is not None:
            if self.paged:
                (new_tl, self.opt_state, logits, self.caches,
                 metrics) = self._jit_combined_paged(
                    self.params, self._train_adapter(), self.opt_state,
                    train_batch, self.caches, toks, pos, tables,
                    ring_len=self.ring_len, serve_lora=self._serve_lora(),
                    attn_backend=self.attn_backend,
                    grad_accum=self.train_grad_accum,
                    train_tokens=tt, **comb_kw)
            else:
                (new_tl, self.opt_state, logits, self.caches,
                 metrics) = self._jit_combined(
                    self.params, self._train_adapter(), self.opt_state,
                    train_batch, self.caches, toks, pos,
                    serve_lora=self._serve_lora(),
                    attn_backend=self.attn_backend,
                    grad_accum=self.train_grad_accum,
                    train_tokens=tt, **comb_kw)
            self._store_trained(new_tl)
            self._record_train(metrics)
            self.last_tick_trained = True
        elif self.paged:
            logits, self.caches = self._jit_decode_paged(
                self.params, self._serve_lora(), self.caches, toks, pos,
                tables, ring_len=self.ring_len,
                attn_backend=self.attn_backend, **dec_kw)
        else:
            logits, self.caches = self._jit_decode(
                self.params, self._serve_lora(), self.caches, toks, pos,
                attn_backend=self.attn_backend, **dec_kw)
        return logits

    def _sanitize_wave(self, active: List[int]) -> None:
        """REPRO_SANITIZE=1 only (``_lsan`` gates the call): verify the
        wave the decode program is about to consume — every slot holds
        an ACTIVE request, every gathered block is live, every write
        target is private and non-scratch, reservations balance, and
        every routed adapter slot is pinned, resident and not
        mid-publish."""
        self._lsan.check_decode_wave(self, active)
        if self.paged and self.allocator.san is not None:
            self.allocator.san.check_decode_wave(self, active)
        if self.adapters is not None and self.adapters.san is not None:
            self.adapters.san.check_decode_wave(self, active)

    def _evict(self, i: int) -> None:
        """Free slot ``i`` completely: request pointer, ragged position
        AND feed token (a stale ``slot_tok`` would leak the previous
        request's last token into the next admission's first tick), plus
        the slot's blocks and any unused reservation in paged mode."""
        self.slot_req[i] = None
        self.slot_pos[i] = 0
        self.slot_tok[i] = 0
        self.slot_prefilled[i] = 0
        self.slot_cached[i] = 0
        self.slot_goal[i] = 0
        self.slot_seq[i] = None
        self.slot_restore_tok[i] = -1
        if self.slot_aid[i] is not None:
            # unpin the request's adapter — without this the registry
            # leaks a ref per request and eventually deadlocks admission
            self.adapters.release(self.slot_aid[i])
            self.slot_aid[i] = None
        if self.paged:
            self.allocator.free(self.slot_blocks[i])
            self.slot_blocks[i] = []
            self.allocator.release(int(self.slot_reserved[i]))
            self.slot_reserved[i] = 0
            self.block_tables[i, :] = 0   # back to scratch block 0
            self._dev_tables = None
            if self.allocator.san is not None:
                self.allocator.san.check_evicted(self, i)

    def drain_all(self) -> List[GenRequest]:
        """Failover teardown: evict every active slot, clear the queue,
        and return all unfinished requests (their partial tokens are
        discarded — a survivor regenerates from the prompt).  In paged
        mode every slot's blocks and reservations return to the
        allocator, so ``allocator.n_used`` drops to 0."""
        out: List[GenRequest] = list(self.queue)
        self.queue.clear()
        for i in self.active_slots():
            req = self.slot_req[i]
            self._evict(i)
            out.append(req)
        for e in self._swapped:
            # parked requests still hold their kept-chain block refs
            # and their adapter pin — return both before draining
            if e.kept:
                self.allocator.free(e.kept)
            if e.adapter_id is not None and self.adapters is not None:
                self.adapters.release(e.adapter_id)
            out.append(e.req)
        self._swapped.clear()
        for r in out:
            r.tokens.clear()
            r.first_token_at = None
            r.admitted_wall = r.first_token_wall = None
            r.rng = None
            if self._lsan is not None:
                self._lsan.on_drain(r)
        if self.paged and self.allocator.san is not None:
            self.allocator.san.check_quiescent(self)
        return out

    def _train_adapter(self) -> Any:
        """The tree the optimizer steps: the staged shadow during a
        train session, the published adapter otherwise (in-place
        continuous adaptation); decode/prefill ALWAYS read
        ``self.lora``."""
        return self.train_lora if self.train_lora is not None \
            else self.lora

    def _store_trained(self, new_tl: Any) -> None:
        if self.train_lora is not None:
            self.train_lora = new_tl
        else:
            self.lora = new_tl

    def _plain_train(self, train_batch, train_tokens: int = 0) -> None:
        new_tl, self.opt_state, metrics = self._jit_train(
            self.params, self._train_adapter(), self.opt_state,
            train_batch, grad_accum=self.train_grad_accum,
            train_tokens=train_tokens)
        self._store_trained(new_tl)
        self._record_train(metrics)

    def _record_budget(self, spent_s: float) -> None:
        """Per-tick budget telemetry (tpot_target > 0 only)."""
        if self.budget is None:
            return
        self.stats.budget_ticks += 1
        self.stats.budget_target_s += self.budget.target_s
        self.stats.budget_spent_s += spent_s

    def _record_train(self, metrics: Dict[str, Any]) -> None:
        """One host sync per train tick: loss history + the scalar
        gradient stats the noise-scale estimator consumes."""
        host = jax.device_get(metrics)  # lint: host-sync-ok one batched metrics pull per train tick
        self.last_train_metrics = {
            "ce_loss": float(host["ce_loss"]),
            "micro_grad_sqnorm": float(host["micro_grad_sqnorm"]),
            "grad_sqnorm": float(host["grad_sqnorm"]),
        }
        loss = self.last_train_metrics["ce_loss"]
        self.train_losses.append(loss)
        self.stats.train_loss = loss
        self.stats.train_steps += 1

    # ------------------------------------------------------------------ run -
    def run(self, requests: Sequence[GenRequest],
            train_data_fn: Optional[Callable[[], Dict[str, Any]]] = None
            ) -> ServeStats:
        """Drain ``requests`` to completion; with ``train_data_fn``,
        every tick co-runs a fused training step."""
        for r in requests:
            self.submit(r)
        t0 = time.perf_counter()
        while not self.idle():
            tb = train_data_fn() if train_data_fn is not None else None
            self.step(train_batch=tb, now=time.perf_counter() - t0)
        self.stats.wall_time += time.perf_counter() - t0
        return self.stats

    # ---------------------------------------------------------- telemetry --
    def cache_bytes(self) -> int:
        """Allocated KV cache bytes (pool + tables)."""
        total = sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(self.caches))
        if self.paged:
            total += self.block_tables.nbytes
        return total


# ========================================================================
# Lock-step static-batch baseline
# ========================================================================
def static_batch_serve(engine, params, lora, requests: Sequence[GenRequest],
                       *, batch_size: int = 8, prompt_pad: int = 32,
                       max_seq: int = 128,
                       eos_id: Optional[int] = None) -> ServeStats:
    """The pre-continuous-batching serving loop: group requests into
    fixed batches, prefill the batch, then decode lock-step until every
    request in the batch finishes (max_new_tokens or EOS) — short /
    early-EOS requests ride along as dead slots.  Same greedy math and
    the same EOS rule as ``ContinuousBatcher`` (equivalence-tested), so
    throughput differences are pure scheduling."""
    model = engine.model
    cfg = model.cfg
    assert not cfg.has_ssm and cfg.family.value != "vlm", \
        "baseline supports attention-only stacks"
    jits = _engine_jits(engine)
    jit_prefill = jits["prefill_ragged"]
    jit_decode = jits["decode"]
    stats = ServeStats()
    t0 = time.perf_counter()

    def finish(r: GenRequest) -> None:
        r.finished_at = time.perf_counter() - t0
        r.finished_wall = time.perf_counter()
        stats.finished += 1

    reqs = list(requests)
    for lo in range(0, len(reqs), batch_size):
        batch = reqs[lo:lo + batch_size]
        bsz = len(batch)
        lens = np.array([len(r.prompt) for r in batch], np.int32)
        padded = np.zeros((bsz, prompt_pad), np.int32)
        for i, r in enumerate(batch):
            padded[i, :lens[i]] = r.prompt
            r.max_new_tokens = max(
                1, min(r.max_new_tokens, max_seq - lens[i]))
        logits, pre = jit_prefill(params, lora,
                                  {"tokens": jnp.asarray(padded)},
                                  jnp.asarray(lens))
        caches = model.init_caches(bsz, max_seq)
        caches = jax.tree.map(
            lambda pool, p: jax.lax.dynamic_update_slice(
                pool, p.astype(pool.dtype), (0,) * pool.ndim),
            caches, {"kv": pre["kv"]})
        toks = np.asarray(  # lint: host-sync-ok one batched argmax pull per prefill batch
            jnp.argmax(logits[:, -1], axis=-1), np.int32)
        pos = lens.copy()
        stats.admitted += bsz
        stats.prefill_tokens += int(lens.sum())
        for i, r in enumerate(batch):
            r.tokens.append(int(toks[i]))
            stats.generated_tokens += 1
            if len(r.tokens) >= r.max_new_tokens \
                    or int(toks[i]) == eos_id:
                finish(r)
        # lock-step decode: every slot pays until the batch's LAST
        # request finishes; finished requests are dead weight
        while not all(r.done for r in batch):
            logits, caches = jit_decode(params, lora, caches,
                                        jnp.asarray(toks[:, None]),
                                        jnp.asarray(pos))
            stats.decode_steps += 1
            toks = np.asarray(  # lint: host-sync-ok one batched argmax pull per decode step
                jnp.argmax(logits[:, -1], axis=-1), np.int32)
            pos += 1
            for i, r in enumerate(batch):
                if r.done:
                    continue
                r.tokens.append(int(toks[i]))
                stats.generated_tokens += 1
                if len(r.tokens) >= r.max_new_tokens \
                        or int(toks[i]) == eos_id:
                    finish(r)
    stats.wall_time += time.perf_counter() - t0
    return stats
