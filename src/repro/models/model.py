"""Unified model: one ``Model`` object per ModelConfig, covering all five
assigned families with the same public surface:

  init(key) -> params                       init_lora(key) -> adapters
  forward_loss(params, lora, batch)         (training objective)
  prefill(params, lora, batch)              -> (logits_last, caches)
  decode_step(params, lora, caches, token, pos) -> (logits, caches)
  init_caches(batch, seq)                   (KV / SSM / cross-KV caches)
  input_specs(cell)                         ShapeDtypeStruct stand-ins

Layer stacks run through ``jax.lax.scan`` over stacked params so compile
time and HLO size are O(1) in depth (grok's 64 layers and the VLM's 100
layers compile like a 1-layer model).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import Family, ModelConfig, ShapeCell
from repro.models import lora as lora_lib
from repro.models import mamba2, transformer as tfm
from repro.models.layers import dense_init, rms_norm, rope_tables
from repro.models.sharding import shard


# ------------------------------------------------------------------ loss ---
def chunked_ce_loss(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                    mask: jax.Array, chunk: int = 512
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy over a vocab head without materializing [B,S,V] f32:
    scans seq chunks, rematerializing logits in the backward pass."""
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    nc = s // chunk
    rem = s - nc * chunk

    def chunk_loss(h, y, m):
        logits = (h @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - ll) * m), jnp.sum(m)

    chunk_loss = jax.checkpoint(chunk_loss)

    def body(carry, xs):
        tot, cnt = carry
        l, c = chunk_loss(*xs)
        return (tot + l, cnt + c), None

    hs = hidden[:, :nc * chunk].reshape(b, nc, chunk, d).swapaxes(0, 1)
    ys = labels[:, :nc * chunk].reshape(b, nc, chunk).swapaxes(0, 1)
    ms = mask[:, :nc * chunk].reshape(b, nc, chunk).swapaxes(0, 1)
    (tot, cnt), _ = lax.scan(body, (jnp.zeros((), jnp.float32),
                                    jnp.zeros((), jnp.float32)), (hs, ys, ms))
    if rem:
        l, c = chunk_loss(hidden[:, nc * chunk:], labels[:, nc * chunk:],
                          mask[:, nc * chunk:])
        tot, cnt = tot + l, cnt + c
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss, {"loss_sum": tot, "token_count": cnt}


@jax.named_scope("lm_head")
def _lm_head(hidden: jax.Array, params: Dict) -> jax.Array:
    """Vocabulary logits of ``hidden`` (a scope of its own in a trace)."""
    return hidden @ params["lm_head"]


def _scan_or_loop(body, init, xs):
    """Unrolled drop-in for lax.scan over stacked-leading-dim xs trees.
    Used by cost-calibration compiles (scan_layers=False): XLA's
    HLOCostAnalysis counts a while-loop body once regardless of trip
    count, so the dry-run measures FLOPs on unrolled small-depth
    variants and extrapolates (see launch/dryrun.py)."""
    length = jax.tree.leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys)
    return carry, stacked


# ----------------------------------------------------------------- model ---
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # --------------------------------------------------------------- init --
    def init(self, key) -> Dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        k_embed, k_blocks, k_cross, k_head = jax.random.split(key, 4)
        params: Dict[str, Any] = {}
        params["embed"] = dense_init(k_embed, cfg.vocab_size, cfg.d_model,
                                     dtype, scale=1.0)
        if cfg.family is Family.VLM:
            units, per = self._vlm_shape()
            bkeys = jax.random.split(k_blocks, units * per).reshape(
                units, per)
            params["blocks"] = jax.vmap(jax.vmap(
                lambda k: tfm.init_block(k, cfg)))(bkeys)
            ckeys = jax.random.split(k_cross, units)
            params["cross"] = jax.vmap(
                lambda k: tfm.init_cross_block(k, cfg))(ckeys)
        else:
            bkeys = jax.random.split(k_blocks, cfg.n_layers)
            params["blocks"] = jax.vmap(
                lambda k: tfm.init_block(k, cfg))(bkeys)
        params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                       dtype)
        return params

    def init_lora(self, key) -> Dict:
        cfg = self.cfg
        if cfg.family is Family.VLM:
            units, per = self._vlm_shape()
            tree = lora_lib.init_lora(key, cfg, units * per)
            return jax.tree.map(
                lambda x: x.reshape((units, per) + x.shape[1:]), tree)
        return lora_lib.init_lora(key, cfg, cfg.n_layers)

    def _vlm_shape(self) -> Tuple[int, int]:
        cfg = self.cfg
        units = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        return units, per

    # ------------------------------------------------------------ forward --
    def _embed(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.encoder_only and "embeds" in batch:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        return shard(x, "batch", "act_seq", "embed")

    def hidden_states(self, params, lora, batch, *, collect_caches=False,
                      block_kv: int = 512, skip_masked_blocks: bool = False,
                      adapter_idx=None):
        """Full-sequence forward.  Returns (hidden, caches|None, aux).

        ``adapter_idx`` [B] int32 (optional) selects each row's adapter
        slot from a STACKED multi-adapter lora tree (leaves
        [L, A, din, r]); < 0 disables the bypass for that row."""
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        rope_cs = rope_tables(jnp.arange(s), cfg.head_dim, cfg.rope_theta) \
            if cfg.has_attention else None

        def body_fn(xc, xs):
            bp, lslice = xs
            y, (kv, ssm_final, aux) = tfm.block_full(
                bp, xc, cfg, rope_cs, lora=lslice, block_kv=block_kv,
                skip_masked_blocks=skip_masked_blocks,
                adapter_idx=adapter_idx)
            outs = (kv, ssm_final, aux) if collect_caches else (None, None, aux)
            return y, outs

        body_fn = tfm.remat_wrap(body_fn, cfg)
        scan = _scan_or_loop if not cfg.scan_layers else lax.scan

        if cfg.family is Family.VLM:
            vis = batch["vision"].astype(x.dtype)
            units, per = self._vlm_shape()

            def unit_fn(xc, xs):
                ublocks, ulora, ucross = xs

                def inner(xc2, xs2):
                    return body_fn(xc2, xs2)

                xc, outs = scan(inner, xc, (ublocks, ulora))
                vkv = tfm.vision_kv(ucross["attn"], vis, cfg)
                xc = tfm.cross_block(ucross, xc, vkv, cfg)
                couts = (vkv if collect_caches else None)
                return xc, (outs, couts)

            x, (outs, cross_kv) = scan(
                unit_fn, x, (params["blocks"], lora, params["cross"]))
            kvs, ssm_finals, auxs = outs
            aux = jnp.sum(auxs)
            caches = None
            if collect_caches:
                caches = {"kv": kvs, "cross_kv": cross_kv}
        else:
            x, (kvs, ssm_finals, auxs) = scan(
                body_fn, x, (params["blocks"], lora))
            aux = jnp.sum(auxs)
            caches = None
            if collect_caches:
                caches = {}
                if cfg.has_attention:
                    caches["kv"] = kvs
                if cfg.has_ssm:
                    caches["ssm"] = ssm_finals  # stacked {"conv","state"}
        hidden = rms_norm(x, params["final_norm"])
        return hidden, caches, aux

    # --------------------------------------------------------------- loss --
    def forward_loss(self, params, lora, batch, *, ce_chunk: int = 512,
                     block_kv: int = 512, skip_masked_blocks: bool = False):
        hidden, _, aux = self.hidden_states(
            params, lora, batch, block_kv=block_kv,
            skip_masked_blocks=skip_masked_blocks)
        loss, metrics = chunked_ce_loss(
            hidden, params["lm_head"], batch["labels"],
            batch["mask"].astype(jnp.float32), chunk=ce_chunk)
        metrics["aux_loss"] = aux
        total = loss + 0.01 * aux
        metrics["ce_loss"] = loss
        return total, metrics

    def logits(self, params, lora, batch):
        """Full-vocab logits for the whole sequence (smoke-scale only)."""
        hidden, _, _ = self.hidden_states(params, lora, batch)
        return _lm_head(hidden, params)

    # ------------------------------------------------------------- caches --
    def init_caches(self, batch: int, seq: int, dtype=None) -> Dict:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
        hd, hkv = cfg.head_dim, cfg.n_kv_heads
        caches: Dict[str, Any] = {}
        if cfg.family is Family.VLM:
            units, per = self._vlm_shape()
            caches["kv"] = (
                jnp.zeros((units, per, batch, seq, hkv, hd), dtype),
                jnp.zeros((units, per, batch, seq, hkv, hd), dtype))
            caches["cross_kv"] = (
                jnp.zeros((units, batch, cfg.vision_tokens, hkv, hd), dtype),
                jnp.zeros((units, batch, cfg.vision_tokens, hkv, hd), dtype))
            return caches
        if cfg.has_attention:
            # sliding-window archs keep a ring buffer of window size —
            # this is what makes the long_500k hymba cell fit (21 GB of
            # flat cache would not).
            kv_seq = seq if cfg.sliding_window == 0 \
                else min(seq, cfg.sliding_window)
            caches["kv"] = (
                jnp.zeros((cfg.n_layers, batch, kv_seq, hkv, hd), dtype),
                jnp.zeros((cfg.n_layers, batch, kv_seq, hkv, hd), dtype))
        if cfg.has_ssm:
            c = mamba2.init_ssm_cache(cfg, batch, dtype,
                                      stacked=cfg.n_layers)
            caches["ssm"] = c._asdict()
        return caches

    def init_paged_caches(self, n_blocks: int, block_size: int,
                          dtype=None) -> Dict:
        """Global paged KV pool: [L, n_blocks, block_size, Hkv, Dh] per
        K/V.  Sequences map logical positions to pool blocks through
        per-slot block tables (see ``decode_step_paged``), so cache
        memory scales with allocated blocks, not slots * max_seq.
        Attention-only stacks: SSM/conv state is per-slot and tiny —
        paging it buys nothing."""
        cfg = self.cfg
        assert cfg.has_attention and not cfg.has_ssm \
            and cfg.family is not Family.VLM, \
            f"{cfg.name}: paged KV caches need an attention-only stack"
        dtype = dtype or jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
        shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
                 cfg.head_dim)
        return {"kv": (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))}

    # -------------------------------------------------------------- prefill -
    def prefill(self, params, lora, batch, *, block_kv: int = 512,
                skip_masked_blocks: bool = False):
        """Process the prompt; returns (last-token logits, caches)."""
        cfg = self.cfg
        hidden, caches, _ = self.hidden_states(
            params, lora, batch, collect_caches=True, block_kv=block_kv,
            skip_masked_blocks=skip_masked_blocks)
        logits = _lm_head(hidden[:, -1:], params)
        out_caches: Dict[str, Any] = {}
        if caches and caches.get("kv") is not None:
            out_caches["kv"] = caches["kv"]
        if caches and caches.get("cross_kv") is not None:
            out_caches["cross_kv"] = caches["cross_kv"]
        if cfg.has_ssm and caches and caches.get("ssm") is not None:
            out_caches["ssm"] = caches["ssm"]  # conv tail + final SSD state
        return logits, out_caches

    def prefill_ragged(self, params, lora, batch, prompt_lens, *,
                       block_kv: int = 512,
                       skip_masked_blocks: bool = False,
                       adapter_idx=None):
        """Prefill right-padded ragged prompts in one batch.

        ``prompt_lens`` [B] int32 gives each row's true prompt length;
        logits are gathered at each row's *last real token* rather than
        the (padded) final position.  Valid for attention-only stacks:
        causal masking keeps pad tokens out of every real position's KV,
        and cache rows past ``prompt_lens`` are dead weight masked by the
        per-slot kv_len at decode time.  SSM/hybrid recurrences thread
        state through pads, so those families must prefill exact-length
        (see runtime/serving_loop.py)."""
        cfg = self.cfg
        assert not cfg.has_ssm, \
            f"{cfg.name}: ragged (padded) prefill breaks SSM recurrence"
        hidden, caches, _ = self.hidden_states(
            params, lora, batch, collect_caches=True, block_kv=block_kv,
            skip_masked_blocks=skip_masked_blocks,
            adapter_idx=adapter_idx)
        idx = (prompt_lens - 1).astype(jnp.int32)[:, None, None]
        last = jnp.take_along_axis(
            hidden, jnp.broadcast_to(idx, (hidden.shape[0], 1,
                                           hidden.shape[2])), axis=1)
        logits = _lm_head(last, params)
        out_caches: Dict[str, Any] = {}
        if caches and caches.get("kv") is not None:
            out_caches["kv"] = caches["kv"]
        if caches and caches.get("cross_kv") is not None:
            out_caches["cross_kv"] = caches["cross_kv"]
        return logits, out_caches

    # ---------------------------------------------------------- slot ops ---
    def write_prefill_slot(self, pool_caches, prefill_caches, slot,
                           src=0):
        """Copy sequence ``src`` of a prefill cache into decode slot
        ``slot`` of a pool cache (continuous batching admission).

        Every non-VLM cache leaf is laid out [L, B, ...]; row ``src`` of
        the prefill leaf has trailing dims <= the pool's (shorter prompt
        into a longer slot), so a single dynamic_update_slice at batch
        index ``slot`` covers KV rings, conv tails and SSD states alike.
        Cache rows beyond the prompt keep stale bytes — never attended,
        because the slot's kv_len masks them until decode overwrites
        them in order."""
        assert self.cfg.family is not Family.VLM, \
            "VLM cache slots (units-leading layout) are future work"

        def write(pool, pre):
            row = lax.dynamic_slice_in_dim(
                pre, jnp.asarray(src, jnp.int32), 1, axis=1)
            start = (jnp.int32(0), jnp.asarray(slot, jnp.int32)) \
                + (jnp.int32(0),) * (pool.ndim - 2)
            return lax.dynamic_update_slice(
                pool, row.astype(pool.dtype), start)

        return jax.tree.map(write, pool_caches, prefill_caches)

    def write_prefill_slots(self, pool_caches, prefill_caches, slots):
        """Batched admission: scatter a whole prefill wave into its
        decode slots in ONE program (vs one ``write_prefill_slot`` call
        per request).  ``slots`` [W] int32 gives row j's target slot;
        rows flagged with slot id >= n_slots are dropped (requests that
        finished at admission).  Attention ragged-wave path only, so
        every leaf is a KV cache [L, B, S, Hkv, Dh]; wave rows shorter
        than the pool's seq dim are zero-padded — those rows are masked
        by the slot's kv_len until decode overwrites them in order."""
        assert self.cfg.family is not Family.VLM, \
            "VLM cache slots (units-leading layout) are future work"
        slots = jnp.asarray(slots, jnp.int32)

        def write(pool, pre):
            p, s = pre.shape[2], pool.shape[2]
            if p < s:
                widths = [(0, 0)] * pre.ndim
                widths[2] = (0, s - p)
                pre = jnp.pad(pre, widths)
            return pool.at[:, slots].set(pre.astype(pool.dtype),
                                         mode="drop")

        return jax.tree.map(write, pool_caches, prefill_caches)

    def write_prefill_blocks(self, pool_caches, prefill_caches,
                             wave_tables):
        """Batched paged admission: scatter a whole prefill wave's KV
        into freshly allocated pool blocks in ONE program.

        ``wave_tables`` [W, NBP] int32 maps wave row j's logical blocks
        to pool blocks; unused entries (short prompts, requests finished
        at admission) hold ``n_blocks`` and are dropped by the scatter.
        The wave's right-padded prefill [L, W, P, Hkv, Dh] is reshaped
        to block granularity, so the whole wave lands as one scatter
        per K/V pool leaf."""
        wave_tables = jnp.asarray(wave_tables, jnp.int32)
        nbp = wave_tables.shape[1]
        ids = wave_tables.reshape(-1)

        def write(pool, pre):
            nl, w, p = pre.shape[0], pre.shape[1], pre.shape[2]
            bs = pool.shape[2]
            assert p <= nbp * bs, \
                f"prefill len {p} exceeds wave table coverage {nbp * bs}"
            if p < nbp * bs:
                widths = [(0, 0)] * pre.ndim
                widths[2] = (0, nbp * bs - p)
                pre = jnp.pad(pre, widths)
            vals = pre.reshape(nl, w * nbp, bs, *pre.shape[3:])
            return pool.at[:, ids].set(vals.astype(pool.dtype),
                                       mode="drop")

        return jax.tree.map(write, pool_caches, prefill_caches)

    def prefill_ragged_suffix(self, params, lora, batch, suffix_lens,
                              prefix_lens, caches, prefix_tables,
                              adapter_idx=None):
        """Prefill only the uncached suffix of each prompt (prefix
        sharing over the paged pool).

        ``batch["tokens"]`` [W, SufPad] holds each row's right-padded
        suffix tokens (absolute positions ``prefix_lens[w] + i``);
        ``prefix_tables`` [W, NBpre] int32 names the pool blocks holding
        each row's cached block-aligned prefix (scratch-padded past
        ``prefix_lens[w]`` rows — those lanes are masked).  The prefix
        K/V are gathered from ``caches`` in-program, so the suffix
        attends over cached prefix + its own causal K/V and reproduces
        the full-prefill logits bit-for-bit.  Returns (logits at each
        row's last real suffix token [W,1,V], {"kv": suffix K/V
        [L, W, SufPad, Hkv, Dh]}) for ``write_prefill_blocks`` into the
        suffix's freshly allocated blocks."""
        cfg = self.cfg
        assert cfg.has_attention and not cfg.has_ssm \
            and cfg.family is not Family.VLM, \
            f"{cfg.name}: suffix prefill needs an attention-only stack"
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        x = shard(x, "batch", "act_seq", "embed")
        prefix_lens = jnp.asarray(prefix_lens, jnp.int32)
        suffix_lens = jnp.asarray(suffix_lens, jnp.int32)
        positions = prefix_lens[:, None] + jnp.arange(tokens.shape[1])
        rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        tables = jnp.asarray(prefix_tables, jnp.int32)
        w, nbpre = tables.shape

        def gather(pool):
            out = jnp.take(pool, tables.reshape(-1), axis=1)
            return out.reshape(pool.shape[0], w,
                               nbpre * pool.shape[2], *pool.shape[3:])

        prefix_kv = (gather(caches["kv"][0]), gather(caches["kv"][1]))

        def body(xc, xs):
            bp, lsl, pre = xs
            y, kv = tfm.block_prefill_suffix(bp, xc, cfg, pre,
                                             prefix_lens, rope_cs,
                                             lora=lsl,
                                             adapter_idx=adapter_idx)
            return y, kv

        scan = _scan_or_loop if not cfg.scan_layers else lax.scan
        x, kvs = scan(body, x, (params["blocks"], lora, prefix_kv))
        hidden = rms_norm(x, params["final_norm"])
        idx = (suffix_lens - 1).astype(jnp.int32)[:, None, None]
        last = jnp.take_along_axis(
            hidden, jnp.broadcast_to(idx, (hidden.shape[0], 1,
                                           hidden.shape[2])), axis=1)
        logits = _lm_head(last, params)
        return logits, {"kv": kvs}

    def prefill_ragged_continue(self, params, lora, batch, suffix_lens,
                                prefix_lens, caches, slot_ids,
                                adapter_idx=None):
        """Resumable chunked prefill over CONTIGUOUS slot caches: one
        fixed-budget chunk per call, attending over the K/V the slot's
        earlier chunks already wrote.

        ``batch["tokens"]`` [W, CPad] holds each row's right-padded
        chunk tokens (absolute positions ``prefix_lens[w] + i``);
        ``slot_ids`` [W] int32 names each row's decode slot, whose cache
        rows ``0 .. prefix_lens[w]-1`` hold the prefix written by chunks
        1..K-1 (rows past that are stale and masked).  Same dense-mirror
        softmax as ``prefill_ragged_suffix``, so chunked prefill
        reproduces the monolithic logits bit-for-bit.  Returns (logits
        at each row's last real chunk token [W,1,V], {"kv": chunk K/V
        [L, W, CPad, Hkv, Dh]}) for ``write_prefill_rows`` back into the
        slots at offset ``prefix_lens``."""
        cfg = self.cfg
        assert cfg.has_attention and not cfg.has_ssm \
            and cfg.family is not Family.VLM, \
            f"{cfg.name}: chunked prefill needs an attention-only stack"
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        x = shard(x, "batch", "act_seq", "embed")
        prefix_lens = jnp.asarray(prefix_lens, jnp.int32)
        suffix_lens = jnp.asarray(suffix_lens, jnp.int32)
        positions = prefix_lens[:, None] + jnp.arange(tokens.shape[1])
        rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        slots = jnp.asarray(slot_ids, jnp.int32)

        def gather(cache):
            # [L, B, S, Hkv, Dh] -> this wave's slot rows [L, W, S, ...]
            return jnp.take(cache, slots, axis=1)

        prefix_kv = (gather(caches["kv"][0]), gather(caches["kv"][1]))

        def body(xc, xs):
            bp, lsl, pre = xs
            y, kv = tfm.block_prefill_suffix(bp, xc, cfg, pre,
                                             prefix_lens, rope_cs,
                                             lora=lsl,
                                             adapter_idx=adapter_idx)
            return y, kv

        scan = _scan_or_loop if not cfg.scan_layers else lax.scan
        x, kvs = scan(body, x, (params["blocks"], lora, prefix_kv))
        hidden = rms_norm(x, params["final_norm"])
        idx = (suffix_lens - 1).astype(jnp.int32)[:, None, None]
        last = jnp.take_along_axis(
            hidden, jnp.broadcast_to(idx, (hidden.shape[0], 1,
                                           hidden.shape[2])), axis=1)
        logits = _lm_head(last, params)
        return logits, {"kv": kvs}

    def write_prefill_rows(self, pool_caches, prefill_caches, slots,
                           offsets, lens):
        """Scatter one chunked-prefill wave's K/V into contiguous slot
        caches at each row's resume offset, in ONE program.

        ``slots``/``offsets``/``lens`` [W] int32: row j's chunk K/V
        [L, W, CPad, Hkv, Dh] lands at cache rows
        ``offsets[j] .. offsets[j]+lens[j]-1`` of slot ``slots[j]``;
        pad positions past ``lens[j]`` are pushed out of range and
        dropped, as are rows flagged with slot id >= n_slots."""
        slots = jnp.asarray(slots, jnp.int32)
        offsets = jnp.asarray(offsets, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)

        def write(pool, pre):
            c, s = pre.shape[2], pool.shape[2]
            pos = offsets[:, None] + jnp.arange(c)            # [W, C]
            pos = jnp.where(jnp.arange(c)[None, :] < lens[:, None],
                            pos, s)                           # pads -> drop
            # slots[:,None] broadcasts with pos at adjacent axes 1,2, so
            # the result matches pre's [L, W, C, Hkv, Dh] layout
            return pool.at[:, slots[:, None], pos].set(
                pre.astype(pool.dtype), mode="drop")

        return jax.tree.map(write, pool_caches, prefill_caches)

    def copy_blocks(self, paged_caches, src_ids, dst_ids):
        """Copy-on-write: duplicate whole pool blocks ``dst := src`` in
        ONE gather+scatter per K/V leaf.  The runtime batches every COW
        of a tick (shared block about to take a decode write) into one
        call."""
        src = jnp.asarray(src_ids, jnp.int32)
        dst = jnp.asarray(dst_ids, jnp.int32)

        def cp(pool):
            return pool.at[:, dst].set(jnp.take(pool, src, axis=1))

        k, v = paged_caches["kv"]
        return {"kv": (cp(k), cp(v))}

    def gather_blocks(self, paged_caches, ids):
        """Preemption swap-out: pull whole pool blocks ``ids`` out of
        the pool in ONE gather per K/V leaf — the device half of a
        batched device->host copy (the caller ``device_get``s the
        result).  Pad entries may repeat a real id (e.g. 0/scratch);
        the host side slices the real rows off."""
        ids = jnp.asarray(ids, jnp.int32)

        def g(pool):
            return jnp.take(pool, ids, axis=1)

        k, v = paged_caches["kv"]
        return {"kv": (g(k), g(v))}

    def scatter_blocks(self, paged_caches, ids, host_kv):
        """Preemption swap-in: land host-side block contents back into
        freshly taken pool blocks ``ids`` in ONE scatter per K/V leaf.
        Pad entries hold ``n_blocks`` and are dropped, so one bucketed
        program shape serves every restore width."""
        ids = jnp.asarray(ids, jnp.int32)

        def s(pool, vals):
            return pool.at[:, ids].set(
                jnp.asarray(vals).astype(pool.dtype), mode="drop")

        k, v = paged_caches["kv"]
        hk, hv = host_kv
        return {"kv": (s(k, hk), s(v, hv))}

    # --------------------------------------------------------------- decode -
    def decode_step(self, params, lora, caches, token, pos, *,
                    attn_backend: Optional[str] = None,
                    adapter_idx=None):
        """One decode step.  token: [B,1] int32; pos: scalar int32 (next
        write position, shared) or [B] int32 (per-sequence positions —
        ragged decode slots for continuous batching).  ``attn_backend``
        (static) picks the decode-attention path — Pallas on TPU, jnp
        elsewhere.  ``adapter_idx`` [B] int32 (optional) selects each
        row's adapter slot from a STACKED multi-adapter ``lora`` tree
        (leaves [L, A, din, r]; < 0 = base only) — the multi-tenant
        decode wave.  Returns (logits [B,1,V], updated caches)."""
        cfg = self.cfg
        pos = jnp.asarray(pos)
        x = jnp.take(params["embed"], token, axis=0)
        x = shard(x, "batch", None, "embed")
        rope_cs = None
        if cfg.has_attention:
            # scalar pos -> [1, Dh/2] tables broadcast over the batch;
            # vector pos -> [B, 1, Dh/2] per-sequence tables
            rope_pos = pos[None] if pos.ndim == 0 else pos[:, None]
            rope_cs = rope_tables(rope_pos, cfg.head_dim, cfg.rope_theta)

        scan = _scan_or_loop if not cfg.scan_layers else lax.scan

        if cfg.family is Family.VLM:
            units, per = self._vlm_shape()

            def unit_fn(xc, xs):
                ublocks, ulora, ucross, ukv, uckv = xs

                def inner(xc2, xs2):
                    bp, lsl, kvl = xs2
                    y, nc = tfm.block_decode(bp, xc2, cfg, {"kv": kvl},
                                             pos, rope_cs, lora=lsl,
                                             backend=attn_backend)
                    return y, nc["kv"]

                xc, new_kv = scan(inner, xc, (ublocks, ulora, ukv))
                xc = tfm.cross_block(ucross, xc, uckv, cfg)
                return xc, new_kv

            x, new_kv = scan(
                unit_fn, x, (params["blocks"], lora, params["cross"],
                             caches["kv"], caches["cross_kv"]))
            new_caches = {"kv": new_kv, "cross_kv": caches["cross_kv"]}
        else:
            def body(xc, xs):
                bp, lsl, cache_l = xs
                y, nc = tfm.block_decode(bp, xc, cfg, cache_l, pos,
                                         rope_cs, lora=lsl,
                                         backend=attn_backend,
                                         adapter_idx=adapter_idx)
                return y, nc

            cache_tree = {}
            if cfg.has_attention:
                cache_tree["kv"] = caches["kv"]
            if cfg.has_ssm:
                cache_tree["ssm"] = caches["ssm"]
            x, new_caches = scan(body, x,
                                 (params["blocks"], lora, cache_tree))
        hidden = rms_norm(x, params["final_norm"])
        logits = _lm_head(hidden, params)
        return logits, new_caches

    def decode_step_paged(self, params, lora, caches, token, pos,
                          block_tables, *, ring_len: int = 0,
                          attn_backend: Optional[str] = None,
                          adapter_idx=None):
        """One decode step over the paged KV pool.

        caches: ``init_paged_caches`` tree; token: [B,1] int32; pos: [B]
        int32 absolute positions (RoPE uses these); block_tables:
        [B, NB] int32 (entries past a sequence's live blocks must point
        at a valid pool block — the runtime keeps them at scratch block
        0).  ``ring_len`` (static) is the logical cache length for
        sliding-window archs: writes wrap at ``ring_len`` exactly like
        the contiguous ring buffer, so greedy outputs are identical; 0
        means no wrap (full attention, table covers the whole budget).
        Returns (logits [B,1,V], updated caches)."""
        cfg = self.cfg
        assert cfg.has_attention and not cfg.has_ssm \
            and cfg.family is not Family.VLM, \
            f"{cfg.name}: paged decode needs an attention-only stack"
        k_pool = caches["kv"][0]
        bs = k_pool.shape[2]
        block_tables = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            pos = jnp.full((token.shape[0],), pos, jnp.int32)
        rl = ring_len if ring_len else block_tables.shape[1] * bs
        wpos = jnp.remainder(pos, rl)
        kv_len = jnp.minimum(pos + 1, rl)
        write_block = jnp.take_along_axis(
            block_tables, (wpos // bs)[:, None], axis=1)[:, 0]
        write_off = wpos % bs

        x = jnp.take(params["embed"], token, axis=0)
        x = shard(x, "batch", None, "embed")
        rope_cs = rope_tables(pos[:, None], cfg.head_dim, cfg.rope_theta)
        scan = _scan_or_loop if not cfg.scan_layers else lax.scan

        # the stacked pool rides in the carry: each layer writes its
        # token in place and the kernel reads (layer, block) straight out
        # of the stack, so no layer's pool is sliced out or stacked back
        def body(carry, xs):
            xc, pool = carry
            bp, lsl, layer = xs
            y, pool = tfm.block_decode_paged(
                bp, xc, cfg, pool, layer, rope_cs, block_tables,
                write_block, write_off, kv_len, lora=lsl,
                backend=attn_backend, adapter_idx=adapter_idx)
            return (y, pool), None

        layers = np.arange(cfg.n_layers, dtype=np.int32)
        (x, new_kv), _ = scan(body, (x, caches["kv"]),
                              (params["blocks"], lora, layers))
        hidden = rms_norm(x, params["final_norm"])
        logits = _lm_head(hidden, params)
        return logits, {"kv": new_kv}

    # ---------------------------------------------------------- input specs -
    def input_specs(self, cell: ShapeCell) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of a cell —
        weak-type-correct, shardable, no device allocation."""
        cfg = self.cfg
        b, s = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        act = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct
        if cell.kind == "train":
            batch = {}
            if cfg.encoder_only:
                batch["embeds"] = sds((b, s, cfg.d_model), act)
            else:
                batch["tokens"] = sds((b, s), i32)
            batch["labels"] = sds((b, s), i32)
            batch["mask"] = sds((b, s), jnp.float32)
            if cfg.family is Family.VLM:
                batch["vision"] = sds((b, cfg.vision_tokens, cfg.d_model), act)
            return {"batch": batch}
        if cell.kind == "prefill":
            batch = {}
            if cfg.encoder_only:
                batch["embeds"] = sds((b, s, cfg.d_model), act)
            else:
                batch["tokens"] = sds((b, s), i32)
            if cfg.family is Family.VLM:
                batch["vision"] = sds((b, cfg.vision_tokens, cfg.d_model), act)
            return {"batch": batch}
        # decode: one new token against caches of length seq
        caches = jax.eval_shape(lambda: self.init_caches(b, s))
        return {
            "caches": caches,
            "token": sds((b, 1), i32),
            "pos": sds((), i32),
        }

    def param_specs(self) -> Dict:
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    def lora_specs(self) -> Dict:
        return jax.eval_shape(lambda: self.init_lora(jax.random.key(0)))


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
