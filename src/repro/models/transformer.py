"""Transformer blocks for every assigned family, consumed via
``jax.lax.scan`` over stacked layer parameters.

Block wiring per family:
  DENSE / MOE / ENCODER : x += attn(norm1(x)); x += mlp|moe(norm2(x))
  SSM (mamba2)          : x += ssm(norm1(x))
  HYBRID (hymba)        : x += mean(attn, ssm)(norm1(x)); x += mlp(norm2(x))
  VLM                   : units of (cross_attn_every-1) self blocks + 1
                          cross-attention block over vision tokens
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import Family, ModelConfig
from repro.models import lora as lora_lib
from repro.models import mamba2
from repro.models.layers import (
    apply_rope, attention_blockwise, attention_decode,
    attention_decode_paged, attention_dense, attention_prefix_suffix,
    dense_init, rms_norm, rope_tables, swiglu,
)
from repro.models.sharding import shard


# --------------------------------------------------------------- params ----
def init_attn(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, h = cfg.d_model, cfg.head_dim
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * h, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * h, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * h, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * h, d, dtype,
                         scale=1.0 / math.sqrt(cfg.n_heads * h)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.n_heads * h,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * h,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * h,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((h,), dtype)
        p["k_norm"] = jnp.ones((h,), dtype)
    return p


def init_mlp(key, cfg: ModelConfig) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    return {"wg": dense_init(ks[0], d, f, dtype),
            "wu": dense_init(ks[1], d, f, dtype),
            "wd": dense_init(ks[2], f, d, dtype)}


def init_block(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if cfg.family is Family.SSM:
        p["ssm"] = mamba2.init_ssm(ks[0], cfg)._asdict()
        return p
    p["attn"] = init_attn(ks[0], cfg)
    if cfg.family is Family.HYBRID:
        p["ssm"] = mamba2.init_ssm(ks[1], cfg)._asdict()
    if cfg.d_ff > 0:
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.family is Family.MOE:
            from repro.models.moe import init_moe
            p["moe"] = init_moe(ks[2], cfg)._asdict()
        else:
            p["mlp"] = init_mlp(ks[2], cfg)
    return p


def init_cross_block(key, cfg: ModelConfig) -> Dict:
    """Cross-attention block (VLM): gated cross-attn + MLP."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attn(ks[0], cfg, cross=True),
        "gate_attn": jnp.zeros((), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": init_mlp(ks[1], cfg),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


# ------------------------------------------------------------- attention ---
# The layer bodies name their phases with ``jax.named_scope`` —
# ``attn_qkv``, ``kv_write``, ``attn``, ``attn_out``, ``mlp`` (and
# ``lm_head`` in models/model.py) — so a device trace's ops say which
# phase of a layer they belong to.  Scopes are op metadata only.
@jax.named_scope("attn_qkv")
def _proj_qkv(p, x, cfg, lora, adapter_idx=None):
    sc = cfg.lora.scaling
    q = lora_lib.apply(x, x @ p["wq"], lora.get("q") if lora else None, sc,
                       adapter_idx)
    k = lora_lib.apply(x, x @ p["wk"], lora.get("k") if lora else None, sc,
                       adapter_idx)
    v = lora_lib.apply(x, x @ p["wv"], lora.get("v") if lora else None, sc,
                       adapter_idx)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    b, s = x.shape[0], x.shape[1]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


@jax.named_scope("attn_out")
def _proj_out(p, o, cfg, lora, adapter_idx=None):
    """Output projection (with LoRA) of attention's [B, S, H, Dh]."""
    o = o.reshape(o.shape[0], o.shape[1], cfg.n_heads * cfg.head_dim)
    return lora_lib.apply(o, o @ p["wo"], lora.get("o") if lora else None,
                          cfg.lora.scaling, adapter_idx)


def use_dense_prefill(cfg: ModelConfig, s: int) -> bool:
    """Whether full-sequence attention at length ``s`` takes the dense
    (full score matrix) path rather than the blockwise online-softmax
    path.  Shared with the serving runtime's prefix-cache gate: suffix
    prefill mirrors the DENSE softmax formulation bit-for-bit, so
    prefix sharing is only sound for configs that prefill densely."""
    return cfg.attn_impl == "dense" or (
        cfg.attn_impl == "auto" and s * s <= 1024 * 1024
        and not cfg.unroll_attn_blocks)


def attn_full(p, x, cfg: ModelConfig, rope_cs, lora=None,
              block_kv: int = 512, skip_masked_blocks: bool = False,
              adapter_idx=None
              ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (training / prefill).  Returns (out, (k, v))
    so prefill can stash the KV cache."""
    q, k, v = _proj_qkv(p, x, cfg, lora, adapter_idx)
    if rope_cs is not None:
        cos, sin = rope_cs
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = shard(q, "batch", "q_seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    causal = not cfg.encoder_only
    s = x.shape[1]
    with jax.named_scope("attn"):
        if use_dense_prefill(cfg, s):
            o = attention_dense(q, k, v, causal=causal,
                                window=cfg.sliding_window)
        else:
            o = attention_blockwise(q, k, v, causal=causal,
                                    window=cfg.sliding_window,
                                    block_kv=block_kv,
                                    skip_masked_blocks=skip_masked_blocks
                                    and causal,
                                    unroll=cfg.unroll_attn_blocks)
    return _proj_out(p, o, cfg, lora, adapter_idx), (k, v)


def attn_decode(p, x, cfg: ModelConfig, cache_kv, pos, rope_cs, lora=None,
                backend=None, adapter_idx=None):
    """One-token attention against a KV cache.

    cache_kv: (k_cache, v_cache) [B,S,Hkv,Dh]; pos: scalar int32 absolute
    position of the new token, or [B] int32 per-sequence positions
    (ragged decode slots — continuous batching).  Sliding-window archs
    keep a *ring buffer* of window size (keys carry absolute RoPE, so
    ring order is irrelevant — attention is permutation-invariant over
    cache slots).  ``backend`` picks the decode-attention path (Pallas
    on TPU, jnp elsewhere; see ``layers.resolve_decode_backend``).
    Returns (out, updated cache)."""
    k_cache, v_cache = cache_kv
    cache_len = k_cache.shape[1]
    ragged = jnp.ndim(pos) > 0
    q, k, v = _proj_qkv(p, x, cfg, lora, adapter_idx)
    if rope_cs is not None:
        cos, sin = rope_cs  # [1, Dh/2] (shared) or [B, 1, Dh/2] (ragged)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    # sequence-sharded flash-decode (shard_map) when the cache's seq dim
    # is mesh-sharded: local write + partial-softmax reduction instead of
    # GSPMD resharding the whole cache around the dynamic write
    from repro.models.sharding import current_mesh, current_rules
    mesh = current_mesh()
    rules = current_rules() if mesh is not None else None
    use_sharded = (
        mesh is not None and rules is not None and not ragged
        and rules.kv_seq in getattr(mesh, "shape", {})
        and cfg.sliding_window == 0
        and cache_len % mesh.shape[rules.kv_seq] == 0)
    if use_sharded:
        from repro.models.layers import attention_decode_seqsharded
        with jax.named_scope("attn"):
            o, k_cache, v_cache = attention_decode_seqsharded(
                q, k, v, k_cache, v_cache, pos)
        return _proj_out(p, o, cfg, lora, adapter_idx), (k_cache, v_cache)

    wpos = lax.rem(pos, cache_len) if cfg.sliding_window > 0 else pos
    with jax.named_scope("kv_write"):
        if ragged:
            # each sequence writes its new K/V at its own cache position
            row_update = jax.vmap(
                lambda c, new, w: lax.dynamic_update_slice_in_dim(
                    c, new, w, axis=0))
            k_cache = row_update(k_cache, k.astype(k_cache.dtype), wpos)
            v_cache = row_update(v_cache, v.astype(v_cache.dtype), wpos)
        else:
            k_cache = lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), wpos, axis=1)
            v_cache = lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), wpos, axis=1)
    kv_len = jnp.minimum(pos + 1, cache_len)
    with jax.named_scope("attn"):
        o = attention_decode(q, k_cache, v_cache, kv_len, backend=backend)
    return _proj_out(p, o, cfg, lora, adapter_idx), (k_cache, v_cache)


def attn_decode_paged(p, x, cfg: ModelConfig, pool_kv, layer, rope_cs,
                      block_tables, write_block, write_off, kv_len,
                      lora=None, backend=None, adapter_idx=None):
    """One-token attention of layer ``layer`` against the stacked paged
    KV block pool.

    pool_kv: (k_pool, v_pool) [L, n_blocks, block_size, Hkv, Dh], every
    layer's pool in one buffer, addressed by (layer, block) and never
    sliced; layer: int32 scalar (traced under the layer scan, a Python
    int when unrolled); block_tables: [B, NB] int32; write_block /
    write_off: [B] int32 pool block id and in-block offset where each
    sequence's new K/V lands (precomputed once per step from the ragged
    positions — ring addressing for sliding-window archs included);
    kv_len: [B] valid logical length AFTER the write.  Returns (out,
    updated pools)."""
    k_pool, v_pool = pool_kv
    q, k, v = _proj_qkv(p, x, cfg, lora, adapter_idx)
    if rope_cs is not None:
        cos, sin = rope_cs
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    # scatter the new token's K/V into each sequence's current block of
    # this layer, in place in the stacked pool — distinct sequences own
    # distinct blocks, so indices never collide (inactive slots share
    # scratch block 0, where last-write-wins garbage is fine: their
    # logits are discarded)
    with jax.named_scope("kv_write"):
        k_pool = k_pool.at[layer, write_block, write_off].set(
            k[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[layer, write_block, write_off].set(
            v[:, 0].astype(v_pool.dtype))
    with jax.named_scope("attn"):
        o = attention_decode_paged(q, k_pool, v_pool, block_tables,
                                   kv_len, layer, backend=backend)
    return _proj_out(p, o, cfg, lora, adapter_idx), (k_pool, v_pool)


def attn_prefill_suffix(p, x, cfg: ModelConfig, prefix_kv, prefix_len,
                        rope_cs, lora=None, adapter_idx=None):
    """Ragged suffix prefill attention for one layer: queries are the
    uncached suffix tokens (absolute positions ``prefix_len + i``, RoPE
    tables precomputed per row); keys are the cached prefix K/V
    (gathered from pool blocks) plus the suffix's own K/V.  Returns
    (out, (k_suf, v_suf)) so the runtime can scatter the fresh suffix
    K/V into its newly allocated blocks."""
    q, k, v = _proj_qkv(p, x, cfg, lora, adapter_idx)
    if rope_cs is not None:
        cos, sin = rope_cs
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    k_pre, v_pre = prefix_kv
    with jax.named_scope("attn"):
        o = attention_prefix_suffix(q, k_pre, v_pre, k, v, prefix_len,
                                    window=cfg.sliding_window)
    return _proj_out(p, o, cfg, lora, adapter_idx), (k, v)


def block_prefill_suffix(bp, x, cfg: ModelConfig, prefix_kv, prefix_len,
                         rope_cs, lora=None, adapter_idx=None):
    """Suffix-prefill block (attention-only stacks — prefix sharing
    rides on the paged KV pool).  Returns (x, (k_suf, v_suf))."""
    h = rms_norm(x, bp["ln1"])
    attn_out, kv = attn_prefill_suffix(bp["attn"], h, cfg, prefix_kv,
                                       prefix_len, rope_cs, lora=lora,
                                       adapter_idx=adapter_idx)
    x = x + attn_out
    if cfg.d_ff > 0:
        y, _ = _mlp_out(bp, rms_norm(x, bp["ln2"]), cfg, lora,
                        adapter_idx)
        x = x + y
    x = shard(x, "batch", "act_seq", "embed")
    return x, kv


def cross_attn(p, x, vision_kv, cfg: ModelConfig):
    """Cross-attention over precomputed vision K/V (no rope, no cache
    mutation — vision tokens are static per request)."""
    b, s = x.shape[0], x.shape[1]
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k, v = vision_kv
    o = attention_dense(q, k, v, causal=False)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return o @ p["wo"]


def vision_kv(p, vis: jax.Array, cfg: ModelConfig):
    """Project vision embeddings to K/V once (cached for decode)."""
    b, t = vis.shape[0], vis.shape[1]
    k = (vis @ p["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = (vis @ p["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    return k, v


# ----------------------------------------------------------------- blocks --
@jax.named_scope("mlp")
def _mlp_out(bp, h, cfg, lora, adapter_idx=None):
    if "moe" in bp:
        from repro.models.moe import MoEParams, moe_mlp
        y, aux = moe_mlp(MoEParams(**bp["moe"]), h, cfg)
        return y, aux
    sc = cfg.lora.scaling
    g = lora_lib.apply(h, h @ bp["mlp"]["wg"],
                       lora.get("gate") if lora else None, sc, adapter_idx)
    u = lora_lib.apply(h, h @ bp["mlp"]["wu"],
                       lora.get("up") if lora else None, sc, adapter_idx)
    hidden = jax.nn.silu(g) * u
    hidden = shard(hidden, "batch", "seq", "ff")
    y = lora_lib.apply(hidden, hidden @ bp["mlp"]["wd"],
                       lora.get("down") if lora else None, sc, adapter_idx)
    return y, jnp.zeros((), jnp.float32)


def block_full(bp, x, cfg: ModelConfig, rope_cs, lora=None,
               block_kv: int = 512, skip_masked_blocks: bool = False,
               adapter_idx=None):
    """Full-sequence block (training / prefill).  Returns
    (x, (kv, ssm_cache_final, aux_loss))."""
    h = rms_norm(x, bp["ln1"])
    kv = None
    ssm_final = None
    if cfg.family is Family.SSM:
        y, ssm_cache = mamba2.ssm_mixer(
            mamba2.SSMParams(**bp["ssm"]), h, cfg,
            cache=None, lora=lora)
        x = x + y
        return x, (kv, ssm_cache._asdict(), jnp.zeros((), jnp.float32))
    attn_out, kv = attn_full(bp["attn"], h, cfg, rope_cs, lora=lora,
                             block_kv=block_kv,
                             skip_masked_blocks=skip_masked_blocks,
                             adapter_idx=adapter_idx)
    if cfg.family is Family.HYBRID:
        ssm_out, ssm_cache = mamba2.ssm_mixer(
            mamba2.SSMParams(**bp["ssm"]), h, cfg, cache=None, lora=lora)
        ssm_final = ssm_cache._asdict()
        attn_out = 0.5 * (attn_out + ssm_out)
    x = x + attn_out
    aux = jnp.zeros((), jnp.float32)
    if cfg.d_ff > 0:
        y, aux = _mlp_out(bp, rms_norm(x, bp["ln2"]), cfg, lora,
                          adapter_idx)
        x = x + y
    # residual-stream constraint: under SP rules the remat-saved carry is
    # sequence-sharded over the model axis (act_seq), not replicated
    x = shard(x, "batch", "act_seq", "embed")
    return x, (kv, ssm_final, aux)


def block_decode(bp, x, cfg: ModelConfig, caches, pos, rope_cs, lora=None,
                 backend=None, adapter_idx=None):
    """One-token block.  caches: dict with optional 'kv' (k,v) and 'ssm'
    (SSMCache).  Returns (x, updated caches)."""
    h = rms_norm(x, bp["ln1"])
    new_caches = dict(caches)
    if cfg.family is Family.SSM:
        y, new_ssm = mamba2.ssm_mixer(
            mamba2.SSMParams(**bp["ssm"]), h, cfg,
            cache=mamba2.SSMCache(**caches["ssm"]), lora=lora)
        new_caches["ssm"] = new_ssm._asdict()
        return x + y, new_caches
    attn_out, new_kv = attn_decode(bp["attn"], h, cfg, caches["kv"], pos,
                                   rope_cs, lora=lora, backend=backend,
                                   adapter_idx=adapter_idx)
    new_caches["kv"] = new_kv
    if cfg.family is Family.HYBRID:
        ssm_out, new_ssm = mamba2.ssm_mixer(
            mamba2.SSMParams(**bp["ssm"]), h, cfg,
            cache=mamba2.SSMCache(**caches["ssm"]), lora=lora)
        new_caches["ssm"] = new_ssm._asdict()
        attn_out = 0.5 * (attn_out + ssm_out)
    x = x + attn_out
    if cfg.d_ff > 0:
        y, _ = _mlp_out(bp, rms_norm(x, bp["ln2"]), cfg, lora,
                        adapter_idx)
        x = x + y
    return x, new_caches


def block_decode_paged(bp, x, cfg: ModelConfig, pool_kv, layer, rope_cs,
                       block_tables, write_block, write_off, kv_len,
                       lora=None, backend=None, adapter_idx=None):
    """One-token block of layer ``layer`` against the stacked paged KV
    pool [L, n_blocks, block_size, Hkv, Dh] (attention-only stacks — SSM
    state is per-slot, not per-block).  Returns (x, updated pools)."""
    h = rms_norm(x, bp["ln1"])
    attn_out, new_kv = attn_decode_paged(
        bp["attn"], h, cfg, pool_kv, layer, rope_cs, block_tables,
        write_block, write_off, kv_len, lora=lora, backend=backend,
        adapter_idx=adapter_idx)
    x = x + attn_out
    if cfg.d_ff > 0:
        y, _ = _mlp_out(bp, rms_norm(x, bp["ln2"]), cfg, lora,
                        adapter_idx)
        x = x + y
    return x, new_kv


def cross_block(cp, x, vkv, cfg: ModelConfig):
    h = rms_norm(x, cp["ln1"])
    ga = jnp.tanh(cp["gate_attn"]).astype(x.dtype)  # f32 gate; keep carry dtype
    x = x + ga * cross_attn(cp["attn"], h, vkv, cfg)
    h = rms_norm(x, cp["ln2"])
    y = swiglu(h, cp["mlp"]["wg"], cp["mlp"]["wu"], cp["mlp"]["wd"])
    gm = jnp.tanh(cp["gate_mlp"]).astype(x.dtype)
    return x + gm * y


# -------------------------------------------------------------- policies ---
def remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    else:  # "full" / "block"
        policy = None
    return jax.checkpoint(fn, policy=policy)
