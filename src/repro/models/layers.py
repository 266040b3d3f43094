"""Core neural layers in pure JAX: norms, RoPE, GQA attention (dense,
blockwise/flash-equivalent, decode), SwiGLU/GeLU MLPs.

All functions are parameter-dict based (no framework).  Weight matrices use
the ``[in, out]`` convention; stacked-layer params carry a leading ``L``
dim and are consumed through ``jax.lax.scan``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.sharding import shard


# ---------------------------------------------------------------- norms ----
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ----------------------------------------------------------------- RoPE ----
def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for given positions [..., S] -> [..., S, head_dim/2]."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B,S,H,D]; cos/sin: [S,D/2] or [B,S,D/2] (broadcast over heads)."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----
def _gqa_repeat(k, n_heads: int):
    """[B,S,Hkv,D] -> [B,S,Hq,D] by repeating KV heads.

    The jnp attention paths use the repeated-KV formulation instead of
    grouped reshapes: a reshape like 48 -> (8, 6) of a 16-way-sharded
    head dim is not expressible in GSPMD and forces all-gathers, while
    the repeat output simply inherits the q head sharding (the source
    read stays Hkv-sized).  The Pallas kernels keep the grouped form —
    in VMEM the repeat would be real memory traffic.
    """
    g = n_heads // k.shape[2]
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def attention_dense(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, kv_len: Optional[jax.Array] = None,
                    scale: Optional[float] = None):
    """Reference GQA attention (materializes full score matrix).

    q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D].  ``q_offset`` is the absolute
    position of q[0] (decode).  ``kv_len`` masks positions >= kv_len.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = _gqa_repeat(k, hq)
    v = _gqa_repeat(v, hq)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    if kv_len is not None:
        mask = mask & (kpos[None, :] < jnp.asarray(kv_len)[..., None, None]) \
            if jnp.ndim(kv_len) else mask & (kpos[None, :] < kv_len)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention_blockwise(q, k, v, *, causal: bool = True, window: int = 0,
                        q_offset: int = 0, block_kv: int = 512,
                        scale: Optional[float] = None,
                        skip_masked_blocks: bool = False,
                        unroll: bool = False):
    """Flash-equivalent attention: lax.scan over KV blocks with online
    softmax.  Memory O(Sq * block_kv) instead of O(Sq * Skv).

    With ``skip_masked_blocks`` (beyond-paper optimization, see
    EXPERIMENTS.md §Perf) the scan runs only over the lower-triangular
    (q-block, kv-block) pairs, halving attention FLOPs for causal prefill.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = _gqa_repeat(k, hq)
    v = _gqa_repeat(v, hq)
    nkv = -(-skv // block_kv)
    pad = nkv * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nkv, block_kv, hq, d)
    vb = v.reshape(b, nkv, block_kv, hq, d)
    qf = q                                           # [B,Sq,H,D]
    qpos = q_offset + jnp.arange(sq)

    if not skip_masked_blocks:
        def body(carry, xs):
            m, l, acc = carry
            kblk, vblk, jblk = xs
            kpos = jblk * block_kv + jnp.arange(block_kv)
            # bf16 operands, f32 accumulation (flash-kernel numerics):
            # no f32 copies of q/k/v stream through HBM
            s = jnp.einsum("bqhd,bkhd->bqhk", qf, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = kpos[None, :] < skv
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard rows where everything is masked so far
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, sq, hq), -jnp.inf, jnp.float32),
                jnp.zeros((b, sq, hq), jnp.float32),
                jnp.zeros((b, sq, hq, d), jnp.float32))
        if unroll:
            # cost-calibration path: XLA's cost analysis counts scan
            # bodies once, so the dry-run unrolls the KV-block loop
            carry = init
            for j in range(nkv):
                carry, _ = body(carry, (kb[:, j], vb[:, j], jnp.int32(j)))
            m, l, acc = carry
        else:
            (m, l, acc), _ = lax.scan(
                body, init,
                (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nkv)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    # --- triangular (block-skipping) variant: scan over valid (i,j) pairs ---
    assert causal and q_offset == 0 and sq == skv, \
        "block skipping is for causal self-attention prefill"
    bq = block_kv
    nq = -(-sq // bq)
    qpad = nq * bq - sq
    qb = qf if not qpad else jnp.pad(
        qf, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    qb = qb.reshape(b, nq, bq, hq, d)
    if window > 0:
        wblocks = -(-window // bq) + 1
        pairs = [(i, j) for i in range(nq) for j in range(nq)
                 if j <= i and i - j < wblocks]
    else:
        pairs = [(i, j) for i in range(nq) for j in range(i + 1)]
    ii = jnp.array([p[0] for p in pairs])
    jj = jnp.array([p[1] for p in pairs])

    def body(carry, xs):
        m, l, acc = carry                     # [B,nq,bq,H(,D)]
        i, j = xs
        qi = lax.dynamic_index_in_dim(qb, i, axis=1, keepdims=False)
        kj = lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
        vj = lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
        qpos_i = i * bq + jnp.arange(bq)
        kpos_j = j * bq + jnp.arange(block_kv)
        s = jnp.einsum("bqhd,bkhd->bqhk", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        mask = (kpos_j[None, :] <= qpos_i[:, None]) & (kpos_j[None, :] < skv)
        if window > 0:
            mask &= (qpos_i[:, None] - kpos_j[None, :]) < window
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        mi = lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        li = lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        acci = lax.dynamic_index_in_dim(acc, i, 1, keepdims=False)
        m_new = jnp.maximum(mi, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
        corr = jnp.where(jnp.isinf(mi), 0.0, jnp.exp(mi - m_safe))
        l_new = li * corr + jnp.sum(p, axis=-1)
        acc_new = acci * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        m = lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        acc = lax.dynamic_update_index_in_dim(acc, acc_new, i, 1)
        return (m, l, acc), None

    init = (jnp.full((b, nq, bq, hq), -jnp.inf, jnp.float32),
            jnp.zeros((b, nq, bq, hq), jnp.float32),
            jnp.zeros((b, nq, bq, hq, d), jnp.float32))
    if unroll:
        carry = init
        for i, j in pairs:
            carry, _ = body(carry, (jnp.int32(i), jnp.int32(j)))
        m, l, acc = carry
    else:
        (m, l, acc), _ = lax.scan(body, init, (ii, jj))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(b, nq * bq, hq, d)[:, :sq]
    return out.astype(q.dtype)


def attention_prefix_suffix(q, k_pre, v_pre, k_suf, v_suf, prefix_len, *,
                            window: int = 0,
                            scale: Optional[float] = None):
    """Suffix-prefill attention: suffix queries attend over a cached
    (gathered) prefix's K/V plus the suffix's own causal K/V.

    q, k_suf, v_suf: [B, Sq, H*, D] — the uncached suffix, row ``i`` at
    absolute position ``prefix_len[b] + i``; k_pre, v_pre:
    [B, Pp, Hkv, D] — prefix K/V gathered from pool blocks, positions
    ``0 .. Pp-1``, valid where ``< prefix_len[b]`` (rows past a
    sequence's real prefix are other blocks' garbage and are masked).
    Mirrors ``attention_dense``'s score/softmax formulation exactly:
    masked lanes contribute exact zeros, so a request prefilled as
    (cached prefix + suffix) reproduces the full-prefill logits
    bit-for-bit."""
    b, sq, hq, d = q.shape
    pp = k_pre.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = jnp.concatenate([k_pre.astype(q.dtype), k_suf], axis=1)
    v = jnp.concatenate([v_pre.astype(q.dtype), v_suf], axis=1)
    k = _gqa_repeat(k, hq)
    v = _gqa_repeat(v, hq)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    plen = jnp.asarray(prefix_len, jnp.int32)
    qpos = plen[:, None] + jnp.arange(sq)                    # [B, Sq]
    kpos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(pp), (b, pp)),
         plen[:, None] + jnp.arange(sq)], axis=1)            # [B, Pp+Sq]
    mask = kpos[:, None, :] <= qpos[:, :, None]              # causal
    mask &= jnp.concatenate(
        [jnp.arange(pp)[None, :] < plen[:, None],            # real prefix
         jnp.ones((b, sq), bool)], axis=1)[:, None, :]
    if window > 0:
        mask &= (qpos[:, :, None] - kpos[:, None, :]) < window
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def resolve_decode_backend(backend: Optional[str]) -> str:
    """Resolve a decode-attention backend name.

    ``None``/"auto" picks the Pallas kernel on TPU and the jnp path
    everywhere else; "pallas" / "interpret" / "jnp" force a path (tests
    force "interpret" to exercise the kernel on CPU).  The choice is an
    explicit (static) argument through the decode stack rather than an
    env read at trace time, so jitted programs cache per backend.
    """
    if backend is None or backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("pallas", "interpret", "jnp"):
        raise ValueError(f"unknown decode backend {backend!r}")
    return backend


def attention_decode(q, k_cache, v_cache, kv_len, *, window: int = 0,
                     scale: Optional[float] = None,
                     backend: Optional[str] = None):
    """Single-token decode attention over a KV cache.

    q: [B,1,Hq,D]; caches: [B,S,Hkv,D]; kv_len: [B] or scalar — number of
    valid cache entries (the new token's KV must already be written).

    ``backend`` (see ``resolve_decode_backend``) dispatches to the Pallas
    kernel when the masking is expressible as a pure ``kv_len`` prefix
    (``window == 0`` here — ring-buffer callers already fold the window
    into ``kv_len``): the contiguous cache is viewed as a block pool with
    an identity block table, so one kernel serves both layouts.
    """
    b, _, hq, d = q.shape
    s = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    backend = resolve_decode_backend(backend)
    if backend in ("pallas", "interpret") and window == 0:
        from repro.kernels.decode_attention import paged_decode_attention
        hkv = k_cache.shape[2]
        bk = next(bk for bk in (256, 128, 64, 32, 16, 8, 4, 2, 1)
                  if s % bk == 0)
        nk = s // bk
        kp = k_cache.reshape(b * nk, bk, hkv, d)
        vp = v_cache.reshape(b * nk, bk, hkv, d)
        tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * nk
                  + jnp.arange(nk, dtype=jnp.int32)[None, :])
        klen = jnp.asarray(kv_len)
        if klen.ndim == 0:
            klen = jnp.full((b,), klen)
        out = paged_decode_attention(q[:, 0], kp, vp, tables, klen,
                                     scale=scale,
                                     interpret=backend == "interpret")
        return out[:, None].astype(q.dtype)
    kr = _gqa_repeat(k_cache, hq)
    vr = _gqa_repeat(v_cache, hq)
    scores = jnp.einsum("bhd,bkhd->bhk", q[:, 0].astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)
    klen = jnp.asarray(kv_len)
    if klen.ndim == 0:
        klen = jnp.full((b,), klen)
    mask = kpos[None, :] < klen[:, None]                 # [B,S]
    if window > 0:
        mask &= kpos[None, :] >= (klen[:, None] - window)
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vr.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def attention_decode_paged(q, k_pool, v_pool, block_tables, kv_len, *,
                           scale: Optional[float] = None,
                           backend: Optional[str] = None):
    """Single-token decode attention over a paged KV cache.

    q: [B,1,Hq,D]; pools: [n_blocks, block_size, Hkv, D] (one layer's
    slice of the global block pool); block_tables: [B, NB] int32 mapping
    each sequence's logical blocks to pool blocks; kv_len: [B] valid
    logical length.  Pallas backends walk the table block-by-block; the
    jnp fallback gathers the logical [B, NB*bs] view and reuses the
    contiguous ``attention_decode`` math (identical masking, so paged
    and contiguous runtimes agree to numerical identity).
    """
    backend = resolve_decode_backend(backend)
    klen = jnp.asarray(kv_len)
    if klen.ndim == 0:
        klen = jnp.full((q.shape[0],), klen)
    if backend in ("pallas", "interpret"):
        from repro.kernels.decode_attention import paged_decode_attention
        out = paged_decode_attention(q[:, 0], k_pool, v_pool,
                                     block_tables, klen, scale=scale,
                                     interpret=backend == "interpret")
        return out[:, None].astype(q.dtype)
    b = q.shape[0]
    nb = block_tables.shape[1]
    bs = k_pool.shape[1]
    k = jnp.take(k_pool, block_tables, axis=0).reshape(
        b, nb * bs, *k_pool.shape[2:])
    v = jnp.take(v_pool, block_tables, axis=0).reshape(
        b, nb * bs, *v_pool.shape[2:])
    return attention_decode(q, k, v, klen, scale=scale, backend="jnp")


def attention_decode_seqsharded(q, k_new, v_new, k_cache, v_cache, pos, *,
                                scale: Optional[float] = None):
    """Sequence-sharded flash-decode via shard_map (beyond-paper
    optimization, EXPERIMENTS.md §Perf).

    Each shard of the mesh axis carrying ``kv_seq`` owns a contiguous
    slice of the cache: it writes the new token's K/V locally (no
    collective — the naive dynamic-update-slice on a sharded dim makes
    GSPMD reshard the whole cache) and computes grouped-GQA partial
    attention over its slice; the only cross-shard traffic is the
    online-softmax reduction — pmax of m [B,Hkv,G] and psum of
    (l, acc) [B,Hkv,G(,D)], a few MB instead of the cache size.

    q/k_new/v_new: [B,1,H*,D]; caches: [B,S,Hkv,D] (S sharded);
    pos: scalar int32.  Returns (out [B,1,Hq,D], new_k, new_v).
    """
    from repro.models.sharding import current_mesh, current_rules
    mesh = current_mesh()
    rules = current_rules()
    seq_ax = rules.kv_seq
    b, s, hkv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    from jax.sharding import PartitionSpec as P
    # keep only mesh-present axes (single-pod mesh has no "pod")
    raw = rules.kv_batch
    raw = raw if isinstance(raw, tuple) else (raw,)
    batch_ax = tuple(a for a in raw if a in mesh.shape) or None
    n_seq = mesh.shape[seq_ax]

    def body(q_, kn, vn, kc, vc, pos_):
        idx = lax.axis_index(seq_ax)
        s_loc = kc.shape[1]
        start = idx * s_loc
        loc = pos_ - start
        in_range = (loc >= 0) & (loc < s_loc)
        loc_c = jnp.clip(loc, 0, s_loc - 1)
        # slot-masked write: out-of-range shards rewrite the old slot
        # value — the DUS stays in-place (one slot of traffic), no
        # full-slice `where` copy
        old_k = lax.dynamic_slice_in_dim(kc, loc_c, 1, axis=1)
        old_v = lax.dynamic_slice_in_dim(vc, loc_c, 1, axis=1)
        kn_eff = jnp.where(in_range, kn.astype(kc.dtype), old_k)
        vn_eff = jnp.where(in_range, vn.astype(vc.dtype), old_v)
        kc = lax.dynamic_update_slice_in_dim(kc, kn_eff, loc_c, axis=1)
        vc = lax.dynamic_update_slice_in_dim(vc, vn_eff, loc_c, axis=1)

        bl = q_.shape[0]
        qg = q_[:, 0].reshape(bl, hkv, g, d)
        # bf16 operands, f32 accumulation — no f32 cache copies.
        # f8 caches (kv_cache_dtype) upcast to the q dtype at the slice.
        kc_m = kc if kc.dtype == qg.dtype else kc.astype(qg.dtype)
        sc = jnp.einsum("bhgd,bshd->bhgs", qg, kc_m,
                        preferred_element_type=jnp.float32) * scale_
        kpos = start + jnp.arange(s_loc)
        mask = kpos <= pos_
        sc = jnp.where(mask[None, None, None, :], sc, -jnp.inf)
        m_loc = jnp.max(sc, axis=-1)
        m_glob = lax.pmax(m_loc, seq_ax)
        m_safe = jnp.where(jnp.isinf(m_glob), 0.0, m_glob)
        p = jnp.where(mask[None, None, None, :],
                      jnp.exp(sc - m_safe[..., None]), 0.0)
        l_loc = jnp.sum(p, axis=-1)
        vc_m = vc if vc.dtype == qg.dtype else vc.astype(qg.dtype)
        acc_loc = jnp.einsum("bhgs,bshd->bhgd",
                             p.astype(qg.dtype), vc_m,
                             preferred_element_type=jnp.float32)
        l = lax.psum(l_loc, seq_ax)
        acc = lax.psum(acc_loc, seq_ax)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return (out.reshape(bl, 1, hq, d).astype(q_.dtype), kc, vc)

    pq = P(batch_ax, None, None, None)
    pc = P(batch_ax, seq_ax, None, None)
    out, new_k, new_v = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(pq, pq, pq, pc, pc, P()),
        out_specs=(pq, pc, pc),
    )(q, k_new, v_new, k_cache, v_cache, pos)
    return out, new_k, new_v


def attention_auto(q, k, v, **kw):
    """Pick dense vs blockwise by sequence length."""
    if q.shape[1] * k.shape[1] <= 1024 * 1024:
        kw.pop("block_kv", None)
        kw.pop("skip_masked_blocks", None)
        return attention_dense(q, k, v, **kw)
    return attention_blockwise(q, k, v, **kw)


# ----------------------------------------------------------------- MLPs ----
def swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    h = shard(h, "batch", "seq", "ff")
    return h @ wd


def gelu_mlp(x, wi, bi, wo, bo):
    h = jax.nn.gelu(x @ wi + bi)
    h = shard(h, "batch", "seq", "ff")
    return h @ wo + bo


# ----------------------------------------------------------------- init ----
def dense_init(key, d_in, d_out, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)
