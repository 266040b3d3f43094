"""Batched decode attention Pallas kernels: one query token per sequence
against a (possibly partially-filled) KV cache.

Decode attention is memory-bound (the whole KV cache streams HBM->VMEM
once per step, arithmetic intensity ~1 FLOP/byte), so the kernels' job is
to keep the streaming dense: KV blocks are walked with the online-softmax
accumulator in VMEM, and blocks entirely beyond ``kv_len`` are skipped
via ``pl.when`` so a short cache in a long buffer doesn't pay for the
empty tail.

Two variants:

``decode_attention``        contiguous caches [B, Hkv, S, D]; grid
                            (B, Hkv, S/bk), one KV head per program.
``paged_decode_attention``  vLLM-style paged caches: the stacked block
                            pool [L, n_blocks, block_size, Hkv, D] of every
                            layer, shared by all sequences, walked at one
                            layer through per-sequence block tables [B, NB]
                            (layer and tables scalar-prefetched to SMEM so
                            the index map DMAs block (layer, tbl[b, j])
                            straight out of the stack: the layer scan never
                            slices the pool).  A contiguous cache is the
                            special case ``tables[b, j] = b * NB + j`` —
                            which is exactly how
                            ``models.layers.attention_decode`` dispatches
                            here without a layout change.

kv_len [B] int32 is the per-sequence valid length (ragged decode slots
from the continuous batcher / CoLLM dispatcher subflows).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bk: int, kv_steps: int, g: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    k_lo = j * bk

    @pl.when(k_lo < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        mask = kpos < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, scale: Optional[float] = None,
                     bk: int = 512, interpret: bool = False) -> jax.Array:
    """q: [B,H,D]; caches: [B,Hkv,S,D]; kv_len: [B] -> [B,H,D].

    Grid (B, Hkv, S/bk); the G=H/Hkv query heads sharing a KV head ride
    in the same block so the cache is streamed once per KV head.
    """
    bsz, h, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = min(bk, s)
    nk = -(-s // bk)
    kp = nk * bk - s
    if kp:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, kp), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, kp), (0, 0)))
    qg = q.reshape(bsz, hkv, g, d)

    kernel = functools.partial(_kernel, scale=scale, bk=bk, kv_steps=nk, g=g)
    out = pl.pallas_call(
        kernel,
        grid=(bsz, hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, hh, j: (b,)),
            pl.BlockSpec((1, 1, g, d), lambda b, hh, j: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, j: (b, hh, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, j: (b, hh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b, hh, j: (b, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(bsz, h, d)


# =========================================================================
# Paged variant: block-table walk over a global block pool
# =========================================================================
def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  scale: float, bs: int, nb_steps: int, hkv: int, g: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    k_lo = j * bs

    @pl.when(k_lo < kv_len)
    def _compute():
        k = k_ref[0, 0]                               # [bs, Hkv, D]
        v = v_ref[0, 0]
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        mask = kpos < kv_len
        # all KV heads of the block are resident: the block streams from
        # HBM once per sequence, not once per head (the unrolled head
        # loop below reuses it from VMEM)
        for hh in range(hkv):
            qh = q_ref[0, hh].astype(jnp.float32)     # [G, D]
            kh = k[:, hh, :].astype(jnp.float32)      # [bs, D]
            s = jnp.dot(qh, kh.T,
                        preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[hh]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[hh] = l_ref[hh] * corr + jnp.sum(p, axis=1)
            acc_ref[hh] = acc_ref[hh] * corr[:, None] + jnp.dot(
                p.astype(v.dtype), v[:, hh, :],
                preferred_element_type=jnp.float32)
            m_ref[hh] = m_new

    @pl.when(j == nb_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           kv_len: jax.Array, layer=0, *,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: [B,H,D]; pools: [L, n_blocks, block_size, Hkv, D] (a 4-D
    [n_blocks, block_size, Hkv, D] pool is taken as L = 1); block_tables:
    [B, NB] int32; kv_len: [B]; layer: int32 scalar, the layer of the
    stack to read -> [B,H,D].

    Grid (B, NB): program (b, j) walks logical block j of sequence b by
    DMA-ing pool block ``(layer, block_tables[b, j])`` (scalar-prefetch
    index map), with the same online-softmax accumulator as the
    contiguous kernel.  Table entries past a sequence's last live block
    must be valid pool indices (the runtime points them at reserved
    scratch block 0); ``pl.when`` skips their compute via ``kv_len``.
    """
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    bsz, h, d = q.shape
    bs, hkv = k_pool.shape[2], k_pool.shape[3]
    nb = block_tables.shape[1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(bsz, hkv, g, d)

    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs,
                               nb_steps=nb, hkv=hkv, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, nb),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d),
                         lambda b, j, tbl, lens, lyr: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, hkv, d),
                         lambda b, j, tbl, lens, lyr:
                         (lyr[0], tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, hkv, d),
                         lambda b, j, tbl, lens, lyr:
                         (lyr[0], tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda b, j, tbl, lens, lyr: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pool, v_pool)
    return out.reshape(bsz, h, d)
