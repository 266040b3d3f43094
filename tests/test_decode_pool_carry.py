"""Paged decode carries the stacked KV pool through the layer scan.

``Model.decode_step_paged`` keeps the pool [L, n_blocks, bs, Hkv, Dh]
in the scan's carry: each layer writes its token at (layer, block,
offset) and the attention reads (layer, block) out of the stack.  The
reference here is the formulation it replaced, written out in the test:
the pool as the scan's ``xs``/``ys``, one layer's [n_blocks, bs, Hkv,
Dh] slice per step.  Both must give the same logits and the same pool,
step after step, for MHA, GQA, a sliding-window ring, per-row adapters
and the unrolled layer loop (``scan_layers=False``); and no scan of the decode or fused program may take a
pool-shaped array as ``xs`` or give one as ``ys``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import make_batch
from repro.configs.registry import get_config
from repro.core.engine import make_engine
from repro.models import transformer as tfm
from repro.models.layers import (
    apply_rope, attention_decode, rms_norm, rope_tables,
)
from repro.models.model import _lm_head

B, N_BLOCKS, BS = 3, 13, 4


def _sliced_reference(model, params, lora, caches, token, pos, tables,
                      ring_len, adapter_idx):
    """One decode step with the pool as the layer scan's xs/ys."""
    cfg = model.cfg
    rl = ring_len if ring_len else tables.shape[1] * BS
    wpos = jnp.remainder(pos, rl)
    kv_len = jnp.minimum(pos + 1, rl)
    wblock = jnp.take_along_axis(tables, (wpos // BS)[:, None],
                                 axis=1)[:, 0]
    woff = wpos % BS
    cos, sin = rope_tables(pos[:, None], cfg.head_dim, cfg.rope_theta)
    x = jnp.take(params["embed"], token, axis=0)

    def body(xc, xs):
        bp, lsl, (kp, vp) = xs
        q, k, v = tfm._proj_qkv(bp["attn"], rms_norm(xc, bp["ln1"]), cfg,
                                lsl, adapter_idx)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        kp = kp.at[wblock, woff].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[wblock, woff].set(v[:, 0].astype(vp.dtype))
        kg = kp[tables].reshape(B, -1, *kp.shape[2:])
        vg = vp[tables].reshape(B, -1, *vp.shape[2:])
        o = attention_decode(q, kg, vg, kv_len, backend="jnp")
        xc = xc + tfm._proj_out(bp["attn"], o, cfg, lsl, adapter_idx)
        y, _ = tfm._mlp_out(bp, rms_norm(xc, bp["ln2"]), cfg, lsl,
                            adapter_idx)
        return xc + y, (kp, vp)

    x, kv = lax.scan(body, x, (params["blocks"], lora, caches["kv"]))
    return _lm_head(rms_norm(x, params["final_norm"]), params), {"kv": kv}


def _setup(arch, multi_tenant, unrolled):
    cfg = get_config(arch).scaled(scan_layers=not unrolled)
    model = make_engine(cfg).model
    params = model.init(jax.random.key(0))
    lora = jax.tree.map(lambda a: a + 0.02,
                        model.init_lora(jax.random.key(1)))
    adapter_idx = None
    if multi_tenant:
        other = jax.tree.map(lambda a: a - 0.03, lora)
        lora = jax.tree.map(lambda a, b: jnp.stack([a, b], axis=1),
                            lora, other)
        adapter_idx = jnp.asarray([1, -1, 0], jnp.int32)
    shape = (cfg.n_layers, N_BLOCKS, BS, cfg.n_kv_heads, cfg.head_dim)
    ks = jax.random.split(jax.random.key(2), 2)
    caches = {"kv": (jax.random.normal(ks[0], shape, jnp.float32),
                     jax.random.normal(ks[1], shape, jnp.float32))}
    return model, params, lora, caches, adapter_idx


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("arch,ring_len,multi_tenant,unrolled,start", [
    ("qwen1.5-0.5b", 0, False, False, [0, 5, 9]),     # MHA
    ("internlm2-1.8b", 0, False, False, [2, 4, 8]),   # GQA 4/2
    ("qwen1.5-0.5b", 8, False, False, [6, 9, 14]),    # ring wraps at 8
    ("qwen1.5-0.5b", 0, True, False, [1, 7, 3]),      # per-row adapters
    ("internlm2-1.8b", 8, True, True, [5, 2, 10]),    # Python-int layers
], ids=["mha", "gqa", "ring", "adapters", "unrolled"])
def test_carried_pool_matches_sliced_reference(arch, ring_len,
                                               multi_tenant, unrolled,
                                               start, backend):
    model, params, lora, caches, adapter_idx = _setup(arch, multi_tenant,
                                                      unrolled)
    tables = jnp.asarray([[3, 7, 1], [12, 2, 5], [4, 9, 11]], jnp.int32)
    step = jax.jit(model.decode_step_paged,
                   static_argnames=("ring_len", "attn_backend"))
    ref = jax.jit(_sliced_reference, static_argnums=(0, 7))
    # the kernel's online softmax rounds differently from the dense
    # reference, and later layers' K/V inherit that
    tol = 1e-6 if backend == "jnp" else 2e-5
    got, want = caches, caches
    for i in range(3):
        token = jnp.asarray([[11 + i], [40 + i], [7 * i]], jnp.int32)
        pos = jnp.asarray(start, jnp.int32) + i
        logits, got = step(params, lora, got, token, pos, tables,
                           ring_len=ring_len, attn_backend=backend,
                           adapter_idx=adapter_idx)
        ref_logits, want = ref(model, params, lora, want, token, pos,
                               tables, ring_len, adapter_idx)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   rtol=tol, atol=tol)
        for g, w in zip(got["kv"], want["kv"]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=tol, atol=tol)
    # the steps wrote: the pool moved away from where it started
    assert not np.allclose(np.asarray(got["kv"][0]),
                           np.asarray(caches["kv"][0]))


def _scans(jaxpr):
    """Every scan equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


@pytest.mark.parametrize("program", ["decode_step_paged",
                                     "combined_step_paged"])
def test_no_scan_slices_or_stacks_the_pool(program):
    cfg = get_config("qwen1.5-0.5b").scaled()
    engine = make_engine(cfg, lr=3e-3)
    model = engine.model
    params = model.init(jax.random.key(0))
    lora = model.init_lora(jax.random.key(1))
    caches = model.init_paged_caches(N_BLOCKS, BS)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.asarray([3, 5, 0], jnp.int32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 0], [5, 6, 0]], jnp.int32)
    if program == "decode_step_paged":
        jaxpr = jax.make_jaxpr(model.decode_step_paged)(
            params, lora, caches, tok, pos, tables)
    else:
        jaxpr = jax.make_jaxpr(engine.combined_step_paged)(
            params, lora, engine.optimizer.init(lora),
            make_batch(cfg, batch=2, seq=8), caches, tok, pos, tables)
    layer_pool = caches["kv"][0].shape[1:]

    def pooled(avals):
        return [a.shape for a in avals
                if tuple(a.shape[-len(layer_pool):]) == layer_pool]

    carried = 0
    for eqn in _scans(jaxpr.jaxpr):
        n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
        n_carry = eqn.params["num_carry"]
        xs = [v.aval for v in eqn.invars[n_in:]]
        ys = [v.aval for v in eqn.outvars[n_carry:]]
        carry = [v.aval for v in eqn.invars[eqn.params["num_consts"]:n_in]]
        assert pooled(xs) == [] and pooled(ys) == [], (pooled(xs),
                                                       pooled(ys))
        carried += len(pooled(carry))
    assert carried == 2      # K and V ride in the layer scan's carry
