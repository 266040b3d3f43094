"""What every family's plain reference shares: float32 building blocks
(matrix products at ``precision="highest"``, RMSNorm, rotary
embeddings, a projection with its LoRA pair), the fp8 control, and
AdamW with global-norm clipping for the train leg.  The decoders
themselves are ``bench/families/<family>.py``.

``quant="fp8"`` is the control: every linear layer's input and weight
are rounded to float8_e4m3fn (absmax scale per tensor for weights, per
row for activations) before an f32 product, the step below the bf16 the
configurations state; its gradients are taken straight through the
rounding.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def q8(x, axis):
    """Round to float8_e4m3fn with an absmax scale over ``axis``.  The
    gradient passes straight through the rounding, as in fp8 training,
    where the backward products take the rounded operands; a cast's
    own gradient would round tiny tangents to zero."""
    s = lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX)
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(F8).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def mm(x, w, quant):
    if quant == "fp8":
        x = q8(x, -1)
        w = q8(w, None)
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def rope(x, pos, theta):
    """x [..., S, H, D]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., :, None] * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def linear(x, w, bias, pair, scaling, quant):
    """``x @ w``, plus the LoRA ``pair`` (``{"a", "b"}``) times
    ``scaling`` and ``bias`` where given."""
    y = mm(x, w, quant)
    if pair is not None:
        y = y + mm(mm(x, pair["a"], quant), pair["b"], quant) * scaling
    if bias is not None:
        y = y + bias
    return y


def cfg_key(cfg: Dict[str, Any]) -> tuple:
    """The configuration's scalar entries, hashable (a static jit arg)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("opt_items",))
def adamw(adapter, grads, state, opt_items):
    """``adamw_step`` compiled, the optimizer's settings as sorted
    items."""
    return adamw_step(adapter, grads, state, dict(opt_items))


def adamw_step(adapter, grads, state, opt: Dict[str, Any]):
    """One AdamW step with global-norm clipping, as the configuration's
    optimizer states it.  Returns (new adapter, new state, clipped
    gradient)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = float(opt["clip_norm"])
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)) \
        if clip > 0 else 1.0
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2, eps, lr = (float(opt[k]) for k in ("b1", "b2", "eps", "lr"))
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, state["m"], g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg,
                     state["v"], g)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    new = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        adapter, m, v)
    return new, {"t": t, "m": m, "v": v}, g
