"""The dense decoder family: RMSNorm, rotary embeddings on half-split
head dims, grouped-query attention with query head i reading key/value
head i // (H / Hkv), optional q/k/v biases, SwiGLU feed-forward, a tied
or untied output head, and LoRA on the attention projections.

Weights and adapters are made from the run's seed, on the device, in
one jitted call each and in the type they are served in.  The tree
layout is the one the program's dense decoder consumes (layers stacked
on a leading axis, ``[in, out]`` matrices); the harness checks it
against the program's own shapes before serving.  Scales keep a random
model's activations and logits near unit size: embeddings and the
output head at 1/sqrt(d_model), every projection at 1/sqrt(fan_in),
norm scales around 1 and, where the configuration has them, nonzero
q/k/v biases.  A tied configuration serves the embedding matrix,
transposed, as its output head.

The plain reference is this decoder in float32 ``jax.numpy``, written
from the published description.  It imports nothing of the program and
takes no array the program made: weights and adapters are made again
from the run's seed.  Matrix products run at ``precision="highest"``;
``quant="fp8"`` is the control (``harness.reference.mm``).

The counts are the operations and bytes the work needs, computed from
shapes: only the work a request needs counts (real prompt tokens,
decode rows that hold a request, their live context, no
recomputation).  A matrix product of [m, k] by [k, n] is 2*m*k*n
operations; softmax, norms and elementwise work are not counted.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from harness.reference import adamw, cfg_key, linear, mm, rms, rope
from harness.weights import lora_scaling, normal

LORA_B_STD = 0.05     # tenant adapters start away from the no-op init


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Sizes under short names, from a configuration file."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h),
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "r": cfg["lora_rank"]}


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry of ``arch`` with every size the file states."""
    from repro.configs.base import LoRAConfig
    from repro.configs.registry import get_config
    m = dims(cfg)
    return dataclasses.replace(
        get_config(cfg["arch"]), n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["V"],
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        rope_theta=float(cfg["rope_theta"]), sliding_window=0,
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
        lora=LoRAConfig(rank=m["r"], alpha=float(cfg["lora_alpha"]),
                        targets=tuple(cfg["lora_targets"])))


# -------------------------------------------------------------- weights --
@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(key, spec):
    (L, d, H, Hkv, hd, f, V), bias, tied, dtype = spec
    ks = iter(jax.random.split(key, 16))
    blocks = {
        "ln1": 1.0 + normal(next(ks), (L, d), 0.1),
        "ln2": 1.0 + normal(next(ks), (L, d), 0.1),
        "attn": {
            "wq": normal(next(ks), (L, d, H * hd), d ** -0.5),
            "wk": normal(next(ks), (L, d, Hkv * hd), d ** -0.5),
            "wv": normal(next(ks), (L, d, Hkv * hd), d ** -0.5),
            "wo": normal(next(ks), (L, H * hd, d), (H * hd) ** -0.5),
        },
        "mlp": {
            "wg": normal(next(ks), (L, d, f), d ** -0.5),
            "wu": normal(next(ks), (L, d, f), d ** -0.5),
            "wd": normal(next(ks), (L, f, d), f ** -0.5),
        },
    }
    if bias:
        blocks["attn"]["bq"] = normal(next(ks), (L, H * hd), 0.1)
        blocks["attn"]["bk"] = normal(next(ks), (L, Hkv * hd), 0.1)
        blocks["attn"]["bv"] = normal(next(ks), (L, Hkv * hd), 0.1)
    embed = normal(next(ks), (V, d), d ** -0.5)
    head = embed.T if tied else normal(next(ks), (d, V), d ** -0.5)
    params = {"embed": embed, "blocks": blocks,
              "final_norm": 1.0 + normal(next(ks), (d,), 0.1),
              "lm_head": head}
    return jax.tree.map(lambda x: x.astype(dtype), params)


def make_params(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16):
    """The base weights in ``dtype`` (the served type by default)."""
    m = dims(cfg)
    spec = ((m["L"], m["d"], m["H"], m["Hkv"], m["hd"], m["f"], m["V"]),
            bool(cfg.get("qkv_bias", False)),
            bool(cfg.get("tie_word_embeddings", False)),
            jnp.dtype(dtype).name)
    return _make_params(jax.random.key(seed), spec)


def lora_targets(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    m = dims(cfg)
    d, H, Hkv, hd = m["d"], m["H"], m["Hkv"], m["hd"]
    shapes = {"q": (d, H * hd), "k": (d, Hkv * hd), "v": (d, Hkv * hd),
              "o": (H * hd, d)}
    return {t: shapes[t] for t in cfg["lora_targets"]}


@functools.partial(jax.jit, static_argnums=(1,))
def _make_adapter(key, spec):
    L, r, targets = spec
    out = {}
    for i, (t, (din, dout)) in enumerate(targets):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        out[t] = {"a": normal(ka, (L, din, r), din ** -0.5),
                  "b": normal(kb, (L, r, dout), LORA_B_STD)}
    return out


def make_adapter(cfg: Dict[str, Any], seed: int, tenant: int):
    """Tenant ``tenant``'s LoRA tree (f32, as the program trains and
    stores adapters)."""
    m = dims(cfg)
    spec = (m["L"], m["r"], tuple(sorted(lora_targets(cfg).items())))
    key = jax.random.fold_in(jax.random.key(seed), 1000 + tenant)
    return _make_adapter(key, spec)


def param_count(cfg: Dict[str, Any]) -> int:
    """Distinct base parameters (a tied head counts once)."""
    m = dims(cfg)
    L, d, H, Hkv, hd, f, V = (m[k] for k in
                              ("L", "d", "H", "Hkv", "hd", "f", "V"))
    per = d * H * hd * 2 + 2 * d * Hkv * hd + 3 * d * f + 2 * d
    if cfg.get("qkv_bias", False):
        per += (H + 2 * Hkv) * hd
    heads = 1 if cfg.get("tie_word_embeddings", False) else 2
    return L * per + heads * V * d + d


# ------------------------------------------------------------ reference --
def hidden(params, adapter, tokens, cfg: Dict[str, Any], quant=None):
    """Final-norm hidden states [B, S, d] for token rows [B, S]
    (causal; a row's padding past its end touches no earlier
    position)."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim", d // H)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    sc = lora_scaling(cfg)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    b, s = tokens.shape
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def layer(x, xs):
        bp, ad = f32(xs[0]), xs[1]
        at = bp["attn"]
        h = rms(x, bp["ln1"], eps)
        q = linear(h, at["wq"], at.get("bq"), ad.get("q"), sc, quant)
        k = linear(h, at["wk"], at.get("bk"), ad.get("k"), sc, quant)
        v = linear(h, at["wv"], at.get("bv"), ad.get("v"), sc, quant)
        q = rope(q.reshape(b, s, H, hd), pos, theta)
        k = rope(k.reshape(b, s, Hkv, hd), pos, theta)
        v = v.reshape(b, s, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                         precision=lax.Precision.HIGHEST) / jnp.sqrt(
                             jnp.float32(hd))
        att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v,
                       precision=lax.Precision.HIGHEST).reshape(b, s, H * hd)
        x = x + linear(o, at["wo"], None, ad.get("o"), sc, quant)
        h = rms(x, bp["ln2"], eps)
        mlp = bp["mlp"]
        g = mm(h, mlp["wg"], quant)
        u = mm(h, mlp["wu"], quant)
        x = x + mm(jax.nn.silu(g) * u, mlp["wd"], quant)
        return x, None

    x, _ = lax.scan(layer, x, (params["blocks"], f32(adapter)))
    return rms(x, params["final_norm"].astype(jnp.float32), eps)


def _logits(params, h, quant):
    return mm(h, params["lm_head"].astype(jnp.float32), quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _served_gaps(params, adapter, tokens, served, cfg_items, quant):
    """Per position p of ``tokens`` [S]: how far the reference logit of
    ``served[p]`` lies below the reference's best, and the same for the
    token the ``quant`` control would put first (None: no control)."""
    cfg = dict(cfg_items)
    h = hidden(params, adapter, tokens[None], cfg)[0]
    ref = _logits(params, h, None)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
    if quant is None:
        return gap, jnp.zeros_like(gap)
    hc = hidden(params, adapter, tokens[None], cfg, quant)[0]
    pick = jnp.argmax(_logits(params, hc, quant), axis=-1)
    ctrl = best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return gap, ctrl


def served_gaps(params, adapter, prompt, tokens, pad_to: int,
                cfg: Dict[str, Any], quant: Optional[str] = None):
    """Teacher-force ``prompt + tokens[:-1]`` (padded to ``pad_to``) and
    return, for each served token, its gap below the reference's best
    logit (and the control's gaps when ``quant`` is set)."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    served = np.zeros(pad_to, np.int32)
    start = len(prompt) - 1
    served[start:start + len(tokens)] = tokens
    padded = np.zeros(pad_to, np.int32)
    padded[:len(seq)] = seq
    gap, ctrl = _served_gaps(params, adapter, jnp.asarray(padded),
                             jnp.asarray(served), cfg_key(cfg), quant)
    gap, ctrl = jax.device_get((gap, ctrl))
    sl = slice(start, start + len(tokens))
    return np.asarray(gap[sl]), np.asarray(ctrl[sl])


def loss(params, adapter, batch, cfg: Dict[str, Any], quant=None):
    """Mean next-token cross-entropy over the batch's mask."""
    h = hidden(params, adapter, batch["tokens"], cfg, quant)
    logits = _logits(params, h, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None],
                             axis=-1)[..., 0]
    m = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _loss_and_grad(params, adapter, batch, cfg_items, quant):
    cfg = dict(cfg_items)
    return jax.value_and_grad(loss, argnums=1)(params, adapter, batch, cfg,
                                               quant)


def train_steps(params, adapter, batches, cfg: Dict[str, Any],
                opt: Dict[str, Any], quant: Optional[str] = None):
    """Follow the program's first train steps: returns the loss of each
    step, the first clipped gradient, and the adapter after them all."""
    state = {"t": jnp.zeros((), jnp.int32),
             "m": jax.tree.map(jnp.zeros_like, adapter),
             "v": jax.tree.map(jnp.zeros_like, adapter)}
    opt_items = tuple(sorted(opt.items()))
    losses, first_grad = [], None
    for batch in batches:
        lval, grads = _loss_and_grad(params, adapter, batch, cfg_key(cfg),
                                     quant)
        adapter, state, g = adamw(adapter, grads, state, opt_items)
        losses.append(float(lval))
        if first_grad is None:
            first_grad = g
    return losses, first_grad, adapter


# --------------------------------------------------------------- counts --
def _lora_per_token(cfg: Dict[str, Any]) -> int:
    r = dims(cfg)["r"]
    return sum(2 * r * (din + dout)
               for din, dout in lora_targets(cfg).values())


def linear_per_token(cfg: Dict[str, Any]) -> int:
    """Operations of every layer's projections and feed-forward, and
    the served adapter, for one token (the output head excluded)."""
    m = dims(cfg)
    d, H, Hkv, hd, f = m["d"], m["H"], m["Hkv"], m["hd"], m["f"]
    per_layer = 2 * d * (2 * H * hd + 2 * Hkv * hd) + 2 * 3 * d * f \
        + _lora_per_token(cfg)
    return m["L"] * per_layer


def head_per_token(cfg: Dict[str, Any]) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def attention_ops(cfg: Dict[str, Any], context: int) -> int:
    """Scores and weighted sum of one query token over ``context`` keys,
    all layers."""
    m = dims(cfg)
    return m["L"] * 4 * m["H"] * m["hd"] * context


def prefill_ops(cfg: Dict[str, Any], prompt_lens: Iterable[int]) -> int:
    """Prefill of real prompt tokens: every token's layers and causal
    attention, the output head at each prompt's last token only."""
    total = 0
    for n in prompt_lens:
        total += n * linear_per_token(cfg) + head_per_token(cfg)
        total += attention_ops(cfg, n * (n + 1) // 2)
    return total


def decode_ops(cfg: Dict[str, Any], contexts: Iterable[int]) -> int:
    """One decode tick over live rows, each at its context length."""
    total = 0
    for ctx in contexts:
        total += linear_per_token(cfg) + head_per_token(cfg) \
            + attention_ops(cfg, ctx)
    return total


def train_ops(cfg: Dict[str, Any], rows: int, seq: int) -> int:
    """The train leg: forward (head at every position) and the input
    gradients, which cost the forward again, plus the adapter's weight
    gradients.  The frozen base weights get no weight gradients."""
    tokens = rows * seq
    fwd = tokens * (linear_per_token(cfg) + head_per_token(cfg)) \
        + rows * attention_ops(cfg, seq * (seq + 1) // 2)
    return 2 * fwd + tokens * dims(cfg)["L"] * _lora_per_token(cfg)


def paged_attention(cfg: Dict[str, Any], contexts: Iterable[int],
                    kv_bytes: int = 2) -> Dict[str, int]:
    """What ``paged_decode_attention`` needs for live rows at their
    context lengths, over all layers: 4*H*hd operations per key, and
    each live key and value read once plus each query read and output
    written once."""
    m = dims(cfg)
    ctx = list(contexts)
    keys = sum(ctx)
    ops = m["L"] * 4 * m["H"] * m["hd"] * keys
    nbytes = m["L"] * (2 * keys * m["Hkv"] * m["hd"] * kv_bytes
                       + 2 * len(ctx) * m["H"] * m["hd"] * kv_bytes)
    return {"ops": ops, "bytes": nbytes}
