"""Mixture-of-Experts MLP with GShard/Switch-style capacity dispatch.

Routing is computed per *group* (a contiguous block of tokens) so the
dispatch/combine one-hot tensors stay O(group² · cf) instead of O(T²);
groups are sharded over the data axis and experts over the model axis
(EP, ``moe_shard="ep"``) or the per-expert ff dim over the model axis
(TP, ``moe_shard="tp"`` — grok's 8 experts don't divide a 16-way axis).

The GSPMD partitioner turns the dispatch einsum into the expected
all-to-all traffic; the dry-run's collective-bytes parse confirms it.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.models.sharding import shard


class MoEParams(NamedTuple):
    router: jax.Array  # [D, E]
    wg: jax.Array      # [E, D, F]
    wu: jax.Array      # [E, D, F]
    wd: jax.Array      # [E, F, D]


def init_moe(key, cfg: ModelConfig) -> MoEParams:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    init = lambda k, di, do: (
        jax.random.normal(k, (e, di, do), jnp.float32)
        / math.sqrt(di)).astype(dtype)
    return MoEParams(
        router=dense_init(ks[0], d, e, jnp.float32),
        wg=init(ks[1], d, f), wu=init(ks[2], d, f), wd=init(ks[3], f, d))


def _routing(logits: jax.Array, top_k: int, capacity: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-k routing with per-expert capacity.

    logits: [G, T, E].  Returns (dispatch [G,T,E,C] bool-ish,
    combine [G,T,E,C], aux_loss scalar).
    """
    g, t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # [G,T,K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=1)                           # [G,E]
    top1 = jax.nn.one_hot(gate_idx[..., 0], e)
    ce = jnp.mean(top1, axis=1)
    aux = jnp.mean(jnp.sum(me * ce, axis=-1)) * e

    # slot ordering: token-major, slot-minor priority
    oh = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)      # [G,T,K,E]
    oh_flat = oh.transpose(0, 2, 1, 3).reshape(g, top_k * t, e)
    # priority: slot-0 of every token first (GShard), then slot-1, ...
    pos = jnp.cumsum(oh_flat, axis=1) - oh_flat            # [G,K*T,E]
    pos = jnp.sum(pos * oh_flat, axis=-1)                  # [G,K*T]
    keep = pos < capacity
    pos_k = pos.reshape(g, top_k, t).transpose(0, 2, 1)    # [G,T,K]
    keep_k = keep.reshape(g, top_k, t).transpose(0, 2, 1)

    disp_oh = jax.nn.one_hot(pos_k, capacity, dtype=jnp.float32)  # [G,T,K,C]
    gate_keep = gate_vals * keep_k
    # combine[G,T,E,C] = sum_k gate * onehot(expert) * onehot(pos)
    combine = jnp.einsum("gtke,gtkc->gtec",
                         oh.astype(jnp.float32) *
                         gate_keep[..., None], disp_oh)
    dispatch = jnp.einsum("gtke,gtkc->gtec",
                          oh.astype(jnp.float32) * keep_k[..., None],
                          disp_oh)
    return dispatch, combine, aux


def moe_decode_shardmap(params: MoEParams, x: jax.Array, cfg: ModelConfig
                        ) -> Tuple[jax.Array, jax.Array]:
    """Explicit-SPMD MoE for small-token (decode) steps.

    With ≤ a few hundred tokens, token activations are tiny (~MBs) while
    expert weights are GBs/device-slice; GSPMD's einsum partitioning
    gathers weights over the data axis (§Perf iteration 3, refuted).
    This shard_map keeps every weight slice resident: tokens are
    replicated, each device contracts its (D-slice × F-slice) block, and
    only capacity-sized f32 partials cross the mesh (psum over data for
    the up-projections, psum over model for the down-projection).
    Works for both expert layouts: EP (experts over model) and TP
    (per-expert ff over model).
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.sharding import current_mesh, current_rules
    mesh = current_mesh()
    rules = current_rules()
    bt, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = bt * s
    xt = x.reshape(t, d)
    capacity = max(k, int(math.ceil(t * k * cfg.capacity_factor / e)))

    def _ax(a):
        return a if isinstance(a, str) and a in mesh.shape else None

    d_ax = _ax(rules.w_embed)
    e_ax = _ax(rules.experts)
    f_ax = _ax(rules.expert_ff)
    d_n = mesh.shape.get(d_ax, 1)
    e_n = mesh.shape.get(e_ax, 1)
    f_n = mesh.shape.get(f_ax, 1)

    def body(xt_, router, wg, wu, wd):
        logits = xt_.astype(jnp.float32) @ router          # [T, E]
        dispatch, combine, aux = _routing(logits[None], k, capacity)
        dispatch, combine = dispatch[0], combine[0]        # [T, E, C]
        ein = jnp.einsum("tec,td->ecd", dispatch.astype(xt_.dtype), xt_)
        # slice tokens to this device's resident blocks
        if e_ax is not None:
            ei = lax.axis_index(e_ax) * (e // e_n)
            ein = lax.dynamic_slice_in_dim(ein, ei, e // e_n, axis=0)
        if d_ax is not None:
            di = lax.axis_index(d_ax) * (d // d_n)
            ein = lax.dynamic_slice_in_dim(ein, di, d // d_n, axis=2)
        h_g = jnp.einsum("ecd,edf->ecf", ein, wg,
                         preferred_element_type=jnp.float32)
        h_u = jnp.einsum("ecd,edf->ecf", ein, wu,
                         preferred_element_type=jnp.float32)
        if d_ax is not None:                               # contraction partial
            h_g = lax.psum(h_g, d_ax)
            h_u = lax.psum(h_u, d_ax)
        h = (jax.nn.silu(h_g) * h_u).astype(xt_.dtype)     # [E_l, C, F_l]
        eout = jnp.einsum("ecf,efd->ecd", h, wd,
                          preferred_element_type=jnp.float32)
        if f_ax is not None:                               # contraction partial
            eout = lax.psum(eout, f_ax)
        # combine back to tokens; un-slice experts via psum over e_ax
        comb = combine
        if e_ax is not None:
            ci = lax.axis_index(e_ax) * (e // e_n)
            comb = lax.dynamic_slice_in_dim(comb, ci, e // e_n, axis=1)
        y_part = jnp.einsum("tec,ecd->td", comb.astype(jnp.float32), eout)
        if e_ax is not None:
            y_part = lax.psum(y_part, e_ax)
        if d_ax is not None:                               # d was sliced
            y = lax.all_gather(y_part, d_ax, axis=1, tiled=True)
        else:
            y = y_part
        return y.astype(xt_.dtype), aux

    pw_g = P(e_ax, d_ax, f_ax)
    pw_d = P(e_ax, f_ax, d_ax)
    y, aux = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), pw_g, pw_g, pw_d),
        out_specs=(P(), P()),
    )(xt, params.router, params.wg, params.wu, params.wd)
    return y.reshape(bt, s, d), aux


def _shardmap_eligible(cfg: ModelConfig) -> bool:
    from repro.models.sharding import current_mesh, current_rules
    mesh = current_mesh()
    if mesh is None:
        return False
    rules = current_rules()
    for dim, ax in ((cfg.d_model, rules.w_embed),
                    (cfg.n_experts, rules.experts),
                    (cfg.d_ff, rules.expert_ff)):
        if isinstance(ax, str) and ax in mesh.shape \
                and dim % mesh.shape[ax] != 0:
            return False
    return True


def moe_mlp(params: MoEParams, x: jax.Array, cfg: ModelConfig,
            group_size: int = 512) -> Tuple[jax.Array, jax.Array]:
    """x: [Bt, S, D] -> ([Bt, S, D], aux_loss)."""
    from repro.models.sharding import current_mesh
    bt, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = bt * s
    if tokens <= 1024 and _shardmap_eligible(cfg):
        return moe_decode_shardmap(params, x, cfg)
    gsz = min(group_size, tokens)
    g = tokens // gsz
    assert g * gsz == tokens, f"tokens {tokens} % group {gsz} != 0"
    xg = x.reshape(g, gsz, d)
    xg = shard(xg, "batch", None, "embed")

    capacity = max(k, int(math.ceil(gsz * k * cfg.capacity_factor / e)))
    logits = xg.astype(jnp.float32) @ params.router        # [G,T,E]
    dispatch, combine, aux = _routing(logits, k, capacity)
    dispatch = dispatch.astype(x.dtype)
    dispatch = shard(dispatch, "batch", None, "experts", None)
    combine = shard(combine.astype(jnp.float32),
                    "batch", None, "experts", None)

    ein = jnp.einsum("gtec,gtd->gecd", dispatch, xg)       # expert inputs
    ein = shard(ein, "batch", "experts", "capacity", "embed")
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", ein, params.wg)) \
        * jnp.einsum("gecd,edf->gecf", ein, params.wu)
    h = shard(h, "batch", "experts", "capacity", "expert_ff")
    eout = jnp.einsum("gecf,efd->gecd", h, params.wd)
    eout = shard(eout, "batch", "experts", "capacity", "embed")

    yg = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), eout)
    y = yg.reshape(bt, s, d)
    return shard(y, "batch", "seq", "embed"), aux
