"""Production mesh + sharding-rule selection (dry-run deliverable).

``make_production_mesh`` builds the assigned meshes:
  single-pod:  (16, 16)        axes ("data", "model")      — 256 chips
  multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

``rules_for`` adapts the logical-axis rule table per architecture ×
step-kind: archs whose head counts don't divide the model axis fall back
to sequence sharding for attention balance; GQA caches too big for
batch-sharding alone shard their sequence dim; training enables
sequence-parallel residual activations (Megatron-SP style) so the
remat-saved carries stay O(tokens/device).

``param_spec``/``batch_spec`` map parameter/input trees to
PartitionSpecs by tree path — the single source of truth the dry-run,
the trainer, and elastic restore all share.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import Family, ModelConfig, ShapeCell
from repro.models.sharding import (
    RULES_TP_FSDP, ShardingRules, _filter_spec,
)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def rules_for(cfg: ModelConfig, mesh: Mesh, kind: str,
              base: Optional[ShardingRules] = None) -> ShardingRules:
    """Pick the rule table for (arch × step kind) on this mesh."""
    rules = base or RULES_TP_FSDP
    model_n = mesh.shape.get("model", 1)
    upd = {}
    if kind == "train":
        # sequence-parallel residual stream: remat-saved carries shard
        # over the model axis instead of being replicated across it
        upd["act_seq"] = "model"
    if cfg.n_heads % model_n != 0:
        # 25/40-head archs: heads can't split the model axis — balance
        # attention by sharding the query sequence dim instead
        upd["heads"] = None
        upd["kv_heads"] = None
        upd["q_seq"] = "model"
    if cfg.n_kv_heads % model_n != 0:
        # GQA caches too big for batch sharding alone (llama3-class
        # decode_32k is ~550 GB): shard the cache sequence dim
        upd["kv_seq"] = "model"
    if cfg.family is Family.MOE:
        if cfg.moe_shard == "ep" and cfg.n_experts % model_n == 0:
            upd["experts"] = "model"
            upd["expert_ff"] = None
        else:  # grok: 8 experts on a 16-way axis -> per-expert ff TP
            upd["experts"] = None
            upd["expert_ff"] = "model"
    return dataclasses.replace(rules, **upd)


# --------------------------------------------------------------------------
# path -> logical axes for every parameter in the model tree
# --------------------------------------------------------------------------
_PARAM_TABLE = [
    # (path regex, logical axes EXCLUDING stacked leading dims)
    (r"embed$", ("vocab", "w_embed")),
    (r"lm_head$", ("w_embed", "vocab")),
    (r"final_norm$", ()),
    (r"attn/w[qkv]$", ("w_embed", "heads")),
    (r"attn/wo$", ("heads", "w_embed")),
    (r"attn/b[qkv]$", ("heads",)),
    (r"attn/[qk]_norm$", ()),
    (r"mlp/w[gu]$", ("w_embed", "ff")),
    (r"mlp/wd$", ("ff", "w_embed")),
    (r"moe/router$", ("w_embed", None)),
    (r"moe/w[gu]$", ("experts", "w_embed", "expert_ff")),
    (r"moe/wd$", ("experts", "expert_ff", "w_embed")),
    (r"ssm/in_proj$", ("w_embed", "ssm_inner")),
    (r"ssm/out_proj$", ("ssm_inner", "w_embed")),
    (r"ssm/conv_w$", (None, "ssm_inner")),
    (r"ssm/conv_b$", ("ssm_inner",)),
    (r"ssm/(A_log|D_skip|dt_bias)$", ()),
    (r"ssm/norm$", ("ssm_inner",)),
    (r"ln[12]$", ()),
    (r"gate_(attn|mlp)$", ()),
    # LoRA adapters + optimizer state over them: tiny, replicated
    (r"(^|/)(a|b)$", None),
]


def _leading(path: str, cfg: ModelConfig) -> int:
    if path.startswith("blocks/"):
        return 2 if cfg.family is Family.VLM else 1
    if path.startswith("cross/"):
        return 1
    return 0


def logical_axes_for(path: str, ndim: int, cfg: ModelConfig
                     ) -> Tuple[Optional[str], ...]:
    lead = _leading(path, cfg)
    for pat, axes in _PARAM_TABLE:
        if re.search(pat, path):
            if axes is None:
                return (None,) * ndim
            out = (None,) * lead + tuple(axes)
            if len(out) < ndim:            # defensive: pad with None
                out = out + (None,) * (ndim - len(out))
            return out[:ndim]
    return (None,) * ndim


def _resolve(rules: ShardingRules, names, shape, mesh: Mesh) -> P:
    spec = rules.resolve(*names)
    spec = _filter_spec(spec, mesh, shape)
    # drop duplicate mesh-axis usage across dims (illegal in XLA)
    seen = set()
    out = []
    for entry in spec:
        axes = entry if isinstance(entry, tuple) else (
            (entry,) if entry else ())
        kept = tuple(a for a in axes if a not in seen)
        seen.update(kept)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def param_shardings(tree: Any, cfg: ModelConfig, mesh: Mesh,
                    rules: ShardingRules) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        names = logical_axes_for(key, leaf.ndim, cfg)
        out.append(NamedSharding(mesh,
                                 _resolve(rules, names, leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------- batches --
_BATCH_TABLE = [
    (r"tokens$|labels$|mask$|token$", ("batch", None)),
    (r"embeds$|vision$", ("batch", None, None)),
    (r"pos$", ()),
    # caches (leading dims added below by _leading-style logic)
    (r"kv/[01]$", ("kv_batch", "kv_seq", "kv_heads", None)),
    (r"cross_kv/[01]$", ("kv_batch", None, "kv_heads", None)),
    (r"ssm/conv$", ("kv_batch", None, "ssm_inner")),
    (r"ssm/state$", ("kv_batch", "ssm_heads", None, None)),
]


def batch_shardings(tree: Any, cfg: ModelConfig, mesh: Mesh,
                    rules: ShardingRules) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        names: Tuple[Optional[str], ...] = (None,) * leaf.ndim
        for pat, axes in _BATCH_TABLE:
            if re.search(pat, key):
                lead = leaf.ndim - len(axes)
                names = (None,) * max(lead, 0) + tuple(axes)
                names = names[:leaf.ndim]
                break
        out.append(NamedSharding(mesh,
                                 _resolve(rules, names, leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)
