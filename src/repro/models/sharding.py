"""Logical-axis sharding: MaxText-style rules mapping logical tensor axes
to physical mesh axes.

Model code annotates tensors with *logical* axis names via ``shard(x,
"batch", "seq", "embed")``.  A ``ShardingRules`` table maps each logical
name to a mesh axis (or None).  Outside a sharding context every
annotation is the identity, so the same model code runs on a single CPU
device (smoke tests) and on the 512-chip production mesh (dry-run).

Hillclimbing swaps rule tables without touching model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of axes, or None)."""
    batch: Axis = ("pod", "data")     # activation batch
    seq: Axis = None                  # sequence (generic)
    act_seq: Axis = None              # residual-stream seq (Megatron-SP)
    q_seq: Axis = None                # attention query seq (head fallback)
    embed: Axis = None                # activation d_model
    heads: Axis = "model"             # attention heads (TP)
    kv_heads: Axis = "model"
    head_dim: Axis = None
    ff: Axis = "model"                # MLP hidden (TP)
    vocab: Axis = "model"             # embedding/logits vocab (TP)
    experts: Axis = "model"           # MoE expert axis (EP)
    expert_ff: Axis = None            # MoE per-expert ff (TP for grok)
    capacity: Axis = None
    layers: Axis = None               # stacked-layer leading axis
    # weight FSDP axes (sharding of the non-TP dim of weights):
    w_embed: Axis = "data"            # d_model dim of weight matrices
    w_ff_in: Axis = "data"            # input dim of down-proj etc.
    conv: Axis = None
    ssm_inner: Axis = "model"         # d_inner of SSD mixer
    ssm_state: Axis = None
    ssm_heads: Axis = "model"
    lora_rank: Axis = None
    kv_batch: Axis = ("pod", "data")  # KV-cache batch
    kv_seq: Axis = None

    def resolve(self, *names: Optional[str]) -> P:
        parts = []
        for n in names:
            if n is None:
                parts.append(None)
            else:
                parts.append(getattr(self, n))
        return P(*parts)


# Presets -------------------------------------------------------------------
RULES_TP_FSDP = ShardingRules()                       # default: TP + FSDP
RULES_TP_ONLY = dataclasses.replace(
    RULES_TP_FSDP, w_embed=None, w_ff_in=None)        # pure TP (replicated DP)
RULES_FSDP_HEAVY = dataclasses.replace(               # FSDP on both weight dims
    RULES_TP_FSDP, w_embed=("pod", "data"), w_ff_in=("pod", "data"))


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: ShardingRules = RULES_TP_FSDP


_CTX = _Ctx()


@contextlib.contextmanager
def sharding_context(mesh: Optional[Mesh], rules: Optional[ShardingRules] = None):
    """Activate a mesh + rule table for ``shard()`` annotations."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    if rules is not None:
        _CTX.rules = rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> ShardingRules:
    return _CTX.rules


def _filter_spec(spec: P, mesh: Mesh, shape) -> P:
    """Drop mesh axes whose size does not divide the tensor dim (keeps the
    dry-run robust for dims like 25 heads or 8 experts on a 16-way axis)."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        kept = []
        for a in axes:
            if a in mesh.shape and dim % (size * mesh.shape[a]) == 0:
                kept.append(a)
                size *= mesh.shape[a]
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def logical_spec(shape, *names: Optional[str]) -> P:
    """Resolve logical names to a PartitionSpec under the current context."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None:
        return P()
    return _filter_spec(rules.resolve(*names), mesh, shape)


def shard(x, *names: Optional[str]):
    """with_sharding_constraint by logical axis names (no-op without mesh)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = logical_spec(x.shape, *names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape, *names: Optional[str]) -> Optional[NamedSharding]:
    mesh = _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_spec(shape, *names))
