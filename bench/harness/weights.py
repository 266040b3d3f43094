"""What every family's weight maker shares: seeded normal draws, and
the size of a tree.  Each family makes its own weights and adapters
(``bench/families/<family>.py``)."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def lora_scaling(cfg: Dict[str, Any]) -> float:
    return float(cfg["lora_alpha"]) / float(cfg["lora_rank"])


def tree_bytes(tree: Any) -> int:
    return sum(int(math.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))
