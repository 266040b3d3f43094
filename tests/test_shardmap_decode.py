"""Correctness of the sequence-sharded flash-decode (shard_map) path:
run a real multi-device (faux CPU) mesh in a subprocess and compare
against the unsharded decode numerically."""
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs.registry import get_config
from repro.models.model import build
from repro.models.sharding import ShardingRules, sharding_context
from jax.sharding import AxisType

cfg = get_config("llama3-8b").scaled(n_layers=2, d_model=64, n_heads=4,
                                     d_ff=128, vocab_size=256)
m = build(cfg)
params = m.init(jax.random.key(0))
lora = m.init_lora(jax.random.key(1))
B, S = 4, 32
toks = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)

# reference: no mesh context -> plain decode path
caches = m.init_caches(B, S)
ref = []
for t in range(S):
    lg, caches = m.decode_step(params, lora, caches, toks[:, t:t+1],
                               jnp.int32(t))
    ref.append(lg)

# sharded: 2x4 mesh, kv_seq on "model" (4-way) -> shard_map path
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = dataclasses.replace(
    ShardingRules(), kv_seq="model", kv_batch="data")
with sharding_context(mesh, rules):
    caches = m.init_caches(B, S)
    step = jax.jit(m.decode_step)
    worst = 0.0
    for t in range(S):
        lg, caches = step(params, lora, caches, toks[:, t:t+1],
                          jnp.int32(t))
        worst = max(worst, float(jnp.max(jnp.abs(lg - ref[t]))))
scale = float(jnp.max(jnp.abs(jnp.stack(ref))))
print("WORST", worst, "SCALE", scale)
assert worst / scale < 5e-5, (worst, scale)
print("OK")
"""


def test_shardmap_decode_matches_plain():
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              # force CPU: the faux 8-device mesh needs
                              # the host platform even on TPU hosts
                              "JAX_PLATFORMS": "cpu",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
