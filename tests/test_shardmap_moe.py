"""Correctness of the shard_map expert-parallel MoE decode path vs the
plain (meshless) einsum path, on a real multi-device faux-CPU mesh."""
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs.base import Family, ModelConfig
from repro.models.moe import MoEParams, init_moe, moe_mlp
from repro.models.sharding import ShardingRules, sharding_context
from jax.sharding import AxisType

for moe_shard, rules_kw in [
    ("ep", dict(experts="model", expert_ff=None, w_embed="data")),
    ("tp", dict(experts=None, expert_ff="model", w_embed="data")),
]:
    cfg = ModelConfig(name="t", family=Family.MOE, n_layers=1,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab_size=64, n_experts=4, top_k=2,
                      dtype="float32", param_dtype="float32",
                      moe_shard=moe_shard)
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)
    y_ref, aux_ref = moe_mlp(p, x, cfg)   # no mesh -> plain path

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = dataclasses.replace(ShardingRules(), **rules_kw)
    with sharding_context(mesh, rules):
        y_sm, aux_sm = jax.jit(lambda pp, xx: moe_mlp(pp, xx, cfg))(p, x)
    err = float(jnp.max(jnp.abs(y_sm - y_ref)))
    err_aux = abs(float(aux_sm) - float(aux_ref))
    print(moe_shard, "err", err, "aux_err", err_aux)
    assert err < 1e-4, (moe_shard, err)
    assert err_aux < 1e-5
print("OK")
"""


def test_shardmap_moe_matches_plain():
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              # force CPU: the faux 8-device mesh needs
                              # the host platform even on TPU hosts
                              "JAX_PLATFORMS": "cpu",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
