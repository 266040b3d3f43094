"""Sharding-rule machinery: logical-axis resolution, divisibility
filtering, duplicate-axis dedup, per-arch coverage."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import (
    batch_shardings, logical_axes_for, param_shardings,
    rules_for,
)
from repro.models.model import build
from repro.models.sharding import (
    RULES_TP_FSDP, ShardingRules, _filter_spec, sharding_context, shard,
)


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_filter_spec_drops_nondivisible():
    mesh = _mesh11()
    # craft a fake mesh shape dict via a real mesh of size 1 but checking
    # logic with the mesh axis sizes it reports
    spec = _filter_spec(P("model", "data"), mesh, (25, 16))
    # axes of size 1 always divide; just sanity-check structure
    assert len(spec) == 2


def test_logical_axes_for_paths():
    cfg = get_config("llama3-8b")
    assert logical_axes_for("blocks/attn/wq", 3, cfg) == \
        (None, "w_embed", "heads")
    assert logical_axes_for("blocks/mlp/wd", 3, cfg) == \
        (None, "ff", "w_embed")
    assert logical_axes_for("embed", 2, cfg) == ("vocab", "w_embed")
    assert logical_axes_for("blocks/q/a", 3, cfg) == (None, None, None)


def test_vlm_paths_two_leading():
    cfg = get_config("llama-3.2-vision-90b")
    assert logical_axes_for("blocks/attn/wq", 4, cfg) == \
        (None, None, "w_embed", "heads")
    assert logical_axes_for("cross/attn/wq", 3, cfg) == \
        (None, "w_embed", "heads")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_shardings_cover_every_leaf(arch):
    """Every param/adapter leaf must resolve to a valid NamedSharding on
    the (1,1) stand-in mesh — guards the path-table against drift."""
    cfg = get_config(arch).scaled()
    mesh = _mesh11()
    rules = rules_for(cfg, mesh, "train")
    model = build(cfg)
    specs = model.param_specs()
    sh = param_shardings(specs, cfg, mesh, rules)
    assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(specs))
    lsh = param_shardings(model.lora_specs(), cfg, mesh, rules)
    assert all(s is not None for s in jax.tree.leaves(lsh))


def test_rules_for_head_fallback():
    mesh16 = _mesh11()
    # qwen3 has 40 heads: on a 16-way model axis they don't divide —
    # emulate by checking the rule function's branch directly

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    r = rules_for(get_config("qwen3-14b"), FakeMesh(), "train")
    assert r.heads is None and r.q_seq == "model"
    r2 = rules_for(get_config("llama3-8b"), FakeMesh(), "train")
    assert r2.heads == "model" and r2.kv_seq == "model"  # kv=8 < 16
    r3 = rules_for(get_config("moonshot-v1-16b-a3b"), FakeMesh(), "train")
    assert r3.experts == "model"
    r4 = rules_for(get_config("grok-1-314b"), FakeMesh(), "train")
    assert r4.experts is None and r4.expert_ff == "model"


def test_shard_noop_without_context():
    x = jnp.ones((4, 4))
    assert shard(x, "batch", "embed") is x


def test_shard_constraint_under_context():
    mesh = _mesh11()
    with sharding_context(mesh, RULES_TP_FSDP):
        y = shard(jnp.ones((4, 4)), "batch", "embed")
        assert y.shape == (4, 4)
