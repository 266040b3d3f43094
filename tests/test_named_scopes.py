"""The jitted serving programs name their phases: the layer bodies'
``attn_qkv``, ``kv_write``, ``attn``, ``attn_out``, ``mlp`` and the
``lm_head``, and the fused program's ``serve``, ``train_fwd_bwd`` and
``optimizer``.  A device trace shows an op's ``op_name`` metadata, so
the scopes must survive into the compiled program."""
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import make_batch
from repro.configs.registry import get_config
from repro.core.engine import make_engine
from repro.runtime.serving_loop import _engine_jits

LAYER = {"attn_qkv", "kv_write", "attn", "attn_out", "mlp", "lm_head"}
FUSED = {"serve", "train_fwd_bwd", "optimizer"}


@pytest.fixture(scope="module")
def programs():
    cfg = get_config("qwen1.5-0.5b").scaled()
    engine = make_engine(cfg, lr=3e-3)
    model = engine.model
    params = model.init(jax.random.key(0))
    lora = model.init_lora(jax.random.key(1))
    caches = model.init_paged_caches(9, 4)
    b = 2
    tok = jnp.zeros((b, 1), jnp.int32)
    pos = jnp.array([3, 5], jnp.int32)
    tables = jnp.array([[1, 2], [3, 4]], jnp.int32)
    jits = _engine_jits(engine)
    decode = jits["decode_paged"].lower(
        params, lora, caches, tok, pos, tables).compile().as_text()
    fused = jits["combined_paged"].lower(
        params, lora, engine.optimizer.init(lora),
        make_batch(cfg, batch=2, seq=8), caches, tok, pos,
        tables).compile().as_text()
    return {"decode_step_paged": decode, "combined_step_paged": fused}


def _scopes(hlo: str):
    """Every path component of every op's ``op_name`` metadata."""
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/")}


@pytest.mark.parametrize("program,want", [
    ("decode_step_paged", LAYER),
    ("combined_step_paged", LAYER | FUSED),
])
def test_scope_names_reach_the_compiled_program(programs, program, want):
    hlo = programs[program]
    assert want <= _scopes(hlo), sorted(want - _scopes(hlo))
    # the program keeps its name: the benchmark's readers match it
    assert program in hlo
