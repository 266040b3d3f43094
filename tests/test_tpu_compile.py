"""Compile the served decode path for a TPU v5e that is described, not
attached: qwen1.5-0.5b at its published widths in bf16, 16 slots of
1,024 tokens in a paged pool of 1,025 blocks of 16.  Nothing runs; the
chip's compiler must accept each program, keep the Pallas paged-decode
kernel in it (``tpu_custom_call``), and fit it in one chip's 16 GB.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports
this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.engine import make_engine
from repro.kernels.decode_attention import paged_decode_attention

HBM_BYTES = 16e9          # one v5e chip
SLOTS, MAX_SEQ, BLOCK = 16, 1024, 16
N_BLOCKS = SLOTS * MAX_SEQ // BLOCK + 1       # + scratch block 0
TABLE_W = MAX_SEQ // BLOCK
N_ADAPTERS = 2
TRAIN_BATCH, TRAIN_SEQ = 4, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compile cache off:
    a program compiled for an absent device cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def engine():
    return make_engine(get_config("qwen1.5-0.5b"))


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _decode_args(model, sharding):
    """Shapes of one paged multi-tenant decode tick: base params, two
    stacked tenant adapters, the pool, tokens, positions, tables, and
    each row's adapter slot."""
    i32 = jnp.int32
    params = jax.eval_shape(model.init, jax.random.key(0))
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (s.shape[0], N_ADAPTERS) + s.shape[1:], s.dtype),
        model.lora_specs())
    caches = jax.eval_shape(
        lambda: model.init_paged_caches(N_BLOCKS, BLOCK))
    return (_on(params, sharding), _on(stacked, sharding),
            _on(caches, sharding), _sds((SLOTS, 1), i32, sharding),
            _sds((SLOTS,), i32, sharding),
            _sds((SLOTS, TABLE_W), i32, sharding),
            _sds((SLOTS,), i32, sharding))


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas paged-decode kernel is missing from the program"
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"


def test_paged_decode_attention_compiles(one_chip, engine):
    cfg = engine.model.cfg
    hkv, d = cfg.n_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    pool = _sds((N_BLOCKS, BLOCK, hkv, d), bf16, one_chip)
    compiled = jax.jit(paged_decode_attention).lower(
        _sds((SLOTS, cfg.n_heads, d), bf16, one_chip), pool, pool,
        _sds((SLOTS, TABLE_W), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip)).compile()
    _check(compiled)


def test_decode_step_paged_compiles(one_chip, engine):
    model = engine.model
    params, stacked, caches, tok, pos, tbl, idx = _decode_args(model,
                                                               one_chip)
    compiled = jax.jit(
        model.decode_step_paged,
        static_argnames=("ring_len", "attn_backend")).lower(
        params, stacked, caches, tok, pos, tbl, ring_len=MAX_SEQ,
        attn_backend="pallas", adapter_idx=idx).compile()
    _check(compiled)


def test_combined_step_paged_compiles(one_chip, engine):
    model = engine.model
    params, stacked, caches, tok, pos, tbl, idx = _decode_args(model,
                                                               one_chip)
    lora = _on(model.lora_specs(), one_chip)
    opt = _on(jax.eval_shape(engine.optimizer.init, model.lora_specs()),
              one_chip)
    batch = {"tokens": _sds((TRAIN_BATCH, TRAIN_SEQ), jnp.int32, one_chip),
             "labels": _sds((TRAIN_BATCH, TRAIN_SEQ), jnp.int32, one_chip),
             "mask": _sds((TRAIN_BATCH, TRAIN_SEQ), jnp.float32, one_chip)}
    compiled = jax.jit(
        engine.combined_step_paged,
        static_argnames=("ring_len", "attn_backend")).lower(
        params, lora, opt, batch, caches, tok, pos, tbl, ring_len=MAX_SEQ,
        serve_lora=stacked, attn_backend="pallas",
        serve_adapter_idx=idx).compile()
    _check(compiled)
