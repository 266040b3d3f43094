"""Compile the served decode path for a TPU v5e that is described, not
attached: qwen1.5-0.5b at its published widths in bf16, 16 slots of
1,024 tokens in a paged pool of 1,025 blocks of 16.  Nothing runs; the
chip's compiler must accept each program, keep the Pallas paged-decode
kernel in it (``tpu_custom_call``), and fit it in one chip's 16 GB.  The
decode and fused programs, compiled with the pool donated as the server
compiles them, must hand the pool back in place and never slice a
layer's pool out of the stack or stack it back (``_pool_ops``).

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports
this file."""
import re

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


_INSTR = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\{([^}]*)\}\S* "
                    r"([\w-]+)\(([^)]*)\)")


def _pool_ops(hlo: str, hkv: int, d: int, relayout: bool = True):
    """The ops of a compiled program that move a pool-shaped array: any
    ``dynamic-slice`` / ``dynamic-update-slice`` of a pool, any ``copy``
    of one layer's pool ([N, BLOCK, hkv, d] or [1, N, BLOCK, hkv, d]),
    and any ``copy`` of the whole stacked pool that keeps its layout.  A
    whole-pool copy that changes the layout (the pool's default device
    layout into the kernel's row-major one and back) is the one copy
    the stacked pool may take; the new token's write is a ``scatter``."""
    pool = [N_BLOCKS, BLOCK, hkv, d]
    defs, bad = {}, []
    for name, dims, layout, op, args in _INSTR.findall(hlo):
        shape = [int(x) for x in dims.split(",") if x]
        minor_to_major = layout.split(":")[0]
        defs[name] = minor_to_major
        if shape[-4:] != pool or len(shape) > 5:
            continue
        per_layer = len(shape) == 4 or shape[0] == 1
        if op in ("dynamic-slice", "dynamic-update-slice"):
            bad.append(f"{op} {name} {shape}")
        elif op == "copy":
            src = defs.get(args.strip().lstrip("%"))
            if (per_layer or not relayout or src is None
                    or src == minor_to_major):
                bad.append(f"copy {name} {shape} {{{src}}}->"
                           f"{{{minor_to_major}}}")
    return bad


def _pool_aliased(hlo: str) -> bool:
    """Every whole-pool entry parameter is aliased to an output."""
    entry = hlo[hlo.index("\nENTRY "):]
    params = re.findall(r"= \w+\[\d+," + str(N_BLOCKS)
                        + r",[\d,]*\]\{[^}]*\} parameter\((\d+)\)", entry)
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}",
                         hlo.split("\n", 1)[0])
    return bool(params) and set(params) <= set(aliased)


def _check(compiled, pool=None):
    """``pool`` = (hkv, d) for a program that carries the stacked pool."""
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, \
        "the Pallas paged-decode kernel is missing from the program"
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"
    if pool is not None:
        assert _pool_ops(hlo, *pool) == [], _pool_ops(hlo, *pool)
        assert _pool_aliased(hlo), "the pool is not updated in place"


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
        model.decode_step_paged, donate_argnums=(2,),
        static_argnames=("ring_len", "attn_backend")).lower(
        params, stacked, caches, tok, pos, tbl, ring_len=MAX_SEQ,
        attn_backend="pallas", adapter_idx=idx).compile()
    _check(compiled, pool=(model.cfg.n_kv_heads, model.cfg.head_dim))


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
        engine.combined_step_paged, donate_argnums=(2, 4),
        static_argnames=("ring_len", "attn_backend")).lower(
        params, lora, opt, batch, caches, tok, pos, tbl, ring_len=MAX_SEQ,
        serve_lora=stacked, attn_backend="pallas",
        serve_adapter_idx=idx).compile()
    _check(compiled, pool=(model.cfg.n_kv_heads, model.cfg.head_dim))


def test_gqa_decode_step_paged_keeps_the_pool_in_place(one_chip):
    """internlm2-1.8b (16 query over 8 KV heads of 128): the pool's
    default v5e layout is already the kernel's row-major one, so the
    decode program takes no copy of it at all."""
    model = make_engine(get_config("internlm2-1.8b")).model
    params, stacked, caches, tok, pos, tbl, idx = _decode_args(model,
                                                               one_chip)
    compiled = jax.jit(
        model.decode_step_paged, donate_argnums=(2,),
        static_argnames=("ring_len", "attn_backend")).lower(
        params, stacked, caches, tok, pos, tbl, attn_backend="pallas",
        adapter_idx=idx).compile()
    cfg = model.cfg
    _check(compiled, pool=(cfg.n_kv_heads, cfg.head_dim))
    assert _pool_ops(compiled.as_text(), cfg.n_kv_heads, cfg.head_dim,
                     relayout=False) == []
