"""Pallas kernel validation: interpret-mode execution vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention, paged_decode_attention,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("m,k,n,r", [(128, 256, 128, 8), (256, 512, 384, 16),
                                     (128, 128, 128, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul(m, k, n, r, dtype):
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (k, n), jnp.float32) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (k, r), jnp.float32) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, n), jnp.float32) * 0.05).astype(dtype)
    y = lora_matmul(x, w, a, b, 2.0, bm=128, bn=128, bk=128, interpret=True)
    yr = ref.lora_matmul(x, w, a, b, 2.0)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,hkv,sq,skv,causal,window", [
    (2, 4, 2, 256, 256, True, 0),
    (1, 8, 2, 300, 300, True, 0),       # ragged / padded path
    (2, 4, 4, 128, 384, False, 0),      # cross-attention style
    (1, 4, 2, 512, 512, True, 128),     # sliding window
])
def test_flash_attention(b, h, hkv, sq, skv, causal, window):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, sq, 64), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, 64), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, 64), jnp.float32)
    y = flash_attention(q, k, v, causal=causal, window=window,
                        bq=128, bk=128, interpret=True)
    yr = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 8, 2, 512, 64), (3, 4, 4, 300, 128), (1, 16, 2, 1024, 64)])
def test_decode_attention(b, h, hkv, s, d):
    ks = jax.random.split(jax.random.key(2), 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    kl = jax.random.randint(ks[3], (b,), 1, s + 1)
    y = decode_attention(q, kc, vc, kl, bk=128, interpret=True)
    yr = ref.decode_attention(q, kc, vc, kl)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,hkv,nb_pool,bs,nb,d,n_layers,layer", [
    pytest.param(2, 8, 2, 16, 16, 4, 64, None, 0,
                 id="2-8-2-16-16-4-64"),      # GQA, short tables
    pytest.param(3, 4, 4, 12, 8, 8, 128, None, 0,
                 id="3-4-4-12-8-8-128"),      # MHA, longer walk
    pytest.param(1, 16, 2, 32, 32, 6, 64, None, 0,
                 id="1-16-2-32-32-6-64"),     # wide grouping
    # stacked [L, n_blocks, bs, Hkv, D] pool read at one layer
    pytest.param(2, 8, 2, 16, 16, 4, 64, 3, 0, id="stacked-gqa-layer0"),
    pytest.param(2, 8, 2, 16, 16, 4, 64, 3, 2, id="stacked-gqa-layer2"),
    pytest.param(3, 4, 4, 12, 8, 8, 128, 3, 0, id="stacked-mha-layer0"),
    pytest.param(3, 4, 4, 12, 8, 8, 128, 3, 2, id="stacked-mha-layer2"),
])
def test_paged_decode_attention(b, h, hkv, nb_pool, bs, nb, d, n_layers,
                                layer):
    """Block-table walk over a shuffled pool == dense attention over
    the gathered logical cache (of ``pool[layer]`` for a stacked pool;
    ``n_layers`` None is the 4-D single-layer pool)."""
    ks = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    lead = () if n_layers is None else (n_layers,)
    kp = jax.random.normal(ks[1], lead + (nb_pool, bs, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], lead + (nb_pool, bs, hkv, d), jnp.float32)
    rng = np.random.default_rng(7)
    # distinct non-scratch blocks per sequence, shuffled pool order
    tables = np.stack([rng.permutation(np.arange(1, nb_pool))[:nb]
                       for _ in range(b)]).astype(np.int32)
    kl = jax.random.randint(ks[3], (b,), 1, nb * bs + 1)
    if n_layers is None:
        y = paged_decode_attention(q, kp, vp, jnp.asarray(tables), kl,
                                   interpret=True)
        k_l, v_l = kp, vp
    else:
        y = paged_decode_attention(q, kp, vp, jnp.asarray(tables), kl,
                                   jnp.int32(layer), interpret=True)
        k_l, v_l = kp[layer], vp[layer]
    k_log = k_l[tables].reshape(b, nb * bs, hkv, d).transpose(0, 2, 1, 3)
    v_log = v_l[tables].reshape(b, nb * bs, hkv, d).transpose(0, 2, 1, 3)
    yr = ref.decode_attention(q, k_log, v_log, kl)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_contiguous_decode_dispatches_to_paged_kernel():
    """layers.attention_decode with the kernel forced on (identity
    block tables) must match its jnp path."""
    from repro.models.layers import attention_decode
    ks = jax.random.split(jax.random.key(8), 3)
    b, s, hq, hkv, d = 3, 48, 8, 2, 64
    q = jax.random.normal(ks[0], (b, 1, hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    klen = jnp.asarray([1, 17, 48], jnp.int32)
    y_jnp = attention_decode(q, kc, vc, klen, backend="jnp")
    y_ker = attention_decode(q, kc, vc, klen, backend="interpret")
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_jnp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,s,p,n,chunk", [
    (2, 4, 256, 32, 16, 64), (1, 2, 300, 64, 32, 128),
    (2, 3, 128, 16, 8, 32)])
def test_ssd_scan(b, h, s, p, n, chunk):
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (b, h, s, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.3
    cm = jax.random.normal(ks[4], (b, s, n), jnp.float32) * 0.3
    y, fin = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, finr = ref.ssd_scan(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1),
                            a, bm, cm)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(yr.transpose(0, 2, 1, 3)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               rtol=1e-4, atol=1e-4)


def test_ops_dispatch_cpu_uses_ref():
    """On CPU without force_kernel, ops must route to the oracle."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(ks[0], (4, 64, 32), jnp.float32)
    w = jax.random.normal(ks[1], (32, 48), jnp.float32)
    a = jax.random.normal(ks[2], (32, 8), jnp.float32)
    b = jax.random.normal(ks[3], (8, 48), jnp.float32)
    y = ops.lora_matmul(x, w, a, b, 1.5)
    yr = ref.lora_matmul(x.reshape(-1, 32), w, a, b, 1.5).reshape(4, 64, 48)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)


def test_ops_force_kernel_pads_odd_shapes():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(ks[0], (3, 50, 70), jnp.float32)
    w = jax.random.normal(ks[1], (70, 90), jnp.float32) * 0.1
    a = jax.random.normal(ks[2], (70, 4), jnp.float32) * 0.1
    b = jax.random.normal(ks[3], (4, 90), jnp.float32) * 0.1
    y = ops.lora_matmul(x, w, a, b, 1.0, force_kernel=True, block=64)
    yr = ref.lora_matmul(x.reshape(-1, 70), w, a, b, 1.0).reshape(3, 50, 90)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
