"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.kernel import fused_rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssm_scan.ops import ssm_scan_batched
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def _mha_ref(q, k, v, causal):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], hd)
    o = attention_ref(qf, kf, vf, n_q_heads_per_kv=G, causal=causal)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 2, 2, 64),       # MHA
    (2, 256, 4, 2, 64),       # GQA 2:1
    (1, 384, 8, 1, 32),       # MQA, ragged seq vs block
    (2, 128, 3, 1, 128),      # odd head count
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, hd, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    out = flash_mha(q, k, v, causal=causal, interpret=True)
    ref = _mha_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.bfloat16)
    out = flash_mha(q, k, v, causal=True, interpret=True).astype(jnp.float32)
    ref = _mha_ref(q, k, v, True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


def test_flash_attention_block_invariance():
    """Block-shape choice must not change the result."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    a = flash_mha(q, k, v, block_q=64, block_k=64, interpret=True)
    b = flash_mha(q, k, v, block_q=128, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("shape,dtype", [
    ((64, 256), jnp.float32),
    ((3, 50, 512), jnp.bfloat16),
    ((1, 1, 128), jnp.float32),
])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
    out = fused_rmsnorm(x, w, interpret=True).astype(jnp.float32)
    ref = rmsnorm_ref(x, w).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.slow
@given(st.integers(2, 300), st.integers(1, 700))
@settings(max_examples=12, deadline=None)
def test_ssm_scan_property(S, C):
    """Property: kernel == associative-scan oracle across shapes."""
    a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(S), (S, C)))
    b = jax.random.normal(jax.random.PRNGKey(C), (S, C))
    out = ssm_scan_batched(a, b, interpret=True)
    ref = ssm_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ssm_scan_batched_3d():
    a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (2, 64, 96)))
    b = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 96))
    out = ssm_scan_batched(a, b, interpret=True)
    ref = jax.vmap(ssm_scan_ref)(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,C,dtype", [
    (37, 200, jnp.bfloat16),   # 16-row bf16 tiles, padded time and channels
    (256, 640, jnp.float32),   # two time blocks, two channel blocks
])
def test_ssm_scan_tiles(S, C, dtype):
    a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (S, C)))
    b = jax.random.normal(jax.random.PRNGKey(4), (S, C))
    a, b = a.astype(dtype), b.astype(dtype)
    out = ssm_scan_batched(a, b, interpret=True).astype(jnp.float32)
    ref = ssm_scan_ref(a, b).astype(jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)
