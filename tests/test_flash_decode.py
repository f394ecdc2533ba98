"""The flash-decoding kernel, interpreted, against the plain einsum of
``models.attention.decode_attention`` and the oracle ``ref.decode_ref``:
one token's attention over the first ``n`` slots of one layer of a cache
stacked over layers, read in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode.kernel import decode_blocks, flash_decode
from repro.kernels.flash_decode.ops import flash_decode_cache
from repro.kernels.flash_decode.ref import decode_ref
from repro.models.attention import decode_attention, stacked_decode_attention

B, KV, HD = 2, 2, 32


def _case(seed, stack=(), G=6, W=128, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, KV * G, HD), dtype)
    k = jax.random.normal(ks[1], stack + (B, KV, W, HD), dtype)
    v = jax.random.normal(ks[2], stack + (B, KV, W, HD), dtype)
    return q, k, v


def _einsum(q, k, v, at, valid):
    return decode_attention(q, k[at], v[at], valid)


def _close(got, want, tol=3e-2):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_block_sizes_follow_the_shapes():
    # qwen2-1.5b and Qwen3-30B-A3B at the serving cell: 1 MiB blocks
    assert decode_blocks(32 * 2, 768, 128, 2) == (64, 64)
    assert decode_blocks(32 * 4, 768, 128, 2) == (128, 32)
    assert decode_blocks(8, 96, 128, 2) == (8, 32)
    # no 64- or 32-slot block divides the cache: the whole of it
    assert decode_blocks(8, 40, 128, 2) == (8, 40)


@pytest.mark.parametrize("n", [1, 64, 65, 128])
def test_lengths_match_the_einsum(n):
    q, k, v = _case(0, stack=(3,))
    at = (jnp.int32(1),)
    got = stacked_decode_attention(q, k, v, at, jnp.int32(n))
    _close(got, _einsum(q, k, v, at, jnp.arange(128) < n))


@pytest.mark.parametrize("G", [6, 8])
def test_query_groups_match_the_einsum(G):
    q, k, v = _case(1, stack=(2,), G=G)
    at = (jnp.int32(1),)
    got = stacked_decode_attention(q, k, v, at, jnp.int32(100))
    _close(got, _einsum(q, k, v, at, jnp.arange(128) < 100))


@pytest.mark.parametrize("stack,at", [((), ()), ((3,), (2,)),
                                      ((2, 3), (1, 2))])
def test_stacks_match_the_einsum(stack, at):
    # the TPU's operands: the whole stack and the layer's indices
    q, k, v = _case(2, stack=stack)
    at = tuple(jnp.int32(i) for i in at)
    got = flash_decode_cache(q, k, v, at, jnp.int32(77), interpret=True)
    _close(got, _einsum(q, k, v, at, jnp.arange(128) < 77))


@pytest.mark.parametrize("layer", [0, 3])
def test_kernel_reads_its_layer_of_the_stack(layer):
    # the kernel itself, given the whole stack and a layer index, in f32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (4, 6, HD), jnp.float32)
    k = jax.random.normal(ks[1], (4, 4, 128, HD), jnp.float32)
    v = jax.random.normal(ks[2], (4, 4, 128, HD), jnp.float32)
    for n in (1, 64, 90, 128):
        got = flash_decode(q, k, v, jnp.int32(layer), jnp.int32(n),
                           interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(decode_ref(q, k, v, layer, n)),
                                   rtol=1e-5, atol=1e-5)


def test_ring_cache_past_its_size():
    # a Hymba-like ring of 4 sink and 36 window slots, at position 57: the
    # whole ring holds keys (``_write_kv``'s mask), and no block divides it
    W, pos = 40, 57
    q, k, v = _case(4, stack=(2,), W=W)
    at = (jnp.int32(1),)
    valid = (jnp.arange(W) <= pos) | (pos >= W)
    got = stacked_decode_attention(q, k, v, at, jnp.minimum(pos + 1, W))
    _close(got, _einsum(q, k, v, at, valid))


@pytest.mark.parametrize("n", [1, 17, 40])
def test_cache_that_no_block_divides(n):
    q, k, v = _case(5, stack=(2,), W=40)
    at = (jnp.int32(0),)
    got = stacked_decode_attention(q, k, v, at, jnp.int32(n))
    _close(got, _einsum(q, k, v, at, jnp.arange(40) < n))


@pytest.mark.parametrize("n", [1, 64, 65, 100])
def test_slots_past_the_length_never_reach_the_output(n):
    q, k, v = _case(6, stack=(2,))
    at = (jnp.int32(1),)
    clean = stacked_decode_attention(q, k, v, at, jnp.int32(n))
    past = (jnp.arange(128) >= n)[:, None]
    k_nan, v_nan = (jnp.where(past, jnp.nan, c).astype(c.dtype)
                    for c in (k, v))
    got = stacked_decode_attention(q, k_nan, v_nan, at, jnp.int32(n))
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
