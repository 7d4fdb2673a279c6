"""Chunked (online-softmax) attention vs materialized-softmax oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.layers.attention import (CACHE_TILE, _sdpa, cache_len, cache_put,
                                   causal_mask, chunked_attention,
                                   init_kv_cache)


def _qkv(rng, b, sq, sk, hq, hkv, dh, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, sq, hq, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, dh)), dtype)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("sq,chunk", [(64, 16), (64, 64), (60, 16),
                                      (128, 32)])
def test_chunked_matches_sdpa(rng, hq, hkv, sq, chunk):
    q, k, v = _qkv(rng, 2, sq, sq, hq, hkv, 16)
    want = _sdpa(q, k, v, causal_mask(sq, sq))
    got = chunked_attention(q, k, v, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_sliding_window(rng):
    sq = 96
    q, k, v = _qkv(rng, 1, sq, sq, 4, 4, 16)
    for w in (8, 32):
        want = _sdpa(q, k, v, causal_mask(sq, sq, window=w))
        got = chunked_attention(q, k, v, window=w, chunk=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_chunked_matches_flash_oracle(rng):
    """Cross-check against the kernels/ref.py flash-attention oracle
    (different layout: [B, H, S, D])."""
    b, s, h, dh = 2, 64, 4, 16
    q, k, v = _qkv(rng, b, s, s, h, h, dh)
    got = chunked_attention(q, k, v, chunk=16)
    want = ref.flash_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-5)


def test_chunked_gradients(rng):
    q, k, v = _qkv(rng, 1, 64, 64, 4, 2, 16)

    def loss_chunked(q, k, v):
        return jnp.sum(chunked_attention(q, k, v, chunk=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_sdpa(q, k, v, causal_mask(64, 64)) ** 2)

    gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_chunked_offset_decode_window(rng):
    """offset-shifted queries (speculative/chunked decode path)."""
    sq, sk = 8, 64
    q, k, v = _qkv(rng, 1, sq, sk, 4, 4, 16)
    offset = sk - sq  # queries are the last sq positions
    want = _sdpa(q, k, v, causal_mask(sq, sk, offset=offset))
    got = chunked_attention(q, k, v, chunk=16, offset=offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bf16_stability(rng):
    q, k, v = _qkv(rng, 1, 128, 128, 4, 4, 32, dtype=jnp.bfloat16)
    out = chunked_attention(q, k, v, chunk=32)
    assert out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, np.float32)).all()


@pytest.mark.parametrize("layer", [None, 2])
@pytest.mark.parametrize("t,pos", [(128, 0), (128, 5), (128, 127),
                                   (256, 128), (256, 200), (256, 255),
                                   (384, 0), (384, 255), (384, 256),
                                   (384, 383), (1152, 1148)])
def test_cache_put_writes_one_row_and_nothing_else(rng, layer, t, pos):
    """A one-token write into a `t`-position cache (one tile or several),
    flat or stacked by layer, changes that one position of that one layer
    and no other number; jitted, with a traced position, as the decode
    scan calls it."""
    shape = (2, 3, t, 4) if layer is None else (4, 2, 3, t, 4)
    buf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, 3, 1, 4)), jnp.float32)
    got = jax.jit(cache_put)(buf, new, jnp.int32(pos),
                             None if layer is None else jnp.int32(layer))
    want = np.array(buf)
    if layer is None:
        want[:, :, pos] = np.asarray(new)[:, :, 0]
    else:
        want[layer, :, :, pos] = np.asarray(new)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("cap,length", [(1, 128), (30, 128), (128, 128),
                                        (129, 256), (1149, 1152)])
def test_a_cache_is_made_whole_tiles_long(cap, length):
    """Every attention cache, and so every caller's, holds `cap` positions
    rounded up to whole tiles, where a one-token write finds its tile."""
    assert cache_len(cap) == length and length % CACHE_TILE == 0
    cache = init_kv_cache(2, 3, cap, 4, jnp.float32)
    assert cache.k.shape == cache.v.shape == (2, 3, length, 4)
