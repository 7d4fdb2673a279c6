"""Grouped-query attention with the variants the assigned archs need:

  * GQA with any kv-head count (incl. MQA kv=1 and MHA kv=heads)
  * optional QKV bias (qwen1.5), qk-norm (qwen3), partial rotary (glm4)
  * sliding-window masks (gemma3 local layers, zamba2 long-context)
  * standard RoPE or M-RoPE (qwen2-vl)
  * KV-cache prefill (bulk write) and decode (single-position update)
  * optional cross-attention (seamless enc-dec)

Pure-functional: `attention(params, x, ...) -> (y, new_cache)`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.activations import BATCH, MODEL, constrain

from .common import apply_mrope, apply_rope, rms_norm

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


class KVCache(NamedTuple):
    k: jax.Array  # [B, n_kv, S_cap, Dh]
    v: jax.Array  # [B, n_kv, S_cap, Dh]


def init_attention(d: int, n_heads: int, n_kv: int, head_dim: int, dtype, key,
                   *, qkv_bias: bool = False, qk_norm: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    s = float(1.0 / np.sqrt(d))
    p = {
        "wq": jax.random.normal(ks[0], (d, n_heads, head_dim), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, n_kv, head_dim), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, n_kv, head_dim), dtype) * s,
        "wo": jax.random.normal(ks[3], (n_heads, head_dim, d), dtype)
        * float(1.0 / np.sqrt(n_heads * head_dim)),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


# Positions in one tile of a cache.  The TPU compiler lays a cache out
# with its positions along the minor dimension, in tiles this wide.
# Updated one row at a time, the cache is re-laid-out around the write, a
# copy the size of the cache every step; so a one-token write reads,
# changes and writes back the whole tile that holds the position, in
# place (`cache_put`), and every cache is made a whole number of tiles
# long (`cache_len`) so that each such tile lies inside it.  The chip
# writes a block that is not one whole tile piecemeal, several times
# slower.
CACHE_TILE = 128


def cache_len(cap: int) -> int:
    """`cap` positions rounded up to whole tiles of `CACHE_TILE`: the
    length of a cache that decode writes one token at a time."""
    return -(-cap // CACHE_TILE) * CACHE_TILE


def init_kv_cache(batch: int, n_kv: int, cap: int, head_dim: int,
                  dtype) -> KVCache:
    """A cache of `cap` positions, made `cache_len(cap)` long."""
    z = jnp.zeros((batch, n_kv, cache_len(cap), head_dim), dtype)
    return KVCache(z, z)


def _project_qkv(p, x, positions, *, theta, rotary_dim, mrope_sections):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)
    if positions is not None:
        if mrope_sections is not None:
            q = apply_mrope(q, positions, theta=theta,
                            sections=mrope_sections)
            k = apply_mrope(k, positions, theta=theta,
                            sections=mrope_sections)
        else:
            q = apply_rope(q, positions, theta=theta, rotary_dim=rotary_dim)
            k = apply_rope(k, positions, theta=theta, rotary_dim=rotary_dim)
    return q, k, v


def _sdpa(q, k, v, mask, kv: str = "bthe"):
    """q: [B,S,H,Dh], k/v: [B,T,Hkv,Dh], mask: broadcastable [B,1,S,T].
    kv="bhte": k/v are [B,Hkv,T,Dh], a cache as it is stored, read as it
    lies (query heads grouped onto their kv head), never transposed."""
    hq, hkv = q.shape[2], k.shape[kv.index("h")]
    scale = 1.0 / np.sqrt(q.shape[-1])
    if hq != hkv:
        g = hq // hkv
        qg = q.reshape(q.shape[0], q.shape[1], hkv, g, q.shape[3])
        logits = jnp.einsum(f"bshge,{kv}->bhgst", qg, k) * scale
        if mask is not None:
            logits = jnp.where(mask[:, :, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        out = jnp.einsum(f"bhgst,{kv}->bshge", probs.astype(v.dtype), v)
        return out.reshape(q.shape)
    logits = jnp.einsum(f"bshe,{kv}->bhst", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum(f"bhst,{kv}->bshe", probs.astype(v.dtype), v)


def chunked_attention(q, k, v, *, window=-1, chunk: int = 1024,
                      offset: int = 0):
    """Online-softmax attention over KV chunks (flash-attention in XLA —
    §Perf H5).  Never materializes the [Sq, Sk] logits in HBM: the scan
    carries (acc [B,Hkv,G,Sq,dh] f32, m, l) and each step touches one
    [Sq, chunk] tile.  The chunk body is rematerialized in the backward
    (jax.checkpoint), matching the flash-attention recompute schedule.

    q: [B,Sq,H,dh]; k/v: [B,Sk,Hkv,dh]; causal with optional sliding
    window; `offset` = absolute position of q[0] minus k[0].
    """
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / np.sqrt(dh)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, hkv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, hkv, dh).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(b, sq, hkv, g, dh)
    qi = jnp.arange(sq, dtype=jnp.int32) + offset              # [Sq] abs pos
    w = jnp.asarray(window, jnp.int32)
    w_eff = jnp.where(w > 0, w, jnp.int32(2 ** 30))

    def body(carry, xs):
        acc, m, l = carry
        k_c, v_c, c_idx = xs
        ki = c_idx * chunk + jnp.arange(chunk, dtype=jnp.int32)  # [C]
        logits = jnp.einsum("bshge,bche->bhgsc", qg, k_c) * scale
        mask = (ki[None, :] <= qi[:, None]) & \
            (ki[None, :] > qi[:, None] - w_eff) & \
            (ki[None, :] < sk)                                  # [Sq, C]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        logits = logits.astype(jnp.float32)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgsc,bche->bhgse", p.astype(v_c.dtype), v_c)
        acc_new = acc * alpha[..., None].astype(acc.dtype) + \
            pv.astype(jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(body),
        (acc0, m0, l0),
        (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh).astype(q.dtype)


# Sequence length at/above which the chunked path replaces materialized
# [S, S] logits (train/prefill).  NOTE (§Perf H5, refuted-then-refined):
# chunking does NOT reduce HBM traffic under XLA (each chunk tile still
# crosses fusion boundaries; the true traffic win needs the Pallas flash
# kernel) — but it replaces the O(S^2) logits TEMP with O(S*CHUNK), which
# is what makes 32k prefill lowerable at production batch sizes.  At 4k
# the materialized path touches fewer bytes (no acc re-reads), so the
# threshold sits above train_4k.
CHUNKED_THRESHOLD = 8192
CHUNK = 2048


def causal_mask(sq: int, sk: int, *, window=-1, offset: int = 0):
    """[1, 1, sq, sk] causal (+sliding window if window > 0) mask.
    `offset` = absolute position of query 0 minus key 0.  `window` may be a
    traced scalar (per-layer window as a scan input, e.g. gemma3)."""
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(sk)[None, :]
    w = jnp.asarray(window, jnp.int32)
    w_eff = jnp.where(w > 0, w, jnp.int32(2**30))
    m = (ki <= qi) & (ki > qi - w_eff)
    return m[None, None]


def cache_put(buf, new, pos, layer=None):
    """`new` written into the cache buffer `buf` at sequence offset `pos`
    (axis -2 of both); into its `layer` slice when `buf` is stacked
    [L, ...].  Only the rows written (for one token, its tile of
    `CACHE_TILE` rows, so `buf` is `cache_len` long) are touched, so a
    carried or donated buffer changes in place."""
    new = new.astype(buf.dtype)
    if layer is not None:
        new = new[None]
    if new.shape[-2] == 1:
        t = buf.shape[-2]
        assert t % CACHE_TILE == 0, f"{t} positions are not whole tiles"
        at = pos - pos % CACHE_TILE
        old = jax.lax.dynamic_slice(
            buf, _start(buf.ndim, at, layer),
            new.shape[:-2] + (CACHE_TILE,) + new.shape[-1:])
        hit = (at + jnp.arange(CACHE_TILE) == pos)[:, None]
        new, pos = jnp.where(hit, new, old), at
    return jax.lax.dynamic_update_slice(buf, new, _start(buf.ndim, pos,
                                                         layer))


def _start(ndim, pos, layer):
    start = [0] * ndim
    start[-2] = pos
    if layer is not None:
        start[0] = layer
    return start


def layer_of(buf, layer=None):
    """`buf`, or its `layer` slice when it is stacked [L, ...]."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def _kv_write(cache: KVCache, k, v, pos, layer=None) -> KVCache:
    """k, v [B,S,Hkv,dh] written into the cache at sequence position `pos`
    (and `layer`, see `cache_put`), under the `kv_write` scope that the
    device trace names them by."""
    with jax.named_scope("kv_write"):
        return KVCache(cache_put(cache.k, k.transpose(0, 2, 1, 3), pos, layer),
                       cache_put(cache.v, v.transpose(0, 2, 1, 3), pos, layer))


def attention(p, x, positions, *, theta: float = 10000.0,
              rotary_dim: int | None = None, window: int = -1,
              mrope_sections=None, cache: KVCache | None = None,
              cache_pos=None, layer=None):
    """Self-attention.

    Train / no-cache: full causal (+window) attention over x.
    Prefill: cache provided, cache_pos None -> bulk-write k/v at [0, S).
    Decode: cache provided, cache_pos scalar -> write at cache_pos, attend
            over cache[<=cache_pos] (with optional window).  With `layer`
            the cache is the stacked [L, ...] cache of every layer: the new
            k/v go into slice `layer` in place and that slice is read.
    Returns (y, new_cache).
    """
    b, s, _ = x.shape
    x = constrain(x, BATCH)
    q, k, v = _project_qkv(p, x, positions, theta=theta,
                           rotary_dim=rotary_dim,
                           mrope_sections=mrope_sections)
    # pin the canonical layout: batch over data axes, heads over model —
    # see launch/activations.py (hillclimb H1).  When the head count does
    # not divide the model axis (llama4: 40 heads on 16) attention would
    # be fully replicated across "model"; shard the QUERY sequence dim
    # instead (sequence-parallel attention, §Perf H6) — keys stay full, so
    # causal masking is unchanged and XLA all-gathers only the [B,S,H,dh]
    # output once per layer.
    from repro.launch.activations import current_mesh
    mesh = current_mesh()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    seq_parallel = (cache is None or cache_pos is None) and s > 1 and \
        q.shape[2] % max(msize, 1) != 0 and s % max(msize, 1) == 0
    if seq_parallel:
        q = constrain(q, BATCH, MODEL)
        k = constrain(k, BATCH, None, MODEL)
        v = constrain(v, BATCH, None, MODEL)
    else:
        q = constrain(q, BATCH, None, MODEL)
        k = constrain(k, BATCH, None, MODEL)
        v = constrain(v, BATCH, None, MODEL)
    if cache is None:
        if s >= CHUNKED_THRESHOLD:
            out = chunked_attention(q, k, v, window=window, chunk=CHUNK)
        else:
            out = _sdpa(q, k, v, causal_mask(s, s, window=window))
        new_cache = None
    elif cache_pos is None:  # prefill
        new_cache = _kv_write(cache, k, v, 0)
        if s >= CHUNKED_THRESHOLD:
            out = chunked_attention(q, k, v, window=window, chunk=CHUNK)
        else:
            out = _sdpa(q, k, v, causal_mask(s, s, window=window))
    else:  # decode: s == 1
        pos = jnp.asarray(cache_pos, jnp.int32)
        new_cache = _kv_write(cache, k, v, pos, layer)
        ki = jnp.arange(cache.k.shape[-2])
        w = jnp.asarray(window, jnp.int32)
        w_eff = jnp.where(w > 0, w, jnp.int32(2**30))
        mask = (ki <= pos) & (ki > pos - w_eff)
        out = _sdpa(q, layer_of(new_cache.k, layer),
                    layer_of(new_cache.v, layer), mask[None, None, None],
                    kv="bhte")
    if seq_parallel:
        out = constrain(out, BATCH, MODEL)
    else:
        out = constrain(out, BATCH, None, MODEL)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return constrain(y, BATCH), new_cache


def cross_attention(p, x, memory, positions=None, *, theta: float = 10000.0,
                    kv_cache: KVCache | None = None):
    """Encoder-decoder cross attention.  If kv_cache is given it holds the
    pre-projected encoder K/V (computed once at prefill)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if "q_norm" in p:
        q = rms_norm(p["q_norm"], q)
    if kv_cache is not None:
        k = kv_cache.k.transpose(0, 2, 1, 3)
        v = kv_cache.v.transpose(0, 2, 1, 3)
    else:
        k = jnp.einsum("btd,dhe->bthe", memory, p["wk"])
        v = jnp.einsum("btd,dhe->bthe", memory, p["wv"])
        if "k_norm" in p:
            k = rms_norm(p["k_norm"], k)
    out = _sdpa(q, k, v, None)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


def project_cross_kv(p, memory) -> KVCache:
    k = jnp.einsum("btd,dhe->bthe", memory, p["wk"])
    v = jnp.einsum("btd,dhe->bthe", memory, p["wv"])
    if "k_norm" in p:
        k = rms_norm(p["k_norm"], k)
    return KVCache(k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
