"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), the
variant without query compression:

    q            = x W_Q                      [H, nope + rope]
    c, k_pe      = x W_KVA                    [kv_lora_rank], [rope]
    c            = RMSNorm(c)
    k_nope, v    = c W_UK, c W_UV             [H, nope], [H, v]
    q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)     (k_pe shared by every head)
    o            = softmax(scale [q_nope; q_pe] . [k_nope; k_pe]) v
    y            = o W_O

The cache holds only c and the rotated k_pe, `kv_lora_rank + rope` numbers
a token (576 in DeepSeek-V2-Lite).  Prefill and training decompress k_nope
and v from the latent; decode uses the absorbed form and reads only the
latent cache: q_nope W_UK^T is a `kv_lora_rank`-wide query scored against
c, so one product of the (latent + rope)-wide query against the cache
gives every score, and the values are (p . c) W_UV.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import NEG_INF, cache_len, cache_put, layer_of
from .common import apply_rope, rms_norm, rope_frequencies


class MLACache(NamedTuple):
    ckv: jax.Array  # [B, S_cap, kv_lora_rank + rope]: normed latent, k_pe


def init_mla(cfg, key) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, vd, dt = cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.pdtype
    ks = jax.random.split(key, 5)
    s_d, s_r = float(1.0 / np.sqrt(d)), float(1.0 / np.sqrt(r))
    return {
        "wq": jax.random.normal(ks[0], (d, h, nope + rope), dt) * s_d,
        "wkv_a": jax.random.normal(ks[1], (d, r + rope), dt) * s_d,
        "kv_norm": jnp.zeros((r,), dt),
        "wk_b": jax.random.normal(ks[2], (r, h, nope), dt) * s_r,
        "wv_b": jax.random.normal(ks[3], (r, h, vd), dt) * s_r,
        "wo": jax.random.normal(ks[4], (h, vd, d), dt)
        * float(1.0 / np.sqrt(h * vd)),
    }


def init_mla_cache(cfg, batch: int, cap: int, dtype) -> MLACache:
    """A latent cache of `cap` positions, made `cache_len(cap)` long."""
    return MLACache(jnp.zeros(
        (batch, cache_len(cap), cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        dtype))


def _inv_freq(cfg):
    rope = cfg.qk_rope_head_dim
    if cfg.rope_yarn is None:
        return rope_frequencies(rope, cfg.rope_theta)
    return jnp.asarray(cfg.rope_yarn.inv_freq(rope, cfg.rope_theta))


def _ckv_write(ckv, new, pos, layer=None):
    with jax.named_scope("kv_write"):
        return cache_put(ckv, new, pos, layer)


def mla_attention(cfg, p, x, positions, *, cache: MLACache | None = None,
                  cache_pos=None, layer=None):
    """Latent self-attention.  Train: causal over x.  Prefill (cache given,
    cache_pos None): writes the latent cache at [0, S).  Decode (cache_pos
    scalar, S == 1): writes at cache_pos and attends over cache[<= pos] in
    the absorbed form; with `layer` the cache is stacked [L, ...] and only
    its slice `layer` is written and read.  Returns (y, new_cache)."""
    b, s, _ = x.shape
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    inv = _inv_freq(cfg)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = x @ p["wkv_a"]
    latent = rms_norm(p["kv_norm"], ckv[..., :r])
    q_pe = apply_rope(q_pe, positions, inv_freq=inv)
    k_pe = apply_rope(ckv[..., None, r:], positions, inv_freq=inv)[:, :, 0]
    if cfg.rope_yarn is not None and cfg.rope_yarn.cos_scale != 1.0:
        q_pe = q_pe * cfg.rope_yarn.cos_scale
        k_pe = k_pe * cfg.rope_yarn.cos_scale
    new = jnp.concatenate([latent, k_pe], axis=-1)
    scale = cfg.mla_scale
    if cache is None or cache_pos is None:
        new_cache = None if cache is None else \
            MLACache(_ckv_write(cache.ckv, new, 0))
        k_nope = jnp.einsum("bsr,rhn->bshn", latent, p["wk_b"])
        v = jnp.einsum("bsr,rhv->bshv", latent, p["wv_b"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], k_nope.shape[:3]
                                      + k_pe.shape[-1:])], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        logits = jnp.einsum("bshe,bthe->bhst", qf, k) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(causal[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhst,bthv->bshv", probs.astype(v.dtype), v)
    else:
        pos = jnp.asarray(cache_pos, jnp.int32)
        new_cache = MLACache(_ckv_write(cache.ckv, new, pos, layer))
        ckv_all = layer_of(new_cache.ckv, layer)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["wk_b"])
            qf = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)
            logits = jnp.einsum("bshc,btc->bhst", qf,
                                ckv_all.astype(qf.dtype)) * scale
            seen = jnp.arange(ckv_all.shape[1]) <= pos
            logits = jnp.where(seen[None, None, None], logits, NEG_INF)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            o_lat = jnp.einsum("bhst,btr->bshr", probs.astype(qf.dtype),
                               ckv_all[..., :r].astype(qf.dtype))
            out = jnp.einsum("bshr,rhv->bshv", o_lat, p["wv_b"])
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, new_cache
