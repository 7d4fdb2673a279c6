"""Encoder-decoder backbone (seamless-m4t-medium).

The speech/modality frontend is a STUB per the assignment: `input_specs()`
provides precomputed frame embeddings [B, frames, d_model].  The encoder is a
bidirectional transformer over frames; the decoder is causal with
cross-attention.  decode_32k: decoder self-cache (32k) + cached cross-K/V.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.layers.attention import (
    KVCache,
    attention,
    cross_attention,
    init_attention,
    init_kv_cache,
    project_cross_kv,
)
from repro.layers.common import (
    cross_entropy,
    embed,
    init_embed,
    init_head,
    init_rms_norm,
    init_swiglu,
    rms_norm,
    swiglu,
    unembed,
)


class EncDecCache(NamedTuple):
    self_kv: KVCache    # stacked [L, B, kv, cap, hd]
    cross_kv: KVCache   # stacked [L, B, kv, frames, hd]


def _init_enc_block(cfg: ArchConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rms_norm(cfg.d_model, cfg.pdtype),
        "attn": init_attention(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.pdtype, k1),
        "ln2": init_rms_norm(cfg.d_model, cfg.pdtype),
        "mlp": init_swiglu(cfg.d_model, cfg.d_ff, cfg.pdtype, k2),
    }


def _init_dec_block(cfg: ArchConfig, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_rms_norm(cfg.d_model, cfg.pdtype),
        "self_attn": init_attention(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.pdtype, k1),
        "ln_x": init_rms_norm(cfg.d_model, cfg.pdtype),
        "cross_attn": init_attention(cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim,
                                     cfg.pdtype, k2),
        "ln2": init_rms_norm(cfg.d_model, cfg.pdtype),
        "mlp": init_swiglu(cfg.d_model, cfg.d_ff, cfg.pdtype, k3),
    }


def init_params(cfg: ArchConfig, key) -> dict:
    ke, kd, kemb, kh = jax.random.split(key, 4)
    ekeys = jax.random.split(ke, cfg.n_enc_layers)
    dkeys = jax.random.split(kd, cfg.n_layers)
    return {
        "embed": init_embed(cfg.vocab_padded, cfg.d_model, cfg.pdtype, kemb),
        "enc_blocks": jax.vmap(lambda k: _init_enc_block(cfg, k))(ekeys),
        "enc_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
        "dec_blocks": jax.vmap(lambda k: _init_dec_block(cfg, k))(dkeys),
        "final_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
        "head": init_head(cfg.vocab_padded, cfg.d_model, cfg.pdtype, kh,
                          tied=cfg.tie_embeddings),
    }


def encode(cfg: ArchConfig, params, frames):
    """frames: [B, T, D] pre-embedded modality features (stub frontend)."""
    def body(x, bp):
        h = rms_norm(bp["ln1"], x)
        # bidirectional self-attention == unmasked cross-attention onto self
        att = cross_attention(bp["attn"], h, h)
        x = x + att
        h = rms_norm(bp["ln2"], x)
        return x + swiglu(bp["mlp"], h), None

    from repro.layers.common import apply_remat
    body = apply_remat(body, cfg.remat)
    x, _ = jax.lax.scan(body, frames.astype(cfg.pdtype),
                        params["enc_blocks"], unroll=cfg.scan_unroll)
    return rms_norm(params["enc_norm"], x)


def _run_decoder(cfg, params, x, positions, memory, cache: EncDecCache | None,
                 cache_pos):
    def body(carry, xs):
        xc = carry
        if cache is None:
            bp = xs
            skv, ckv = None, None
        else:
            bp, skv, ckv = xs
        h = rms_norm(bp["ln1"], xc)
        att, new_skv = attention(bp["self_attn"], h, positions,
                                 theta=cfg.rope_theta, cache=skv,
                                 cache_pos=cache_pos)
        xc = xc + att
        h = rms_norm(bp["ln_x"], xc)
        if ckv is not None:
            xc = xc + cross_attention(bp["cross_attn"], h, None,
                                      kv_cache=ckv)
            new_ckv = ckv
        else:
            xc = xc + cross_attention(bp["cross_attn"], h, memory)
            new_ckv = None
        h = rms_norm(bp["ln2"], xc)
        xc = xc + swiglu(bp["mlp"], h)
        new_c = None if cache is None else (new_skv, new_ckv)
        return xc, new_c

    from repro.layers.common import apply_remat
    body = apply_remat(body, cfg.remat)
    xs = params["dec_blocks"] if cache is None else \
        (params["dec_blocks"], cache.self_kv, cache.cross_kv)
    x, ys = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
    new_cache = None if cache is None else EncDecCache(ys[0], ys[1])
    return x, new_cache


def forward(cfg: ArchConfig, params, tokens, *, frames=None, **_):
    """Training: frames [B,T,D] + decoder tokens [B,S] -> logits [B,S,V]."""
    b, s = tokens.shape
    memory = encode(cfg, params, frames)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed(params["embed"], tokens).astype(cfg.pdtype)
    x, _ = _run_decoder(cfg, params, x, positions, memory, None, None)
    x = rms_norm(params["final_norm"], x)
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, jnp.zeros((), jnp.float32)


def loss_fn(cfg: ArchConfig, params, batch):
    logits, _ = forward(cfg, params, batch["tokens"], frames=batch["frames"])
    loss = cross_entropy(logits, batch["labels"])
    return loss, {"loss": loss}


def init_cache(cfg: ArchConfig, batch: int, cap: int, frames: int,
               dtype=jnp.bfloat16) -> EncDecCache:
    stack = lambda leaf: jnp.broadcast_to(leaf[None],
                                          (cfg.n_layers,) + leaf.shape)
    # the encoder's K/V, written whole at prefill and attended unmasked, so
    # exactly `frames` long (not rounded as the decoder's self-cache is)
    z = jnp.zeros((batch, cfg.n_kv_heads, frames, cfg.head_dim), dtype)
    return EncDecCache(
        self_kv=jax.tree.map(stack, init_kv_cache(
            batch, cfg.n_kv_heads, cap, cfg.head_dim, dtype)),
        cross_kv=jax.tree.map(stack, KVCache(z, z)),
    )


def prefill(cfg: ArchConfig, params, tokens, *, frames=None,
            cache_dtype=jnp.bfloat16, cap: int | None = None, **_):
    """Encode frames once (cross-K/V cached), prefill decoder self-cache."""
    b, s = tokens.shape
    memory = encode(cfg, params, frames)
    cross = jax.vmap(
        lambda bp: project_cross_kv(bp["cross_attn"], memory),
        in_axes=(0,))(params["dec_blocks"])
    cache = EncDecCache(
        self_kv=jax.tree.map(
            lambda leaf: jnp.broadcast_to(
                leaf[None], (cfg.n_layers,) + leaf.shape),
            init_kv_cache(b, cfg.n_kv_heads, cap or s, cfg.head_dim,
                          cache_dtype)),
        cross_kv=jax.tree.map(lambda l: l.astype(cache_dtype), cross),
    )
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed(params["embed"], tokens).astype(cfg.pdtype)
    x, new_cache = _run_decoder(cfg, params, x, positions, memory, cache,
                                None)
    x = rms_norm(params["final_norm"], x[:, -1:])
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, new_cache


def decode_step(cfg: ArchConfig, params, cache: EncDecCache, tokens, pos):
    b, s = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    x = embed(params["embed"], tokens).astype(cfg.pdtype)
    x, new_cache = _run_decoder(cfg, params, x, positions, None, cache, pos)
    x = rms_norm(params["final_norm"], x)
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, new_cache
