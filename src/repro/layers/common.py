"""Shared building blocks: norms, RoPE (incl. M-RoPE), MLPs, embeddings.

All layers are pure functions over param pytrees (dicts of jax Arrays); param
factories return *initializer thunks* so `jax.eval_shape` can build
ShapeDtypeStruct trees without allocation (dry-run path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(scale, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dt)


def init_rms_norm(d: int, dtype):
    """Norm scales are raw arrays (zero-init, applied as 1 + scale)."""
    return jnp.zeros((d,), dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(rotary_dim: int, theta):
    """theta may be a python float or a traced scalar (per-layer theta in
    gemma3's local/global scan)."""
    expo = jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    return jnp.asarray(theta, jnp.float32) ** (-expo)


def apply_rope(x, positions, *, theta=10000.0, rotary_dim: int | None = None,
               inv_freq=None):
    """x: [B, S, H, Dh]; positions: [B, S] (int). Partial rotary supported.
    `inv_freq` [rd / 2], where given, replaces theta's frequencies (YaRN)."""
    dh = x.shape[-1]
    rd = rotary_dim or dh
    inv = rope_frequencies(rd, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * inv  # [B,S,rd/2]
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype) if rd < dh \
        else out.astype(x.dtype)


def apply_mrope(x, positions3, *, theta=10000.0,
                sections: tuple[int, int, int] = (16, 24, 24)):
    """Multimodal RoPE (Qwen2-VL).  positions3: [3, B, S] (t, h, w ids);
    `sections` gives rotary half-dims per section, sum = Dh/2."""
    dh = x.shape[-1]
    assert sum(sections) == dh // 2, (sections, dh)
    inv = rope_frequencies(dh, theta)  # [dh/2]
    ang = positions3[..., None].astype(jnp.float32) * inv  # [3,B,S,dh/2]
    sec_id = jnp.asarray(
        np.repeat(np.arange(3), np.asarray(sections)), jnp.int32)  # [dh/2]
    # select ang[sec_id[d], b, l, d] for each rotary dim d
    ang = jnp.einsum("sbld,ds->bld", ang,
                     jax.nn.one_hot(sec_id, 3, axis=-1, dtype=ang.dtype))
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def init_swiglu(d: int, f: int, dtype, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = float(1.0 / np.sqrt(d))
    s_out = float(1.0 / np.sqrt(f))
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) * s_in,
        "w_up": jax.random.normal(k2, (d, f), dtype) * s_in,
        "w_down": jax.random.normal(k3, (f, d), dtype) * s_out,
    }


def relu_mlp(p, x):
    return jax.nn.relu(x @ p["w_in"] + p["b_in"]) @ p["w_out"] + p["b_out"]


def init_relu_mlp(d: int, f: int, dtype, key) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": jax.random.normal(k1, (d, f), dtype) * float(1.0 / np.sqrt(d)),
        "b_in": jnp.zeros((f,), dtype),
        "w_out": jax.random.normal(k2, (f, d), dtype) * float(1.0 / np.sqrt(f)),
        "b_out": jnp.zeros((d,), dtype),
    }


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def init_embed(vocab: int, d: int, dtype, key) -> dict:
    return {"tok": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def unembed(p_embed, p_head, x, *, tied: bool):
    w = p_embed["tok"].T if tied else p_head["w"]
    return x @ w.astype(x.dtype)


def init_head(vocab: int, d: int, dtype, key, *, tied: bool) -> dict:
    if tied:
        return {}
    return {"w": jax.random.normal(key, (d, vocab), dtype) * float(1.0 / np.sqrt(d))}


def cross_entropy(logits, labels, *, ignore_id: int = -1):
    """Mean token cross-entropy in fp32; labels==ignore_id are masked."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def apply_remat(body, policy: str):
    """Activation-checkpoint policy for the layer-scan body (§Perf H7).

    none — save everything (no recompute; activation-memory bound)
    full — save only layer boundaries (recompute everything; paper-faithful
           MaxText-style default)
    dots — jax.checkpoint with dots_with_no_batch_dims_saveable: matmul
           outputs are saved, elementwise work is recomputed — removes the
           forward matmul recompute from the backward at the cost of storing
           projection outputs (beyond-paper hillclimb option).
    """
    import jax as _jax
    if policy == "full":
        return _jax.checkpoint(body)
    if policy == "dots":
        return _jax.checkpoint(
            body,
            policy=_jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return body
