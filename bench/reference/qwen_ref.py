"""Plain reference of a Qwen1.5 (Qwen2 architecture) decoder: weights in
the published layout made from a seed, and the teacher-forced forward pass
in float32, layer by layer, with no kernel, cache or batching trick.

Published description followed (Qwen/Qwen1.5-0.5B, model type qwen2):
RMSNorm ``x * rsqrt(mean(x^2) + eps) * w`` computed in float32; attention
with bias on q, k and v and none on o, rotary embedding on q and k by the
rotate-half convention (dimension i turns with i + head_dim / 2, frequency
``theta ** (-2i / head_dim)``), causal softmax with scale
``1 / sqrt(head_dim)``; SwiGLU MLP ``down(silu(gate(x)) * up(x))``;
pre-norm residual blocks; final norm; output head tied to the embedding.

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8_e4m3fn under a per-tensor scale, the precision
below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    L, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {
        "embed": (v, d),
        "ln1": (L, d), "ln2": (L, d), "final_norm": (d,),
        "wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
        "bq": (L, h * hd), "bk": (L, kv * hd), "bv": (L, kv * hd),
        "wo": (L, h * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name in ("ln1", "ln2", "final_norm"):
        return 0.05                      # around 1, see make_weights
    if name.startswith("b"):
        return 0.05
    return float(1.0 / np.sqrt(shape[-2]))   # fan-in of the projection


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _make(key, cfg_items):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    names = sorted(shapes(cfg))
    for name, k in zip(names, jax.random.split(key, len(names))):
        shape = shapes(cfg)[name]
        x = jax.random.normal(k, shape, jnp.float32) * _std(name, shape)
        if name in ("ln1", "ln2", "final_norm"):
            x = 1.0 + x
        out[name] = x.astype(dtype)
    return out


def _cfg_items(cfg: dict):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "torch_dtype")
    return tuple((k, cfg[k]) for k in keys)


def make_weights(cfg: dict, seed_word: int) -> dict:
    """Weights in the published layout and dtype, on the device, in one
    jitted call from a 32-bit seed word."""
    return _make(jax.random.key(seed_word), _cfg_items(cfg))


# -- forward -------------------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q, scale


def _mm(precision: str, eq: str, a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        (a, sa), (b, sb) = _fp8(a), _fp8(b)
        return jnp.einsum(eq, a, b,
                          precision=jax.lax.Precision.HIGHEST) * (sa * sb)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, S, H, hd]; rotate-half convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]   # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "theta", "precision"))
def _layer(x, lw, *, n_heads, n_kv, eps, theta, precision):
    b, s, d = x.shape
    hd = d // n_heads
    mm = functools.partial(_mm, precision)
    h = _norm(x, lw["ln1"], eps)
    q = (mm("bsd,de->bse", h, lw["wq"]) + lw["bq"].astype(jnp.float32)) \
        .reshape(b, s, n_heads, hd)
    k = (mm("bsd,de->bse", h, lw["wk"]) + lw["bk"].astype(jnp.float32)) \
        .reshape(b, s, n_kv, hd)
    v = (mm("bsd,de->bse", h, lw["wv"]) + lw["bv"].astype(jnp.float32)) \
        .reshape(b, s, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = mm("bshe,bthe->bhst", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm("bhst,bthe->bshe", probs, v).reshape(b, s, n_heads * hd)
    x = x + mm("bse,ed->bsd", att, lw["wo"])
    h = _norm(x, lw["ln2"], eps)
    g = mm("bsd,df->bsf", h, lw["w_gate"])
    u = mm("bsd,df->bsf", h, lw["w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, embed, *, eps, precision):
    return _mm(precision, "bsd,vd->bsv", _norm(x, final_norm, eps), embed)


def logits(cfg: dict, w: dict, tokens, first: int, *,
           precision: str = "f32"):
    """Teacher-forced logits f32[B, S - first, V] at positions first..S-1
    of tokens i32[B, S] (position t predicts token t + 1)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layer_keys = ("ln1", "ln2", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
                  "w_gate", "w_up", "w_down")
    kw = dict(n_heads=cfg["num_attention_heads"],
              n_kv=cfg["num_key_value_heads"], eps=cfg["rms_norm_eps"],
              theta=float(cfg["rope_theta"]), precision=precision)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, {k: w[k][i] for k in layer_keys}, **kw)
    return _head(x[:, first:], w["final_norm"], w["embed"],
                 eps=cfg["rms_norm_eps"], precision=precision)
