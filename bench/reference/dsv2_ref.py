"""Plain reference of DeepSeek-V2-Lite on one chip of an expert-parallel
deployment: weights in the published layout made from a seed, and the
teacher-forced forward pass in float32 at `Precision.HIGHEST`, layer by
layer, with no kernel, cache, absorption or batching trick.

Published description followed (deepseek-ai/DeepSeek-V2-Lite, model type
deepseek_v2; arXiv:2405.04434 §2.1–2.2):

* RMSNorm ``x * rsqrt(mean(x^2) + eps) * w`` in float32; pre-norm residual
  blocks; final norm; untied output head.
* Latent attention without query compression: ``q = h Wq`` split per head
  into 128 no-rope and 64 rope dimensions; ``[c; k_pe] = h Wkva`` with
  ``c`` (512) normed by its own RMSNorm and ``[k_nope; v] = c Wkvb`` per
  head (128 + 128); the rope key shared by every head; causal softmax with
  scale ``192^-1/2 * mscale^2``.
* Rotary embedding as the published code applies it: the rope dimensions
  de-interleaved (2i, 2i + 1 -> i, i + 32), then rotate-half with YaRN
  frequencies (factor 40 over 4096 original positions, ramp between the
  correction dimensions of beta_fast 32 and beta_slow 1) and cos/sin
  scaled by mscale(mscale) / mscale(mscale_all_dim).
* Layer 0 a SwiGLU of width 10 944; layers 1–26 route each token by a
  float32 softmax over all 64 experts, keep the greedy top 6 with gates
  left unnormalised times ``routed_scaling_factor``, and add the shared
  experts, one SwiGLU of width 1408 * 2.

Departures, each the chip's share of the deployment: only the experts
``experts_held`` hold weights, and a layer's routed output is the part
that those experts give (gates of other experts drop out), as the
program computes it before the exchange that this chip does not make.
The held experts run densely over every token, weighted by their gate
or 0.  Weights are (in, out) matrices, the transpose of the published
``nn.Linear`` layout, except the router, kept as published [experts, in].

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8_e4m3fn under a per-tensor scale, the precision
below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen_ref import _mm, _norm

NORMS = ("ln1", "ln2", "kv_norm", "final_norm")


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    e0, e1 = cfg["experts_held"]
    return dict(d=cfg["hidden_size"], h=h, nope=nope, rope=rope,
                r=cfg["kv_lora_rank"], vd=cfg["v_head_dim"],
                f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                e=cfg["n_routed_experts_published"], held=e1 - e0)


def layer_shapes(cfg: dict, dense: bool) -> dict:
    n = _dims(cfg)
    d, h, r = n["d"], n["h"], n["r"]
    out = {
        "ln1": (d,), "ln2": (d,),
        "wq": (d, h * (n["nope"] + n["rope"])),
        "wkv_a": (d, r + n["rope"]), "kv_norm": (r,),
        "wkv_b": (r, h * (n["nope"] + n["vd"])),
        "wo": (h * n["vd"], d),
    }
    if dense:
        out.update(w_gate=(d, n["f"]), w_up=(d, n["f"]), w_down=(n["f"], d))
    else:
        fe, fs, k = n["fe"], n["fs"], n["held"]
        out.update(router=(n["e"], d),
                   experts_gate=(k, d, fe), experts_up=(k, d, fe),
                   experts_down=(k, fe, d),
                   shared_gate=(d, fs), shared_up=(d, fs),
                   shared_down=(fs, d))
    return out


def _draw(key, shapes: dict, dtype) -> dict:
    out = {}
    names = sorted(shapes)
    for name, k in zip(names, jax.random.split(key, len(names))):
        shape = shapes[name]
        if name in NORMS:
            x = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:   # fan-in of the projection; the router's is its last axis
            fan_in = shape[-1] if name in ("router", "head") else shape[-2]
            x = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        out[name] = x.astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "dense"))
def _make_layer(key, cfg_items, dense):
    cfg = dict(cfg_items)
    return _draw(key, layer_shapes(cfg, dense), jnp.dtype(cfg["torch_dtype"]))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _make_top(key, cfg_items):
    cfg = dict(cfg_items)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return _draw(key, {"embed": (v, d), "final_norm": (d,), "head": (v, d)},
                 jnp.dtype(cfg["torch_dtype"]))


def _cfg_items(cfg: dict):
    keys = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts",
            "n_routed_experts_published", "vocab_size", "torch_dtype")
    return tuple((k, cfg[k]) for k in keys) + \
        (("experts_held", tuple(cfg["experts_held"])),)


def make_top(cfg: dict, seed_word: int) -> dict:
    """Embedding, final norm and head, on the device, from a seed word."""
    return _make_top(jax.random.fold_in(jax.random.key(seed_word), 0),
                     _cfg_items(cfg))


def make_layer(cfg: dict, seed_word: int, i: int) -> dict:
    """Layer i's weights in the published layout and dtype, on the device:
    one layer at a time, so that no float32 copy of the model is held."""
    key = jax.random.fold_in(jax.random.key(seed_word), i + 1)
    return _make_layer(key, _cfg_items(cfg),
                       i < cfg["first_k_dense_replace"])


def make_weights(cfg: dict, seed_word: int) -> dict:
    return dict(make_top(cfg, seed_word), layers=[
        make_layer(cfg, seed_word, i)
        for i in range(cfg["num_hidden_layers"])])


# -- forward ------------------------------------------------------------------

def _yarn(cfg: dict):
    """(inv_freq [rope / 2], cos/sin scale, softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    factor = float(y["factor"])

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 \
        * mscale(y["mscale_all_dim"]) ** 2
    return (inv.astype(np.float32),
            mscale(y["mscale"]) / mscale(y["mscale_all_dim"]), scale)


def _rope(x, inv, cos_scale):
    """x [B, S, H, rope] in the published interleaved layout."""
    b, s, h, dim = x.shape
    x = x.reshape(b, s, h, dim // 2, 2).swapaxes(-1, -2).reshape(b, s, h, dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * cos_scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * cos_scale
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _swiglu(mm, h, g, u, dn):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", h, g))
              * mm("bsd,df->bsf", h, u), dn)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dense",
                                             "precision"))
def _layer(x, lw, *, cfg_items, dense, precision):
    """One decoder layer; returns (x, held slots routed in it)."""
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    n = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    h_, nope, rope, r, vd = n["h"], n["nope"], n["rope"], n["r"], n["vd"]
    mm = functools.partial(_mm, precision)
    inv, cos_scale, scale = _yarn(cfg)

    h = _norm(x, lw["ln1"], eps)
    q = mm("bsd,de->bse", h, lw["wq"]).reshape(b, s, h_, nope + rope)
    kv_a = mm("bsd,de->bse", h, lw["wkv_a"])
    c = _norm(kv_a[..., :r], lw["kv_norm"], eps)
    kv = mm("bsr,re->bse", c, lw["wkv_b"]).reshape(b, s, h_, nope + vd)
    q_pe = _rope(q[..., nope:], inv, cos_scale)
    k_pe = _rope(kv_a[..., None, r:], inv, cos_scale)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h_, rope))], -1)
    scores = mm("bshe,bthe->bhst", qf, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    att = mm("bhst,bthe->bshe", probs, kv[..., nope:]).reshape(b, s, h_ * vd)
    x = x + mm("bse,ed->bsd", att, lw["wo"])

    h = _norm(x, lw["ln2"], eps)
    if dense:
        return x + _swiglu(mm, h, lw["w_gate"], lw["w_up"], lw["w_down"]), 0
    probs = jax.nn.softmax(mm("bsd,ed->bse", h, lw["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    e0 = cfg["experts_held"][0]
    # gate of each held expert for each token: its top-k weight, or 0
    held = idx[..., None] == e0 + jnp.arange(n["held"])     # [B, S, K, E]
    gates = jnp.sum(jnp.where(held, top[..., None], 0.0), axis=-2)
    g = jax.nn.silu(mm("bsd,edf->bsef", h, lw["experts_gate"]))
    u = mm("bsd,edf->bsef", h, lw["experts_up"])
    routed = mm("bsef,efd->bsd", g * u * gates[..., None],
                lw["experts_down"])
    shared = _swiglu(mm, h, lw["shared_gate"], lw["shared_up"],
                     lw["shared_down"])
    return x + routed + shared, jnp.sum(held)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, head, *, eps, precision):
    return _mm(precision, "bsd,vd->bsv", _norm(x, final_norm, eps), head)


def _forward(cfg: dict, w: dict, tokens, precision: str):
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    slots = 0
    for i, lw in enumerate(w["layers"]):
        x, n = _layer(x, lw, cfg_items=_layer_items(cfg), precision=precision,
                      dense=i < cfg["first_k_dense_replace"])
        slots = slots + n
    return x, slots


def _layer_items(cfg: dict):
    keep = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts",
            "n_routed_experts_published", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keep) + (
        ("experts_held", tuple(cfg["experts_held"])),
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))))


def logits(cfg: dict, w: dict, tokens, first: int, *,
           precision: str = "f32"):
    """Teacher-forced logits f32[B, S - first, V] at positions first..S-1
    of tokens i32[B, S] (position t predicts token t + 1)."""
    x, _ = _forward(cfg, w, tokens, precision)
    return _head(x[:, first:], w["final_norm"], w["head"],
                 eps=cfg["rms_norm_eps"], precision=precision)


def held_slots(cfg: dict, w: dict, tokens) -> int:
    """(token, expert) slots routed to the held experts over every routed
    layer of a forward pass over tokens i32[B, S]."""
    return int(_forward(cfg, w, tokens, "f32")[1])
