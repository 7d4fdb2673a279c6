"""Decoder-LM substrate for the dense / moe / vlm families.

One scan-over-layers implementation covers qwen1.5, glm4, qwen3, gemma3
(per-layer window/theta as scan inputs), olmoe (MoE every layer), llama4
(scan over dense+MoE *pairs* with a shared expert), qwen2-vl (M-RoPE +
pre-embedded vision patches) and deepseek-v2-lite (latent attention, a
leading dense layer ahead of the scan, held experts dropless).  Stacked
per-layer params keep the HLO size O(1) in depth — essential for 64-layer
archs on the 512-device dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.layers import attention as attn_lib
from repro.layers.attention import KVCache, attention, init_attention, init_kv_cache
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
from repro.layers.mla import init_mla, init_mla_cache, mla_attention
from repro.layers.moe import init_moe, moe_ffn, moe_ffn_held
from repro.layers.moe_ep import moe_ffn_ep


# ---------------------------------------------------------------------------
# per-layer schedule (windows / rope thetas)
# ---------------------------------------------------------------------------

def layer_schedule(cfg: ArchConfig, n_units: int):
    """(windows i32[U], thetas f32[U]) per scan unit."""
    if cfg.local_global_ratio:
        r = cfg.local_global_ratio
        is_global = (np.arange(n_units) % (r + 1)) == r
        windows = np.where(is_global, -1, cfg.sliding_window or -1)
        thetas = np.where(is_global, cfg.rope_theta_global or cfg.rope_theta,
                          cfg.rope_theta)
    else:
        windows = np.full(n_units, cfg.sliding_window or -1)
        thetas = np.full(n_units, cfg.rope_theta)
    return jnp.asarray(windows, jnp.int32), jnp.asarray(thetas, jnp.float32)


def _rotary_dim(cfg: ArchConfig) -> int:
    rd = int(cfg.head_dim * cfg.partial_rotary)
    return rd - rd % 2


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _init_attention(cfg: ArchConfig, key) -> dict:
    if cfg.mla:
        return init_mla(cfg, key)
    return init_attention(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.pdtype, key,
                          qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)


def init_dense_block(cfg: ArchConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rms_norm(cfg.d_model, cfg.pdtype),
        "attn": _init_attention(cfg, k1),
        "ln2": init_rms_norm(cfg.d_model, cfg.pdtype),
        "mlp": init_swiglu(cfg.d_model, cfg.d_ff, cfg.pdtype, k2),
    }


def init_moe_block(cfg: ArchConfig, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": init_rms_norm(cfg.d_model, cfg.pdtype),
        "attn": _init_attention(cfg, k1),
        "ln2": init_rms_norm(cfg.d_model, cfg.pdtype),
        "moe": init_moe(cfg.d_model, cfg.expert_d_ff or cfg.d_ff,
                        cfg.n_experts, cfg.pdtype, k2,
                        n_held=cfg.n_held if cfg.experts_held else None),
    }
    if cfg.shared_expert:
        p["shared_mlp"] = init_swiglu(
            cfg.d_model, cfg.shared_expert_d_ff or cfg.d_ff, cfg.pdtype, k3)
    return p


def _attention_block(cfg: ArchConfig, bp, x, positions, window, theta,
                     cache: KVCache | None, cache_pos):
    """Pre-norm self-attention with its residual, under the `attention`
    scope that the device trace names its operations by."""
    with jax.named_scope("attention"):
        h = rms_norm(bp["ln1"], x)
        if cfg.mla:
            att, new_cache = mla_attention(cfg, bp["attn"], h, positions,
                                           cache=cache, cache_pos=cache_pos)
        else:
            att, new_cache = attention(
                bp["attn"], h, positions, theta=theta,
                rotary_dim=_rotary_dim(cfg), window=window,
                mrope_sections=cfg.mrope_sections, cache=cache,
                cache_pos=cache_pos)
        return x + att, new_cache


def apply_dense_block(cfg: ArchConfig, bp, x, positions, window, theta,
                      cache: KVCache | None, cache_pos):
    x, new_cache = _attention_block(cfg, bp, x, positions, window, theta,
                                    cache, cache_pos)
    with jax.named_scope("mlp"):
        h = rms_norm(bp["ln2"], x)
        x = x + swiglu(bp["mlp"], h)
    return x, new_cache, jnp.zeros((), jnp.float32)


def apply_moe_block(cfg: ArchConfig, bp, x, positions, window, theta,
                    cache: KVCache | None, cache_pos):
    x, new_cache = _attention_block(cfg, bp, x, positions, window, theta,
                                    cache, cache_pos)
    with jax.named_scope("mlp"):
        h = rms_norm(bp["ln2"], x)
        if cfg.experts_held:
            y, lb, counts = moe_ffn_held(
                bp["moe"], h, top_k=cfg.top_k, held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor)
            aux = (lb, counts)
        elif cfg.moe_impl == "ep":
            y, aux = moe_ffn_ep(bp["moe"], h, top_k=cfg.top_k,
                                capacity_factor=cfg.capacity_factor,
                                expert_axis=cfg.expert_axis,
                                norm_topk_prob=cfg.norm_topk_prob)
        else:
            y, aux = moe_ffn(bp["moe"], h, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             norm_topk_prob=cfg.norm_topk_prob)
        if "shared_mlp" in bp:
            with jax.named_scope("shared_experts"):
                y = y + swiglu(bp["shared_mlp"], h)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# scan units: one layer (dense / moe-every-layer) or a dense+moe pair (llama4)
# ---------------------------------------------------------------------------

def n_units(cfg: ArchConfig) -> int:
    """Units of the scan; `first_k_dense` leading layers run ahead of it."""
    layers = cfg.n_layers - cfg.first_k_dense
    return layers // 2 if (cfg.family == "moe" and cfg.moe_every == 2) \
        else layers


def layers_per_unit(cfg: ArchConfig) -> int:
    return 2 if (cfg.family == "moe" and cfg.moe_every == 2) else 1


def init_unit(cfg: ArchConfig, key) -> dict:
    if cfg.family == "moe" and cfg.moe_every == 2:
        k1, k2 = jax.random.split(key)
        return {"dense": init_dense_block(cfg, k1),
                "moe": init_moe_block(cfg, k2)}
    if cfg.family == "moe":
        return init_moe_block(cfg, key)
    return init_dense_block(cfg, key)


def init_unit_cache(cfg: ArchConfig, batch: int, cap: int, dtype) -> Any:
    if cfg.mla:
        return init_mla_cache(cfg, batch, cap, dtype)
    mk = lambda: init_kv_cache(batch, cfg.n_kv_heads, cap, cfg.head_dim, dtype)
    if cfg.family == "moe" and cfg.moe_every == 2:
        return {"dense": mk(), "moe": mk()}
    return mk()


def apply_unit(cfg: ArchConfig, up, x, positions, window, theta, cache,
               cache_pos):
    if cfg.family == "moe" and cfg.moe_every == 2:
        c_d = cache["dense"] if cache is not None else None
        c_m = cache["moe"] if cache is not None else None
        x, nc_d, _ = apply_dense_block(cfg, up["dense"], x, positions, window,
                                       theta, c_d, cache_pos)
        x, nc_m, aux = apply_moe_block(cfg, up["moe"], x, positions, window,
                                       theta, c_m, cache_pos)
        new_cache = None if nc_d is None else {"dense": nc_d, "moe": nc_m}
        return x, new_cache, aux
    if cfg.family == "moe":
        return apply_moe_block(cfg, up, x, positions, window, theta, cache,
                               cache_pos)
    return apply_dense_block(cfg, up, x, positions, window, theta, cache,
                             cache_pos)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key) -> dict:
    ku, ke, kh = jax.random.split(key, 3)
    unit_keys = jax.random.split(ku, n_units(cfg))
    units = jax.vmap(lambda k: init_unit(cfg, k))(unit_keys)
    params = {
        "embed": init_embed(cfg.vocab_padded, cfg.d_model, cfg.pdtype, ke),
        "units": units,
        "final_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
        "head": init_head(cfg.vocab_padded, cfg.d_model, cfg.pdtype, kh,
                          tied=cfg.tie_embeddings),
    }
    if cfg.first_k_dense:
        lead_keys = jax.random.split(jax.random.fold_in(ku, 1),
                                     cfg.first_k_dense)
        params["lead"] = jax.vmap(lambda k: init_dense_block(cfg, k))(
            lead_keys)
    return params


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, params, tokens, vision_embeds):
    x = embed(params["embed"], tokens).astype(cfg.pdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.family == "vlm" and vision_embeds is not None:
        # patches pre-embedded by the (stubbed) vision frontend; spliced in
        # after the BOS position.
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 1, 0))
    return x


def _scan_layers(cfg: ArchConfig, apply, stacked, x, positions, cache,
                 cache_pos, aux0):
    """Scan `apply` over stacked per-layer params (and cache, or None)."""
    windows, thetas = layer_schedule(cfg, jax.tree.leaves(stacked)[0].shape[0])

    def body(carry, xs):
        xc, aux = carry
        if cache is None:
            up, w, th = xs
            c = None
        else:
            up, w, th, c = xs
        xc, new_c, a = apply(cfg, up, xc, positions, w, th, c, cache_pos)
        return (xc, jax.tree.map(jnp.add, aux, a)), new_c

    from repro.layers.common import apply_remat
    body = apply_remat(body, cfg.remat)
    xs = (stacked, windows, thetas) if cache is None else \
        (stacked, windows, thetas, cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, aux0), xs,
                                       unroll=cfg.scan_unroll)
    return x, aux, new_cache


def _run_units(cfg: ArchConfig, params, x, positions, cache, cache_pos):
    """The leading dense layers, if any, then the scan over the stacked
    units.  cache: stacked [U, ...] pytree ({"lead", "units"} with leading
    dense layers) or None.  aux: the balance loss, with held experts
    (loss, routing counts int32[2])."""
    zero = jnp.zeros((), jnp.float32)
    aux0 = (zero, jnp.zeros((2,), jnp.int32)) if cfg.experts_held else zero
    if not cfg.first_k_dense:
        return _scan_layers(cfg, apply_unit, params["units"], x, positions,
                            cache, cache_pos, aux0)
    x, _, lead = _scan_layers(cfg, apply_dense_block, params["lead"], x,
                              positions, None if cache is None
                              else cache["lead"], cache_pos, zero)
    x, aux, units = _scan_layers(cfg, apply_unit, params["units"], x,
                                 positions, None if cache is None
                                 else cache["units"], cache_pos, aux0)
    return x, aux, None if cache is None else {"lead": lead, "units": units}


def forward(cfg: ArchConfig, params, tokens, *, vision_embeds=None,
            positions=None):
    """Training/eval forward: tokens [B,S] -> logits [B,S,V] (bf16), aux."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3, b, s))
    x = _embed_inputs(cfg, params, tokens, vision_embeds)
    x, aux, _ = _run_units(cfg, params, x, positions, None, None)
    if cfg.experts_held:
        aux = aux[0]
    x = rms_norm(params["final_norm"], x)
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, aux


def loss_fn(cfg: ArchConfig, params, batch):
    logits, aux = forward(cfg, params, batch["tokens"],
                          vision_embeds=batch.get("vision_embeds"),
                          positions=batch.get("positions"))
    loss = cross_entropy(logits, batch["labels"])
    return loss + 0.01 * aux, {"loss": loss, "aux": aux}


# -- serving ----------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, cap: int, dtype=jnp.bfloat16):
    """Stacked [U, ...] KV cache; {"lead", "units"} with leading dense
    layers."""
    unit = init_unit_cache(cfg, batch, cap, dtype)

    def stack(n):
        return jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (n,) + leaf.shape),
            unit)
    if cfg.first_k_dense:
        return {"lead": stack(cfg.first_k_dense), "units": stack(n_units(cfg))}
    return stack(n_units(cfg))


def prefill(cfg: ArchConfig, params, tokens, *, vision_embeds=None,
            positions=None, cache_dtype=jnp.bfloat16, cap: int | None = None):
    """Build the KV cache for the whole prompt; return last-token logits.
    `cap` is the cache capacity (>= prompt + generated tokens; defaults to
    the prompt length, matching the decode-shape dry-run contract)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3, b, s))
    x = _embed_inputs(cfg, params, tokens, vision_embeds)
    cache = init_cache(cfg, b, cap or s, cache_dtype)
    x, _, new_cache = _run_units(cfg, params, x, positions, cache, None)
    x = rms_norm(params["final_norm"], x[:, -1:])
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, new_cache


def decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    """One serving step: tokens [B,1] at absolute position `pos` (scalar),
    attending over cache[<= pos].  Returns (logits [B,1,V], new_cache)."""
    logits, new_cache, _ = _decode(cfg, params, cache, tokens, pos)
    return logits, new_cache


def decode_step_routed(cfg: ArchConfig, params, cache, tokens, pos):
    """`decode_step` of a model with held experts, also returning the
    step's routing counts int32[2] summed over layers: the held (token,
    expert) slots computed and the held experts with at least one slot."""
    logits, new_cache, aux = _decode(cfg, params, cache, tokens, pos)
    return logits, new_cache, aux[1]


def _decode(cfg: ArchConfig, params, cache, tokens, pos):
    b, s = tokens.shape
    assert s == 1
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(positions[None], (3, b, 1))
    x = _embed_inputs(cfg, params, tokens, None)
    x, aux, new_cache = _run_units(cfg, params, x, positions, cache, pos)
    x = rms_norm(params["final_norm"], x)
    logits = unembed(params["embed"], params["head"], x,
                     tied=cfg.tie_embeddings)
    return logits, new_cache, aux
