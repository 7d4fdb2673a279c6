"""Model registry: family -> (init, loss, prefill, decode) + input_specs.

`input_specs(cfg, shape)` returns ShapeDtypeStruct stand-ins for every input
of the lowered step — weak-type-correct, shardable, no device allocation —
exactly what the multi-pod dry-run lowers against.
"""
from __future__ import annotations

from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from . import encdec, hybrid, lm, ssm

_FAMILY_MOD: dict[str, ModuleType] = {
    "dense": lm, "moe": lm, "vlm": lm,
    "ssm": ssm, "hybrid": hybrid, "encdec": encdec,
}


def model_module(cfg: ArchConfig) -> ModuleType:
    return _FAMILY_MOD[cfg.family]


def init_params(cfg: ArchConfig, key):
    return model_module(cfg).init_params(cfg, key)


def param_shapes(cfg: ArchConfig):
    """ShapeDtypeStruct tree of the params without allocating."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0))


def loss_fn(cfg: ArchConfig, params, batch):
    return model_module(cfg).loss_fn(cfg, params, batch)


def prefill(cfg: ArchConfig, params, batch, cache_dtype=jnp.bfloat16,
            cap: int | None = None):
    mod = model_module(cfg)
    kwargs = {}
    if cap is not None and cfg.family != "ssm":
        kwargs["cap"] = cap
    if cfg.family == "vlm":
        kwargs["vision_embeds"] = batch.get("vision_embeds")
    if cfg.family == "encdec":
        kwargs["frames"] = batch.get("frames")
    return mod.prefill(cfg, params, batch["tokens"], cache_dtype=cache_dtype,
                       **kwargs)


def decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    return model_module(cfg).decode_step(cfg, params, cache, tokens, pos)


def decode_step_routed(cfg: ArchConfig, params, cache, tokens, pos):
    """decode_step of a model with held experts, with its routing counts."""
    return model_module(cfg).decode_step_routed(cfg, params, cache, tokens,
                                                pos)


def cache_shapes(cfg: ArchConfig, batch: int, cap: int,
                 dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree of the serving cache."""
    mod = model_module(cfg)
    if cfg.family == "encdec":
        frames = cap // cfg.frames_ratio
        return jax.eval_shape(
            lambda: mod.init_cache(cfg, batch, cap, frames, dtype))
    if cfg.family == "ssm":
        return jax.eval_shape(lambda: mod.init_cache(cfg, batch, dtype=dtype))
    return jax.eval_shape(lambda: mod.init_cache(cfg, batch, cap, dtype))


# ---------------------------------------------------------------------------
# input specs per (arch x shape)
# ---------------------------------------------------------------------------

def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def supports_shape(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """(supported, reason-if-not).  long_500k needs sub-quadratic attention
    (DESIGN.md §4 — run for ssm/hybrid/local-global; skip pure full-attn)."""
    if shape.name == "long_500k" and not cfg.long_context_ok:
        return False, ("pure full-attention arch: 500k dense-KV decode is the "
                       "quadratic regime this shape excludes (DESIGN.md §4)")
    return True, ""


def input_specs(cfg: ArchConfig, shape: ShapeConfig | str) -> dict:
    """ShapeDtypeStructs for the step function the shape lowers."""
    if isinstance(shape, str):
        shape = SHAPES[shape]
    b, s = shape.global_batch, shape.seq_len
    tok = jnp.int32
    if shape.kind == "train":
        batch = {"tokens": sds((b, s), tok), "labels": sds((b, s), tok)}
        if cfg.family == "vlm":
            batch["vision_embeds"] = sds((b, cfg.n_patches, cfg.d_model),
                                         jnp.bfloat16)
            batch["positions"] = sds((3, b, s), tok)
        if cfg.family == "encdec":
            batch["frames"] = sds((b, s // cfg.frames_ratio, cfg.d_model),
                                  jnp.bfloat16)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((b, s), tok)}
        if cfg.family == "vlm":
            batch["vision_embeds"] = sds((b, cfg.n_patches, cfg.d_model),
                                         jnp.bfloat16)
        if cfg.family == "encdec":
            batch["frames"] = sds((b, s // cfg.frames_ratio, cfg.d_model),
                                  jnp.bfloat16)
        return batch
    # decode: one new token against a cache of seq_len
    cache = cache_shapes(cfg, b, s)
    return {
        "cache": cache,
        "tokens": sds((b, 1), tok),
        "pos": sds((), jnp.int32),
    }
