"""Per-arch smoke tests (deliverable f): reduced same-family configs run a
forward/train step on CPU asserting shapes + no NaNs, and the serving path
(prefill + decode) is consistent with the training-time forward."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.launch.steps import build_train_step
from repro.models import registry
from repro.optim import init_state

ARCH_IDS = list(ARCHS)


def _batch(cfg, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab - 1, (b, s)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_patches, cfg.d_model)) * 0.02,
            jnp.float32)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, None], (3, b, s))
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, s // cfg.frames_ratio, cfg.d_model)) * 0.02,
            jnp.float32)
    return batch


@pytest.fixture(scope="module")
def smoke_state():
    """Cache (params, cfg) per arch for the whole module."""
    cache = {}

    def get(arch_id):
        if arch_id not in cache:
            cfg = smoke_config(ARCHS[arch_id])
            params = registry.init_params(cfg, jax.random.key(0))
            cache[arch_id] = (cfg, params)
        return cache[arch_id]

    return get


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_forward_shapes_and_finite(smoke_state, arch_id):
    cfg, params = smoke_state(arch_id)
    batch = _batch(cfg)
    loss, metrics = registry.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss)), arch_id
    assert float(loss) > 0
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_one_train_step_reduces_loss_direction(smoke_state, arch_id):
    """A train step must produce finite grads and update params in place."""
    cfg, params = smoke_state(arch_id)
    step = build_train_step(cfg, peak_lr=1e-3, warmup=1, total_steps=10)
    opt = init_state(params)
    batch = _batch(cfg)
    # step 0 is pure warmup (lr=0); step 1 must move the params
    mid_params, opt, _ = step(params, opt, batch)
    new_params, new_opt, metrics = step(mid_params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    assert int(new_opt.step) == 2
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert moved, arch_id


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_prefill_decode_matches_forward(smoke_state, arch_id):
    """Greedy serving consistency: logits from (prefill prompt; decode token
    t) must match the training forward at position t.  This pins the KV
    cache layout, position handling and mask semantics across all 10 archs."""
    cfg, params = smoke_state(arch_id)
    if cfg.family == "moe":
        # GShard capacity drops differ between batch-forward and 1-token
        # decode; lift the capacity so routing is drop-free and the
        # comparison tests true cache consistency.
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    b, s = 2, 16
    batch = _batch(cfg, b=b, s=s, seed=1)
    tokens = batch["tokens"]

    # full forward logits
    mod = registry.model_module(cfg)
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["vision_embeds"] = batch["vision_embeds"]
    if cfg.family == "encdec":
        kwargs["frames"] = batch["frames"]
    full_logits, _ = mod.forward(cfg, params, tokens, **kwargs)

    # prefill the first s-1 tokens (cap leaves room for the decoded token),
    # then decode token s-1
    prompt = tokens[:, : s - 1]
    pre_kwargs = dict(kwargs)
    if cfg.family != "ssm":
        pre_kwargs["cap"] = s
    logits_p, cache = mod.prefill(cfg, params, prompt,
                                  cache_dtype=jnp.float32, **pre_kwargs)
    # prefill's last-position logits == forward at position s-2
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32),
        np.asarray(full_logits[:, s - 2], np.float32), rtol=2e-2, atol=2e-2)

    logits_d, _ = mod.decode_step(cfg, params, cache,
                                  tokens[:, s - 1: s],
                                  jnp.asarray(s - 1, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_d[:, 0], np.float32),
        np.asarray(full_logits[:, s - 1], np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b",                 # MHA
    "glm4-9b",                      # GQA, 2 kv heads for 4
    "gemma3-1b",                    # sliding window 16, passed by decode
    "llama4-maverick-400b-a17b",    # dense + MoE pair units
    "deepseek-v2-lite",             # latent cache, a lead layer ahead
    "zamba2-1.2b",                  # shared attention over mamba groups
    "seamless-m4t-medium",          # decoder self-cache beside cross K/V
])
def test_decode_steps_through_the_carried_cache_match_forward(smoke_state,
                                                              arch_id):
    """Eight decode steps, each writing its token's cache tile in place
    (the lm families carry the stacked cache through the layer scan; all
    are donated to the jit), give the training forward's logits at every
    position, and leave the cache that a prefill of the whole sequence
    writes (the positions past it untouched)."""
    cfg, params = smoke_state(arch_id)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    b, s, p0, cap = 2, 24, 16, 30
    batch = _batch(cfg, b=b, s=s, seed=2)
    tokens = batch["tokens"]
    kwargs = {"frames": batch["frames"]} if cfg.family == "encdec" else {}
    mod = registry.model_module(cfg)
    full, _ = mod.forward(cfg, params, tokens, **kwargs)
    _, cache = mod.prefill(cfg, params, tokens[:, :p0],
                           cache_dtype=jnp.float32, cap=cap, **kwargs)
    decode = jax.jit(functools.partial(mod.decode_step, cfg),
                     donate_argnums=(1,))
    for pos in range(p0, s):
        logits, cache = decode(params, cache, tokens[:, pos:pos + 1],
                               jnp.asarray(pos, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, pos], np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"{arch_id} at {pos}")
    _, want = mod.prefill(cfg, params, tokens, cache_dtype=jnp.float32,
                          cap=cap, **kwargs)
    assert jax.tree.structure(cache) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_param_shapes_no_allocation(arch_id):
    """registry.param_shapes must eval_shape (dry-run path) and match the
    real init for the reduced config."""
    cfg = smoke_config(ARCHS[arch_id])
    shapes = registry.param_shapes(cfg)
    params = registry.init_params(cfg, jax.random.key(0))
    st = jax.tree.structure(shapes)
    pt = jax.tree.structure(params)
    assert st == pt
    for s, p in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
        assert s.shape == p.shape and s.dtype == p.dtype


def test_full_configs_match_assignment():
    """The exact assigned hyper-parameters (guard against drift)."""
    c = ARCHS["qwen1.5-0.5b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (24, 1024, 16, 16, 2816, 151936) and c.qkv_bias
    c = ARCHS["glm4-9b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (40, 4096, 32, 2, 13696, 151552)
    c = ARCHS["qwen3-4b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (36, 2560, 32, 8, 9728, 151936) and c.qk_norm
    c = ARCHS["gemma3-1b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (26, 1152, 4, 1, 6912, 262144)
    assert c.local_global_ratio == 5
    c = ARCHS["zamba2-1.2b"]
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab, c.ssm_state) == \
        (38, 2048, 8192, 32000, 64) and c.family == "hybrid"
    c = ARCHS["llama4-maverick-400b-a17b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.vocab,
            c.n_experts, c.top_k) == (48, 5120, 40, 8, 202048, 128, 1)
    c = ARCHS["olmoe-1b-7b"]
    assert (c.n_layers, c.d_model, c.n_experts, c.top_k) == (16, 2048, 64, 8)
    c = ARCHS["seamless-m4t-medium"]
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == \
        (12, 1024, 4096, 256206) and c.family == "encdec"
    c = ARCHS["qwen2-vl-7b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (28, 3584, 28, 4, 18944, 152064)
    assert c.mrope_sections is not None
    c = ARCHS["falcon-mamba-7b"]
    assert (c.n_layers, c.d_model, c.vocab, c.ssm_state) == \
        (64, 4096, 65024, 16) and c.family == "ssm"


def test_moe_capacity_and_balance():
    """MoE dispatch: token conservation within capacity; aux loss >= 1."""
    from repro.layers.moe import init_moe, moe_ffn
    key = jax.random.key(0)
    p = init_moe(32, 64, 8, jnp.float32, key)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y, aux = moe_ffn(p, x, top_k=2)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) >= 0.99  # E * sum(f_i * p_i) >= 1 by Cauchy-Schwarz


def test_gemma3_local_global_schedule():
    from repro.models.lm import layer_schedule
    cfg = ARCHS["gemma3-1b"]
    windows, thetas = layer_schedule(cfg, 12)
    w = np.asarray(windows)
    assert (w[[5, 11]] == -1).all()          # every 6th layer is global
    assert (w[[0, 1, 2, 3, 4]] == cfg.sliding_window).all()
    th = np.asarray(thetas)
    assert th[5] == cfg.rope_theta_global and th[0] == cfg.rope_theta


def test_mamba_state_cache_is_constant_size():
    cfg = smoke_config(ARCHS["falcon-mamba-7b"])
    c1 = registry.cache_shapes(cfg, batch=2, cap=1024)
    c2 = registry.cache_shapes(cfg, batch=2, cap=1 << 19)
    s1 = [x.shape for x in jax.tree.leaves(c1)]
    s2 = [x.shape for x in jax.tree.leaves(c2)]
    assert s1 == s2  # O(1) in context length -> long_500k tractable
