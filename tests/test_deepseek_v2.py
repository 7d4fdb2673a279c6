"""DeepSeek-V2-Lite at smoke size on the CPU, against the plain reference
(bench/reference/dsv2_ref.py) on seeded random weights: the served path
(prefill, then decode through the latent cache), the absorbed decode, the
held-expert shares and dropless routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_dsv2
from bench.reference import dsv2_ref
from repro.configs import ARCHS, smoke_config
from repro.launch.serve import ServeEngine
from repro.layers.attention import cache_len
from repro.layers.common import rms_norm, swiglu
from repro.layers.mla import init_mla, mla_attention
from repro.layers.moe import init_moe, moe_ffn_held
from repro.models import lm, registry

# the cut of the benchmark cell at smoke size, in float32: 4 of 16 experts
# held (experts 4-7), top-6, gates not renormalised, one dense layer
CFG = {
    "name": "smoke-dsv2", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 6, "n_shared_experts": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "torch_dtype": "float32",
}
SEED = 1234


def _cfg(**kw):
    return dict(CFG, **kw)


def _program(cfg):
    arch = serve_dsv2.arch_config(cfg)
    return arch, serve_dsv2.to_program(cfg, arch, SEED)


def _tokens(b, s, seed=0):
    return np.random.default_rng(seed).integers(
        3, CFG["vocab_size"] - 1, (b, s)).astype(np.int32)


def test_published_config_and_latent_cache_shape():
    """The registered config has the published widths, and its serving
    cache holds 576 numbers a token a layer (512 latent + 64 rope key) in
    bf16, for the dense layer and the 26 routed layers, over the asked
    positions rounded up to whole 128-position tiles."""
    c = ARCHS["deepseek-v2-lite"]
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (27, 2048, 16, 10944, 102400, 512, 128, 64, 128)
    assert (c.n_experts, c.top_k, c.expert_d_ff, c.shared_expert_d_ff,
            c.first_k_dense, c.norm_topk_prob) == (64, 6, 1408, 2816, 1,
                                                   False)
    assert abs(c.mla_scale - 192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1)
               ** 2) < 1e-12 and c.rope_yarn.cos_scale == 1.0
    assert 15.6e9 < c.n_params() < 15.8e9
    cache = registry.cache_shapes(c, batch=32, cap=1149)
    assert cache["lead"].ckv.shape == (1, 32, 1152, 576)
    assert cache["units"].ckv.shape == (26, 32, 1152, 576)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(cache))


def test_yarn_frequencies_match_the_reference():
    c = ARCHS["deepseek-v2-lite"]
    inv, cos_scale, scale = dsv2_ref._yarn(
        {**CFG, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128})
    np.testing.assert_allclose(c.rope_yarn.inv_freq(64, c.rope_theta), inv,
                               rtol=1e-6)
    assert cos_scale == c.rope_yarn.cos_scale and \
        abs(scale - c.mla_scale) < 1e-12


def test_prefill_then_decode_matches_the_reference():
    """Prefill, then three decode steps through the latent cache, give the
    reference's teacher-forced logits; the decode steps' routing counts
    are the reference's held slots at those positions."""
    cfg = _cfg()
    arch, params = _program(cfg)
    w = dsv2_ref.make_weights(cfg, SEED)
    toks = _tokens(2, 12)
    ref = np.asarray(dsv2_ref.logits(cfg, w, toks, 8))
    sigma = ref.std()
    logits, cache = lm.prefill(arch, params, jnp.asarray(toks[:, :9]),
                               cache_dtype=jnp.float32, cap=12)
    got = [np.asarray(logits[:, -1])]
    slots = 0
    for pos in range(9, 12):
        logits, cache, counts = lm.decode_step_routed(
            arch, params, cache, jnp.asarray(toks[:, pos:pos + 1]),
            jnp.int32(pos))
        got.append(np.asarray(logits[:, 0]))
        slots += int(counts[0])
    got = np.stack(got, 1)[..., :cfg["vocab_size"]]
    assert np.max(np.abs(got - ref)) < 1e-3 * sigma
    assert slots == dsv2_ref.held_slots(cfg, w, toks) - \
        dsv2_ref.held_slots(cfg, w, toks[:, :9])


def test_the_engine_serves_the_reference_tokens_from_a_donated_cache():
    """`ServeEngine` over the latent cache, a group of two prompts and then
    a group of one: every decode launch consumes its cache in place
    (`cache_donations == steps` after every tick), and every served token
    is the reference's best at its position, teacher-forced over the
    served tokens, as the benchmark's comparison judges it."""
    cfg = _cfg()
    arch, params = _program(cfg)
    plen, gen = 9, 4
    e = ServeEngine(arch, params, batch=2, cap=plen + gen)
    t = e.add_tenant("a", host_id=0)
    for p in _tokens(3, plen, seed=5):
        e.submit("a", p)
    while e.has_work():
        e.step(gen=gen)
        assert e.cache_donations == e.steps
    assert e.steps == 2 * gen and len(t.done) == 3 and not t.aborted
    toks = np.stack([np.concatenate([p, np.asarray(g, np.int32)])
                     for p, g in t.done])
    ref = np.asarray(dsv2_ref.logits(cfg, dsv2_ref.make_weights(cfg, SEED),
                                     toks[:, :-1], plen - 1))
    served = toks[:, plen:]
    gap = ref.max(-1) - np.take_along_axis(ref, served[..., None], -1)[..., 0]
    assert gap.max() < 1e-3 * ref.std()


def test_absorbed_decode_equals_decompressed_attention():
    """Decode in the absorbed form, over the latent cache, gives what the
    decompressed causal attention gives at the last position."""
    arch = smoke_config(ARCHS["deepseek-v2-lite"])
    p = init_mla(arch, jax.random.key(3))
    b, s = 2, 10
    x = jax.random.normal(jax.random.key(4), (b, s, arch.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    full, _ = mla_attention(arch, p, x, pos)
    cache = lm.init_unit_cache(arch, b, s, jnp.float32)
    _, cache = mla_attention(arch, p, x[:, :-1], pos[:, :-1], cache=cache)
    last, cache = mla_attention(arch, p, x[:, -1:], pos[:, -1:],
                                cache=cache, cache_pos=s - 1)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-4,
                               atol=1e-5)
    assert cache.ckv.shape == (b, cache_len(s), arch.kv_lora_rank
                               + arch.qk_rope_head_dim)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Every chip's share of a routed layer (two experts each of 8), with
    what each computes alike (attention, shared experts) counted once,
    adds up to the uncut reference layer."""
    cfg = _cfg(n_routed_experts=8, n_routed_experts_published=8,
               experts_held=[0, 8])
    arch = serve_dsv2.arch_config(cfg)
    lw = dsv2_ref.make_layer(cfg, SEED, 1)
    x = jax.random.normal(jax.random.key(5), (2, 8, cfg["hidden_size"]))
    ref, _ = dsv2_ref._layer(x, lw, cfg_items=dsv2_ref._layer_items(cfg),
                             dense=False, precision="f32")
    pl = serve_dsv2._convert_layer(lw, dims=(64, 4, 16, 8, 32, 16),
                                   dense=False)
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    xa, _ = lm._attention_block(arch, pl, x, pos, -1, 1e4, None, None)
    h = rms_norm(pl["ln2"], xa)
    total = xa + swiglu(pl["shared_mlp"], h)
    for e0 in range(0, 8, 2):
        share = dict(pl["moe"], **{k: pl["moe"][k][e0:e0 + 2]
                                   for k in ("w_gate", "w_up", "w_down")})
        total = total + moe_ffn_held(share, h, top_k=6, held=(e0, e0 + 2),
                                     norm_topk_prob=False)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_skewed_routing_drops_nothing():
    """Every token routes to one held expert (64 slots where a capacity
    of 1.25 would keep 30): the held share equals the dense sum over the
    held experts, and every held slot is counted."""
    d, f, e, k, t = 16, 24, 8, 3, 64
    held = (2, 6)
    p = init_moe(d, f, e, jnp.float32, jax.random.key(6),
                 n_held=held[1] - held[0])
    p["router"] = p["router"].at[:, 3].add(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(7), (1, t, d)))
    y, _, counts = moe_ffn_held(p, x, top_k=k, held=held,
                                norm_topk_prob=False)

    xt = np.asarray(x[0], np.float64)
    logits = xt @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1, kind="stable")[:, :k]
    assert (top[:, 0] == 3).all()
    want = np.zeros_like(xt)
    slots = 0
    for j in range(held[1] - held[0]):
        gate = np.where(top == held[0] + j,
                        np.take_along_axis(probs, top, -1), 0).sum(-1)
        slots += int((top == held[0] + j).sum())
        g = xt @ np.asarray(p["w_gate"][j], np.float64)
        u = xt @ np.asarray(p["w_up"][j], np.float64)
        want += gate[:, None] * ((g / (1 + np.exp(-g)) * u)
                                 @ np.asarray(p["w_down"][j], np.float64))
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-5)
    assert int(counts[0]) == slots >= t
    assert int(counts[1]) == len(set(top.ravel()) & set(range(*held)))


@pytest.mark.parametrize("norm", [True, False])
def test_capacity_paths_follow_norm_topk_prob(norm):
    """The einsum and sorted-dispatch paths weigh the top-k gates as the
    config says: renormalised to sum 1 (the default), or as routed."""
    from repro.layers.moe import moe_ffn
    from repro.layers.moe_ep import moe_ffn_ep
    p = init_moe(16, 24, 8, jnp.float32, jax.random.key(8))
    x = jax.random.normal(jax.random.key(9), (2, 12, 16))
    a, _ = moe_ffn(p, x, top_k=2, capacity_factor=8.0, norm_topk_prob=norm)
    b, _ = moe_ffn_ep(p, x, top_k=2, capacity_factor=8.0,
                      norm_topk_prob=norm)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
    dflt, _ = moe_ffn(p, x, top_k=2, capacity_factor=8.0)
    assert np.allclose(np.asarray(a), np.asarray(dflt)) == norm

