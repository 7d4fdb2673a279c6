"""Serving engine: batched multi-tenant decode + live revocation."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.launch.serve import ServeEngine
from repro.layers.common import rms_norm, unembed
from repro.models import lm, registry

PLEN, GEN = 10, 4


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    return cfg, registry.init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    e = ServeEngine(cfg, params, batch=2, cap=24)
    e.add_tenant("a", host_id=0)
    e.add_tenant("b", host_id=1)
    return e


def test_batched_decode_serves_all(engine):
    rng = np.random.default_rng(0)
    for _ in range(3):
        engine.submit("a", rng.integers(3, engine.cfg.vocab - 1, 12))
    r = engine.run_tenant("a", gen=4)
    assert not r["aborted"] and r["served"] == 3
    assert len(engine.tenants["a"].done) == 3
    for prompt, generated in engine.tenants["a"].done:
        assert len(generated) == 4
        assert all(0 <= t < engine.cfg.vocab_padded for t in generated)


def test_tenants_isolated_kv_ranges(engine):
    a, b = engine.tenants["a"], engine.tenants["b"]
    assert a.hwpid != b.hwpid
    ra = range(a.kv_start_page, a.kv_start_page + a.kv_n_pages)
    rb = range(b.kv_start_page, b.kv_start_page + b.kv_n_pages)
    assert set(ra).isdisjoint(rb)


def test_revocation_aborts_decoding(engine):
    rng = np.random.default_rng(1)
    engine.submit("b", rng.integers(3, engine.cfg.vocab - 1, 12))
    engine.revoke("b")
    r = engine.run_tenant("b", gen=4)
    assert r["aborted"] and r["fault"] > 0
    # tenant a unaffected
    engine.submit("a", rng.integers(3, engine.cfg.vocab - 1, 12))
    r2 = engine.run_tenant("a", gen=2)
    assert not r2["aborted"]


def _greedy(cfg, params, group, batch, gen):
    """Each prompt's greedy tokens, one `registry.decode_step` at a time
    after a prefill of the group padded to `batch` rows (as the engine
    lays it out); the padding rows' tokens are dropped."""
    decode = jax.jit(functools.partial(registry.decode_step, cfg))
    plen = max(len(p) for p in group)
    toks = np.full((batch, plen), 2, np.int32)
    for i, p in enumerate(group):
        toks[i, :len(p)] = p
    logits, cache = registry.prefill(
        cfg, params, {"tokens": jnp.asarray(toks)}, cache_dtype=cfg.pdtype,
        cap=plen + gen)
    served = []
    for pos in range(plen, plen + gen):
        cur = np.argmax(np.asarray(logits[:, -1]), -1).astype(np.int32)
        served.append(cur)
        logits, cache = decode(params, cache, jnp.asarray(cur[:, None]),
                               jnp.asarray(pos, jnp.int32))
    return np.stack(served, 1)[:len(group)].tolist()


def _prompts(cfg, seed, n):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab - 1, PLEN).astype(np.int32)
            for _ in range(n)]


def test_served_tokens_are_the_greedy_tokens_and_padding_is_never_served(
        model):
    cfg, params = model
    e = ServeEngine(cfg, params, batch=2, cap=PLEN + GEN)
    t = e.add_tenant("a", host_id=0)
    prompts = _prompts(cfg, 7, 3)          # a group of 2, then a group of 1
    for p in prompts:
        e.submit("a", p)
    served_rows = []
    while e.has_work():
        out = t.out if t.group is not None else None
        before = [len(o) for o in out or ()]
        e.step(gen=GEN)
        served_rows.append(len(t.out))
        if t.out is out:                   # one more token on every row
            assert [len(o) for o in out] == [n + 1 for n in before]
    assert served_rows == [2] * GEN + [1] * GEN
    assert len(t.done) == 3 and not t.aborted
    want = (_greedy(cfg, params, prompts[:2], 2, GEN)
            + _greedy(cfg, params, prompts[2:], 2, GEN))
    for (prompt, generated), p, w in zip(t.done, prompts, want):
        assert list(prompt) == list(p)
        assert generated == w


def test_a_tenant_revoked_mid_group_serves_nothing_from_its_deny(model):
    cfg, params = model
    e = ServeEngine(cfg, params, batch=2, cap=PLEN + GEN)
    a = e.add_tenant("a", host_id=0)
    c = e.add_tenant("c", host_id=0)       # co-resident on a's host
    pa, pc = _prompts(cfg, 8, 2), _prompts(cfg, 9, 2)
    for p in pa:
        e.submit("a", p)
    for p in pc:
        e.submit("c", p)
    for _ in range(2):
        e.step(gen=GEN)
    a_out, c_out = [list(o) for o in a.out], [len(o) for o in c.out]
    e.revoke("a")
    res = e.step(gen=GEN)
    assert res["a"]["aborted"] and not res["c"]["aborted"]
    assert a.out == a_out and [len(o) for o in c.out] == [n + 1 for n in c_out]
    while e.has_work():
        assert not e.step(gen=GEN).get("a")
    assert [list(p) for p in a.aborted] == [list(p) for p in pa]
    assert not a.done and a.out == a_out
    assert [g for _, g in c.done] == _greedy(cfg, params, pc, 2, GEN)


def test_every_decode_step_consumes_its_cache_in_place(model):
    """Each decode launch is given the tenant's cache to update in place:
    after every tick the launches that consumed their input cache equal
    the decode steps, the old cache's buffers are gone, and the served
    tokens are still the greedy tokens."""
    cfg, params = model
    e = ServeEngine(cfg, params, batch=2, cap=PLEN + GEN)
    pa, pb = _prompts(cfg, 10, 2), _prompts(cfg, 11, 2)
    for name, host, prompts in (("a", 0, pa), ("b", 1, pb)):
        e.add_tenant(name, host_id=host)
        for p in prompts:
            e.submit(name, p)
    ticks = 0
    while e.has_work():
        before = {n: t.cache for n, t in e.tenants.items()
                  if t.group is not None}
        e.step(gen=GEN)
        ticks += 1
        assert e.cache_donations == e.steps
        assert all(leaf.is_deleted() for c in before.values()
                   for leaf in jax.tree.leaves(c))
    assert ticks == GEN and e.steps == 2 * GEN
    assert [g for _, g in e.tenants["a"].done] == _greedy(cfg, params, pa,
                                                           2, GEN)
    assert [g for _, g in e.tenants["b"].done] == _greedy(cfg, params, pb,
                                                           2, GEN)


def _decode_through_xs(cfg, params, cache, tokens, pos):
    """The decode step as the layer scan ran it before the cache was
    carried: each layer's cache a scan input, its new cache a scan
    output."""
    x = lm._embed_inputs(cfg, params, tokens, None)
    positions = jnp.broadcast_to(pos, tokens.shape)
    windows, thetas = lm.layer_schedule(cfg, lm.n_units(cfg))

    def body(x, xs):
        up, w, th, c = xs
        x, c, _ = lm.apply_unit(cfg, up, x, positions, w, th, c, pos)
        return x, c
    x, cache = jax.lax.scan(body, x, (params["units"], windows, thetas,
                                      cache))
    x = rms_norm(params["final_norm"], x)
    return unembed(params["embed"], params["head"], x,
                   tied=cfg.tie_embeddings), cache


def test_the_compiled_decode_updates_the_cache_without_a_copy(model):
    """Compiled at test size: the serve decode aliases its whole cache
    input to its output, and needs at least one cache fewer bytes of
    temporaries than the scan that passed each layer's cache in and out,
    donated alike, which copies the stacked cache into a new one."""
    cfg, _ = model
    b, cap = 4, 256
    args = (registry.param_shapes(cfg),
            registry.cache_shapes(cfg, b, cap, cfg.pdtype),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(args[1]))
    engine = ServeEngine(cfg, None, batch=b, cap=cap)
    carried = engine._decode.lower(*args).compile().memory_analysis()
    through_xs = jax.jit(functools.partial(_decode_through_xs, cfg),
                         donate_argnums=(1,)).lower(*args).compile() \
        .memory_analysis()
    assert carried.alias_size_in_bytes >= cache_bytes
    assert carried.temp_size_in_bytes + cache_bytes <= \
        through_xs.temp_size_in_bytes
