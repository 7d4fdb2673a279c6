"""Driver of the DeepSeek-V2-Lite serving cell: the serving driver's
closed-loop clients and timed `ServeEngine.step`, on the chip's share of
an expert-parallel deployment (its held experts, all of attention, the
dense layer, the shared experts and the vocabulary), with the served
tokens compared against `bench.reference.dsv2_ref` by the serving cells'
logit-gap rule.

Traffic parameters are those of the serving driver (bench/drivers/serve.py,
"driver": "serve_dsv2").  Besides the serving counters it reports the
engine's routing counters over the window (`expert_slots`, `experts_hit`)
and the least bytes its decode steps had to read (`decode_bytes`,
`bench.roofline_dsv2.decode_bytes`).
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.serve import Clients, weight_seed
from bench.harness import percentile
from bench.reference import dsv2_ref
from bench import roofline_dsv2


def arch_config(cfg: dict):
    """The program's configuration object for the sizes the file states.
    Raises at once, before any weight is made, on a program without latent
    attention or held experts."""
    try:
        from repro.configs.base import ArchConfig, YarnScaling
        from repro.models.registry import decode_step_routed  # noqa: F401
    except ImportError as e:
        raise RuntimeError(
            "the program in this checkout has no latent attention or held "
            f"experts, which {cfg['name']} needs: {e}") from None
    if cfg["rms_norm_eps"] != 1e-6 or cfg["q_lora_rank"] is not None:
        raise ValueError("the program's norms use eps 1e-6 and its latent "
                         "attention has no query compression")
    y = cfg["rope_scaling"]
    return ArchConfig(
        arch_id=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rope_yarn=YarnScaling(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        n_experts=cfg["n_routed_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        shared_expert=cfg["n_shared_experts"] > 0,
        shared_expert_d_ff=cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=tuple(cfg["experts_held"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"])


@functools.partial(jax.jit, static_argnames=("dims", "dense"))
def _convert_layer(lw, *, dims, dense):
    """One layer from the reference's layout to the program's: norm
    weights as offsets from 1, projections split per head, k_nope and v of
    kv_b split, the router transposed to [in, experts] in float32.  The
    rope dimensions keep the published interleaved pairs, which the
    program's rotary embedding turns as pairs (2i, 2i + 1): the same
    rotation as the published de-interleave and rotate-half, with the
    dimensions in another order on q and k alike, so every score is
    unchanged."""
    d, h, nope, rope, r, vd = dims
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)  # exact
    kv_b = lw["wkv_b"].reshape(r, h, nope + vd)
    out = {
        "ln1": off(lw["ln1"]), "ln2": off(lw["ln2"]),
        "attn": {"wq": lw["wq"].reshape(d, h, nope + rope),
                 "wkv_a": lw["wkv_a"], "kv_norm": off(lw["kv_norm"]),
                 "wk_b": kv_b[..., :nope], "wv_b": kv_b[..., nope:],
                 "wo": lw["wo"].reshape(h, vd, d)},
    }
    if dense:
        out["mlp"] = {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                      "w_down": lw["w_down"]}
    else:
        out["moe"] = {"router": lw["router"].T.astype(jnp.float32),
                      "w_gate": lw["experts_gate"], "w_up": lw["experts_up"],
                      "w_down": lw["experts_down"]}
        out["shared_mlp"] = {"w_gate": lw["shared_gate"],
                             "w_up": lw["shared_up"],
                             "w_down": lw["shared_down"]}
    return out


def _stack(layers: list) -> dict:
    """Stack per-layer trees leaf by leaf, emptying `layers` and freeing
    each layer's leaf once it is stacked, so that the model is held about
    once."""
    leaves = [jax.tree.flatten(t) for t in layers]
    treedef = leaves[0][1]
    cols = [list(col) for col in zip(*(lv for lv, _ in leaves))]
    layers.clear()
    del leaves
    out = []
    for col in cols:
        out.append(jnp.stack(col))
        col.clear()
    return jax.tree.unflatten(treedef, out)


def to_program(cfg: dict, arch, seed_word: int) -> dict:
    """The benchmark's weights (`dsv2_ref`, the same seed) made layer by
    layer and converted to the program's layout, as a checkpoint loader
    would; the vocabulary padded with zero rows that no token wins."""
    from repro.models import registry

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dims = (d, h, cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["kv_lora_rank"], cfg["v_head_dim"])
    k = cfg["first_k_dense_replace"]
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        layers.append(_convert_layer(dsv2_ref.make_layer(cfg, seed_word, i),
                                     dims=dims, dense=i < k))
    top = dsv2_ref.make_top(cfg, seed_word)
    vp, dt = arch.vocab_padded, top["embed"].dtype
    pad = lambda x: jnp.zeros((vp, d), dt).at[:cfg["vocab_size"]].set(x)
    params = {
        "embed": {"tok": pad(top["embed"])},
        "final_norm": (top["final_norm"].astype(jnp.float32) - 1.0)
        .astype(dt),
        "head": {"w": pad(top["head"]).T},
        "lead": _stack([layers.pop(0) for _ in range(k)]),
        "units": _stack(layers),
    }
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        registry.param_shapes(arch))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{got} vs {want}")
    return params


def compare(cfg: dict, w: dict, reqs, plen: int, *, control: bool = False):
    """The serving cells' rule (bench/drivers/serve.py `compare`) against
    the DeepSeek-V2-Lite reference: the widest gap of a served token's
    logit below the reference's best, in reference standard deviations;
    with `control`, the same for the fp8 reference's first tokens."""
    gaps, cgaps, var = [], [], []
    for i in range(0, len(reqs), 4):
        block = reqs[i:i + 4]
        toks = np.stack([np.concatenate([p, np.asarray(g, np.int32)])
                         for p, g in block]).astype(np.int32)
        served = jnp.asarray(toks[:, plen:])
        ref = dsv2_ref.logits(cfg, w, toks[:, :-1], plen - 1)
        best = jnp.max(ref, -1)
        gaps.append(best - jnp.take_along_axis(ref, served[..., None],
                                               -1)[..., 0])
        if control:
            top_c = jnp.argmax(dsv2_ref.logits(
                cfg, w, toks[:, :-1], plen - 1, precision="fp8"), -1)
            cgaps.append(best - jnp.take_along_axis(ref, top_c[..., None],
                                                    -1)[..., 0])
        var.append(jnp.var(ref, -1))
    sigma = float(jnp.sqrt(jnp.mean(jnp.concatenate(
        [v.reshape(-1) for v in var]))))
    gap = float(max(float(jnp.max(g)) for g in gaps)) / sigma
    cgap = float(max(float(jnp.max(g)) for g in cgaps)) / sigma \
        if control else None
    return gap, cgap


# widest gap of a served token below the reference's best logit, in
# reference standard deviations; set from sound runs and fp8 controls of
# this cell on a TPU v5e, whose readings PERF.md gives
GAP_LIMIT = 0.3


def run(ctx, *, tamper=None, control: bool = False) -> dict:
    """One run of the cell, as the serving driver's `run`.  `tamper(engine)`
    may break the engine underneath before the window, for the fault
    tests; with `control` the fp8 reference is judged in the program's
    place."""
    from repro.launch.serve import ServeEngine

    cfg, tr = ctx.config, ctx.traffic
    spans = ctx.spans
    plen, gen, batch = tr["prompt_len"], tr["gen"], tr["batch"]
    arch = arch_config(cfg)
    with spans("setup.weights"):
        params = to_program(cfg, arch, weight_seed(ctx.seed))
        jax.block_until_ready(params)
    with spans("setup.engine"):
        engine = ServeEngine(arch, params, batch=batch, cap=plen + gen,
                             fused_egress=True, n_hosts=tr["hosts"],
                             table_capacity=tr["table_capacity"])
        names = [f"tenant-{i}" for i in range(tr["tenants"])]
        for i, name in enumerate(names):
            engine.add_tenant(name, host_id=i * tr["hosts"] // tr["tenants"])
        clients = Clients(ctx, names, cfg["vocab_size"])
        clients.refill(engine, plen, batch)
    if tamper is not None:
        tamper(engine)

    times: dict[tuple, list[float]] = {}
    serial: dict[str, int] = {}
    last_out: dict[str, list] = {}
    # decode steps in the window and the context each one attended over
    decode = {"steps": 0, "context_sum": 0}

    def tick() -> None:
        with spans("bench.serve_step"):
            res = engine.step(gen=gen)
        now = time.perf_counter()
        for name, r in res.items():
            if r["aborted"] or r.get("stalled"):
                continue
            t = engine.tenants[name]
            decode["steps"] += 1
            decode["context_sum"] += t.pos
            if t.out is not last_out.get(name):
                last_out[name] = t.out
                serial[name] = serial.get(name, 0) + 1
            for row in range(len(t.out)):
                times.setdefault((name, serial[name], row), []).append(now)
        clients.refill(engine, plen, batch)

    with spans("setup.warmup"):
        for _ in range(2):
            tick()
        jax.block_until_ready([t.cur for t in engine.tenants.values()
                               if t.cur is not None])
    done0 = {n: len(t.done) for n, t in engine.tenants.items()}
    times.clear()
    decode.update(steps=0, context_sum=0)
    # device scalars: held now, read once after the window
    slots0, hit0 = engine.expert_slots, engine.experts_hit
    ok, err = True, None
    ticks = 0
    ctx.begin_window()
    deadline = ctx.deadline()
    try:
        while time.perf_counter() < deadline:
            tick()
            ticks += 1
        jax.block_until_ready([t.cur for t in engine.tenants.values()
                               if t.cur is not None])
    except Exception as e:  # noqa: BLE001 - a failed tick is a wrong run
        ok, err = False, e
        ctx.log(f"the timed path raised: {e!r}")
    window_s = ctx.end_window()
    expert_slots = int(engine.expert_slots - slots0)
    experts_hit = int(engine.experts_hit - hit0)

    tokens = sum(len(v) for v in times.values())
    gaps = [b - a for v in times.values() for a, b in zip(v, v[1:])]
    finished = [(n, req) for n, t in engine.tenants.items()
                for req in t.done[done0[n]:]]
    aborted = sum(len(t.aborted) for t in engine.tenants.values())
    ctx.log(f"window {window_s:.3f} s: {ticks} ticks, {tokens} tokens, "
            f"{len(times)} requests served from, {len(finished)} finished, "
            f"{aborted} aborted, {len(gaps)} token gaps, "
            f"{expert_slots} held expert slots, {experts_hit} experts hit")

    del engine, params
    gc.collect()
    pick = ctx.rng("sample").permutation(len(finished))[
        :tr["compare_requests"]]
    reqs = [(np.asarray(finished[i][1][0], np.int32), finished[i][1][1])
            for i in sorted(pick)]
    if reqs and all(len(g) == gen for _, g in reqs):
        w = dsv2_ref.make_weights(cfg, weight_seed(ctx.seed))
        gap, cgap = compare(cfg, w, reqs, plen, control=control)
        del w
    else:
        ok = False
        gap, cgap = float("inf"), None
    ctx.log(f"compared {len(reqs)} requests ({len(reqs) * gen} served "
            f"tokens): widest gap {gap!r} reference standard deviations"
            + (f", fp8 control {cgap!r}" if control else ""))

    flops = roofline_dsv2.flops_per_token(cfg, plen + gen / 2,
                                          prefill=False) \
        + plen / gen * roofline_dsv2.flops_per_token(cfg, plen / 2,
                                                     prefill=True)
    counters = {"ticks": ticks, "tokens": tokens, "requests": len(times),
                "finished": len(finished), "aborted": aborted,
                "token_gaps": len(gaps), "flops_per_token": flops,
                "window_s": window_s, "expert_slots": expert_slots,
                "experts_hit": experts_hit,
                "decode_steps": decode["steps"],
                "decode_bytes": roofline_dsv2.decode_bytes(
                    cfg, steps=decode["steps"], batch=batch,
                    context_sum=decode["context_sum"],
                    experts_hit=experts_hit)}
    if control:
        counters["program_gap"] = gap
        gap = gap if cgap is None else cgap
    metrics = {"tokens_per_s": tokens / window_s,
               "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps
               else 1e3 * window_s}
    return {"metrics": metrics, "attempted": len(times),
            "failed": aborted + (err is not None), "ok": ok and not aborted,
            "counters": counters,
            "checks": {"logit_gap": (gap, GAP_LIMIT)}}
