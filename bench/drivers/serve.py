"""General driver of the serving cells: a `ServeEngine` with the fused
fabric egress kernel on every tick, tenants admitted on fabric hosts, and
closed-loop clients that send a new prompt as soon as their previous
request has finished.

Traffic parameters (bench/traffic/<mix>.json, "driver": "serve"):

    tenants, hosts   tenants admitted, and the fabric hosts they share
                     (tenant i on host i * hosts // tenants)
    batch            closed-loop clients of each tenant (its group size)
    prompt_len, gen  prompt tokens and generated tokens of every request
    table_capacity   fabric permission-table entries
    compare_requests finished requests, drawn from the seed, whose served
                     tokens are compared with the reference

All of a cell's groups start together, so one prefill shape and one decode
shape serve the whole window.  The timed entry is `ServeEngine.step`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import percentile, seed_words
from bench.reference import qwen_ref
from bench.roofline import decoder_flops_per_token


def arch_config(cfg: dict):
    """The program's configuration object for the sizes the file states."""
    from repro.configs.base import ArchConfig
    h = cfg["num_attention_heads"]
    return ArchConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["hidden_size"] // h, qkv_bias=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), param_dtype=cfg["torch_dtype"])


def to_program(cfg: dict, arch, w: dict) -> dict:
    """The benchmark's weights in the program's layout, as a checkpoint
    loader would convert them: norm weights as offsets from 1, q and k head
    dimensions reordered from rotate-half to the program's interleaved
    rotary pairs (dimension i, i + hd/2 -> 2i, 2i + 1; a permutation that
    leaves every q.k product unchanged), projections split per head, and
    the vocabulary padded with zero rows that no token ever wins."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry

    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    perm = np.empty(hd, np.int32)
    perm[0::2] = np.arange(hd // 2)
    perm[1::2] = np.arange(hd // 2) + hd // 2
    dt = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def convert(w):
        def heads(x, n, rotary):
            x = x.reshape(x.shape[:-1] + (n, hd))
            return x[..., perm] if rotary else x
        off = lambda g: (g.astype(jnp.float32) - 1.0).astype(dt)  # exact
        vp = arch.vocab_padded
        embed = jnp.zeros((vp, d), dt).at[:cfg["vocab_size"]].set(w["embed"])
        return {
            "embed": {"tok": embed},
            "units": {
                "ln1": off(w["ln1"]), "ln2": off(w["ln2"]),
                "attn": {
                    "wq": heads(w["wq"], h, True),
                    "wk": heads(w["wk"], kv, True),
                    "wv": heads(w["wv"], kv, False),
                    "wo": w["wo"].reshape(L, h, hd, d),
                    "bq": heads(w["bq"], h, True),
                    "bk": heads(w["bk"], kv, True),
                    "bv": heads(w["bv"], kv, False),
                },
                "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                        "w_down": w["w_down"]},
            },
            "final_norm": off(w["final_norm"]),
            "head": {},
        }

    params = convert(w)
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        registry.param_shapes(arch))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{got} vs {want}")
    return params


def weight_seed(seed: int) -> int:
    return seed_words(seed, 1, "weights")[0]


class Clients:
    """Closed-loop clients: each tenant's `batch` clients send their next
    prompt when the previous request has finished; prompts come from a
    per-tenant stream drawn from the seed."""

    def __init__(self, ctx, names, vocab: int):
        self.rngs = {n: ctx.rng(f"prompts-{n}") for n in names}
        self.vocab = vocab

    def refill(self, engine, plen: int, batch: int) -> None:
        for name, t in engine.tenants.items():
            if t.group is None and not t.queue:
                for _ in range(batch):
                    engine.submit(name, self.rngs[name].integers(
                        3, self.vocab - 1, plen).astype(np.int32))


def compare(cfg: dict, w: dict, reqs, plen: int, *, control: bool = False):
    """The widest gap by which a served token's logit lies below the
    reference's best, in units of the reference logits' standard deviation,
    over the served tokens of `reqs` [(prompt, generated)].  With
    `control`, also the same gap for the token that the fp8 reference puts
    first at each position of the same tokens (else None)."""
    import jax.numpy as jnp
    gaps, cgaps, var = [], [], []
    for i in range(0, len(reqs), 4):
        block = reqs[i:i + 4]
        toks = np.stack([np.concatenate([p, np.asarray(g, np.int32)])
                         for p, g in block]).astype(np.int32)
        served = jnp.asarray(toks[:, plen:])
        ref = qwen_ref.logits(cfg, w, toks[:, :-1], plen - 1)
        best = jnp.max(ref, -1)
        gaps.append(best - jnp.take_along_axis(ref, served[..., None],
                                               -1)[..., 0])
        if control:
            top_c = jnp.argmax(qwen_ref.logits(
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
# both serving cells on a TPU v5e, whose readings PERF.md gives
GAP_LIMIT = 0.3


def run(ctx, *, tamper=None, control: bool = False) -> dict:
    """One run of a serving cell.  `tamper(engine)` may break the engine
    underneath before the window, for the fault tests.  With `control`
    (bench/tools/control.py) the fp8 reference's first tokens are judged
    in the program's place, and the program's own gap is a counter."""
    import jax
    from repro.launch.serve import ServeEngine

    cfg, tr = ctx.config, ctx.traffic
    spans = ctx.spans
    plen, gen, batch = tr["prompt_len"], tr["gen"], tr["batch"]
    arch = arch_config(cfg)
    with spans("setup.weights"):
        w = qwen_ref.make_weights(cfg, weight_seed(ctx.seed))
        params = to_program(cfg, arch, w)
        del w
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

    # host times at which each request's tokens were served, keyed by
    # (tenant, serial number of its group, row)
    times: dict[tuple, list[float]] = {}
    serial: dict[str, int] = {}
    last_out: dict[str, list] = {}

    def tick() -> None:
        with spans("bench.serve_step"):
            res = engine.step(gen=gen)
        now = time.perf_counter()
        for name, r in res.items():
            if r["aborted"] or r.get("stalled"):
                continue
            t = engine.tenants[name]
            if t.out is not last_out.get(name):
                last_out[name] = t.out
                serial[name] = serial.get(name, 0) + 1
            for row in range(len(t.out)):
                times.setdefault((name, serial[name], row), []).append(now)
        clients.refill(engine, plen, batch)

    # warm-up: the prefill tick and one decode tick compile every shape
    # the window uses (prefill (batch, prompt), decode at cap, the checker
    # and the fused kernel at the window's row count)
    with spans("setup.warmup"):
        for _ in range(2):
            tick()
        jax.block_until_ready([t.cur for t in engine.tenants.values()
                               if t.cur is not None])
    done0 = {n: len(t.done) for n, t in engine.tenants.items()}
    times.clear()
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

    tokens = sum(len(v) for v in times.values())
    gaps = [b - a for v in times.values() for a, b in zip(v, v[1:])]
    finished = [(n, req) for n, t in engine.tenants.items()
                for req in t.done[done0[n]:]]
    aborted = sum(len(t.aborted) for t in engine.tenants.values())
    ctx.log(f"window {window_s:.3f} s: {ticks} ticks, {tokens} tokens, "
            f"{len(times)} requests served from, {len(finished)} finished, "
            f"{aborted} aborted, {len(gaps)} token gaps")

    # -- checks: served tokens of a sample of finished requests ------------
    del engine, params
    gc.collect()
    pick = ctx.rng("sample").permutation(len(finished))[
        :tr["compare_requests"]]
    reqs = [(np.asarray(finished[i][1][0], np.int32), finished[i][1][1])
            for i in sorted(pick)]
    if reqs and all(len(g) == gen for _, g in reqs):
        w = qwen_ref.make_weights(cfg, weight_seed(ctx.seed))
        gap, cgap = compare(cfg, w, reqs, plen, control=control)
        del w
    else:
        ok = False
        gap, cgap = float("inf"), None
    ctx.log(f"compared {len(reqs)} requests ({len(reqs) * gen} served "
            f"tokens): widest gap {gap!r} reference standard deviations"
            + (f", fp8 control {cgap!r}" if control else ""))

    flops = decoder_flops_per_token(cfg, plen + gen / 2) + plen / gen * \
        decoder_flops_per_token(cfg, plen / 2)
    counters = {"ticks": ticks, "tokens": tokens, "requests": len(times),
                "finished": len(finished), "aborted": aborted,
                "token_gaps": len(gaps), "flops_per_token": flops,
                "window_s": window_s}
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
