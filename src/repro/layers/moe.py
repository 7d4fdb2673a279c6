"""Mixture-of-Experts FFN (GShard/Switch-style einsum dispatch).

Compile-friendly and EP-shardable: the expert dimension of the stacked expert
weights is sharded (llama4: experts over "data" x per-expert ffn over "model";
olmoe: experts over "model").  Dispatch/combine are one-hot einsums so XLA
inserts the all-to-alls implied by the shardings.

This layer is also the paper's flagship integration point: expert weights are
the *shared disaggregated pool* ("sharing of machine learning model weights
(especially in expert models) across hosts", paper §1), and the serving path
can route expert access through Space-Control's checked_gather (see
repro.core.pool and examples/shared_pool_serving.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def init_moe(d: int, f: int, n_experts: int, dtype, key,
             *, router_dtype=jnp.float32, n_held: int | None = None) -> dict:
    """Router over all `n_experts`; weights of `n_held` of them (default
    all)."""
    ks = jax.random.split(key, 4)
    s_in = float(1.0 / np.sqrt(d))
    s_out = float(1.0 / np.sqrt(f))
    e = n_experts if n_held is None else n_held
    return {
        "router": jax.random.normal(ks[0], (d, n_experts), router_dtype) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * s_out,
    }


def moe_ffn(p, x, *, top_k: int, capacity_factor: float = 1.25,
            norm_topk_prob: bool = True):
    """x: [B, S, D] -> [B, S, D].  Returns (y, aux_loss)."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(p["router"].dtype) @ p["router"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # [T, K]
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(np.ceil(t * capacity_factor * top_k / e))
    cap = max(cap, 1)

    # position of each (token, k) slot within its expert's buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)       # [T, K, E]
    flatoh = onehot.reshape(t * top_k, e)
    pos = jnp.cumsum(flatoh, axis=0) * flatoh - 1               # [T*K, E]
    pos = pos.reshape(t, top_k, e)
    within = (pos < cap) & (onehot > 0)

    # dispatch tensor [T, E, C] (bf16 one-hot matmuls drive the MXU)
    poh = jax.nn.one_hot(pos, cap, dtype=x.dtype) * \
        within[..., None].astype(x.dtype)                       # [T,K,E,C]
    dispatch = poh.sum(axis=1)                                  # [T, E, C]
    combine = (poh * gate_vals[..., None, None].astype(x.dtype)).sum(axis=1)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)         # [E, C, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])     # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine, expert_out)

    # load-balance aux loss (Switch): E * sum(frac_tokens * frac_probs)
    frac_tok = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (t * top_k)
    frac_prob = probs.mean(axis=0).astype(jnp.float32)
    aux = e * jnp.sum(frac_tok * frac_prob)
    return y.reshape(b, s, d), aux


def moe_ffn_held(p, x, *, top_k: int, held: tuple[int, int],
                 norm_topk_prob: bool, routed_scaling_factor: float = 1.0):
    """The part of a routed-expert layer's output that the experts
    [held[0], held[1]) give, dropless: what one chip of an expert-parallel
    deployment computes before the exchange.

    Routing runs in float32 over every expert of the router (softmax,
    greedy top-k).  Each (token, expert) slot whose expert is held is
    sorted by expert and computed by grouped matmuls over the held experts
    (`jax.lax.ragged_dot`), with no capacity.  x: [B, S, D] ->
    (y [B, S, D], aux, counts int32[2]): the Switch balance loss over all
    experts, and the held slots computed and the held experts with at
    least one slot."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    e0, e1 = held
    n_held = e1 - e0
    t = b * s
    xt = x.reshape(t, d)

    with jax.named_scope("router"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # [T, K]
        if norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
        gate_vals = gate_vals * routed_scaling_factor
        # held slots keyed by their local expert, the rest by n_held (last)
        key = gate_idx.reshape(t * top_k) - e0
        key = jnp.where((key >= 0) & (key < n_held), key, n_held)
        sizes = jnp.bincount(key, length=n_held + 1)[:n_held]
        # a token has at most one slot per expert: this bound drops nothing
        m = t * min(top_k, n_held)
        order = jnp.argsort(key, stable=True)[:m]
        valid = key[order] < n_held
        rows = order // top_k
        gates = jnp.where(valid, gate_vals.reshape(t * top_k)[order], 0.0)

    with jax.named_scope("experts"):
        xs = xt[rows]
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) * \
            jax.lax.ragged_dot(xs, p["w_up"], sizes)
        out = jax.lax.ragged_dot(h, p["w_down"], sizes)
        out = jnp.where(valid[:, None],
                        out.astype(jnp.float32) * gates[:, None], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[rows].add(out).astype(x.dtype)

    frac_tok = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
        1.0) / (t * top_k)
    aux = e * jnp.sum(frac_tok * probs.mean(axis=0))
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return y.reshape(b, s, d), aux, counts
