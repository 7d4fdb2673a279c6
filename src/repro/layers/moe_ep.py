"""Expert-parallel MoE via shard_map + sorted (gather/scatter) dispatch.

Beyond-paper optimization (EXPERIMENTS.md §Perf H3/H4).  The baseline
GShard-style einsum dispatch in ``moe.py`` builds a [T, E, C] one-hot
tensor and pays 2*T*E*C*D dispatch FLOPs — for olmoe prefill_32k that is
~40x the useful expert FLOPs (measured useful_flops_ratio 0.004), and for
llama4 (experts sharded over "data") it additionally forces an all-gather
of ALL tokens.  Here dispatch is data movement, not matmul:

  * route: top-k per token, capacity positions via cumsum (int ops),
  * dispatch: token_idx [E_loc, C] scatter + one gather  xt[token_idx],
  * expert FFN: the only matmuls left are the useful ones,
  * combine: gather expert outputs back per (token, k) slot + weighted sum.

Two mesh layouts, chosen by ``cfg.expert_axis``:

  experts over "model"  (olmoe): tokens stay on their data shard
      (replicated over model); each model column computes its E/m experts
      on the column-local copy and a single psum over "model" combines.
      Per-layer collectives: 1 all-reduce of [t_loc, D].

  experts over "data" + per-expert FFN over "model"  (llama4 2-D EP):
      tokens all_to_all over "data" to the expert's home row, FFN computed
      with the model-column F-slice (psum over "model" after w_down), then
      all_to_all back.  Per-layer collectives: 2 all-to-all + 1 all-reduce.

Both modes are numerically identical to ``moe.moe_ffn`` when capacity is
non-binding (tests/test_moe_ep.py); with binding capacity both drop
over-capacity (token, k) slots — same semantics, different drop order
(GShard drop order is position-in-batch; ours is position-in-shard).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.activations import current_mesh


def _shmap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# local (per-shard) routing helpers — plain jnp, shard_map-safe
# ---------------------------------------------------------------------------

def _route(xt, router, top_k: int):
    """[t, D] -> (gate_vals [t,K], gate_idx [t,K], aux scalar)."""
    logits = xt.astype(router.dtype) @ router                 # [t, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    e = router.shape[1]
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    frac_tok = onehot.sum(axis=(0, 1)) / (xt.shape[0] * top_k)
    frac_prob = probs.mean(axis=0).astype(jnp.float32)
    aux = e * jnp.sum(frac_tok * frac_prob)
    return gate_vals, gate_idx, aux


def _positions(gate_idx, n_experts: int, cap: int):
    """Per-(token,k) slot position within its expert's capacity buffer.

    Returns (pos [t,K] int32, valid [t,K] bool).  Order: flat (t*K) program
    order (cheap, deterministic).
    """
    t, k = gate_idx.shape
    flat = gate_idx.reshape(t * k)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # [tK, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1)                     # [tK, E]
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    valid = pos < cap
    return pos.reshape(t, k).astype(jnp.int32), valid.reshape(t, k)


def _scatter_token_idx(gate_idx, pos, valid, n_experts: int, cap: int, t: int):
    """token slot table [E, C]: flat token index (t*K space) per slot;
    empty slots hold t*K (points at a zero pad row)."""
    tk = gate_idx.size
    flat_e = gate_idx.reshape(tk)
    flat_p = pos.reshape(tk)
    flat_v = valid.reshape(tk)
    slot = jnp.where(flat_v, flat_e * cap + flat_p, n_experts * cap)
    table = jnp.full((n_experts * cap + 1,), tk, jnp.int32)
    table = table.at[slot].set(jnp.arange(tk, dtype=jnp.int32), mode="drop")
    return table[: n_experts * cap].reshape(n_experts, cap)


def _expert_ffn(expert_in, wg, wu, wd):
    """[Eloc, C, D] x [Eloc, D, F] -> [Eloc, C, D] (the useful FLOPs)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


# ---------------------------------------------------------------------------
# mode 1: experts over the model axis (olmoe)
# ---------------------------------------------------------------------------

def _moe_block_model_axis(xt, router, wg, wu, wd, *, top_k: int, cap: int,
                          n_experts: int, model_axis: str):
    """shard_map body.  xt [t_loc, D] (same copy on every model column);
    wg/wu/wd [E_loc, ...] (this column's experts)."""
    t = xt.shape[0]
    e_loc = wg.shape[0]
    j = jax.lax.axis_index(model_axis) if model_axis else jnp.int32(0)
    e0 = j * e_loc

    gate_vals, gate_idx, aux = _route(xt, router, top_k)
    pos, valid = _positions(gate_idx, n_experts, cap)
    token_idx = _scatter_token_idx(gate_idx, pos, valid, n_experts, cap, t)
    token_idx = jax.lax.dynamic_slice(token_idx, (e0, 0), (e_loc, cap))

    # gather my experts' tokens ([tK] flat space; pad row = zeros)
    xt_pairs = jnp.concatenate(
        [jnp.repeat(xt, top_k, axis=0), jnp.zeros((1, xt.shape[1]), xt.dtype)])
    expert_in = xt_pairs[token_idx]                       # [E_loc, C, D]
    expert_out = _expert_ffn(expert_in, wg, wu, wd)       # [E_loc, C, D]

    # combine: (t, k) slot fetches its output if its expert is local
    owner = gate_idx // e_loc                             # [t, K] column id
    local = (owner == j) & valid
    local_slot = jnp.where(
        local, (gate_idx - e0) * cap + pos, e_loc * cap)  # [t, K]
    out_flat = jnp.concatenate(
        [expert_out.reshape(e_loc * cap, -1),
         jnp.zeros((1, xt.shape[1]), expert_out.dtype)])
    per_k = out_flat[local_slot]                          # [t, K, D]
    y = jnp.einsum("tkd,tk->td", per_k,
                   gate_vals.astype(per_k.dtype) * local.astype(per_k.dtype))
    if model_axis:
        y = jax.lax.psum(y, model_axis)
        aux = jax.lax.pmean(aux, model_axis)
    return y, aux


# ---------------------------------------------------------------------------
# mode 2: experts over the data axis, per-expert FFN over model (llama4)
# ---------------------------------------------------------------------------

def _moe_block_data_axis(xt, router, wg, wu, wd, *, top_k: int, cap: int,
                         n_experts: int, data_axes: tuple,
                         model_axis: str):
    """shard_map body.  xt [t_loc, D] per data shard (replicated over
    model); wg/wu/wd [E_loc, D, F_loc] (this data-row's experts, this
    model-column's FFN slice)."""
    t, d = xt.shape
    e_loc = wg.shape[0]
    rows = n_experts // e_loc                     # data-axis size

    gate_vals, gate_idx, aux = _route(xt, router, top_k)
    dest = gate_idx // e_loc                      # [t, K] home row per slot

    # per-destination-row send positions (capacity per row)
    send_cap = cap * e_loc                        # row-level capacity
    pos_r, valid_r = _positions(dest, rows, send_cap)

    # pack [rows, send_cap] of flat (t*K) indices
    table = _scatter_token_idx(dest, pos_r, valid_r, rows, send_cap, t)
    xt_pairs = jnp.concatenate(
        [jnp.repeat(xt, top_k, axis=0), jnp.zeros((1, d), xt.dtype)])
    send = xt_pairs[table]                                    # [R, S, D]
    eid_pairs = jnp.concatenate(
        [(gate_idx % e_loc).reshape(-1), jnp.array([e_loc], jnp.int32)])
    send_eid = eid_pairs[table]                               # [R, S]
    send_valid = table < t * top_k                            # [R, S]

    # all_to_all over the data axis: row dim <-> shard dim
    recv = jax.lax.all_to_all(send, data_axes, split_axis=0, concat_axis=0,
                              tiled=True).reshape(rows * send_cap, d)
    recv_eid = jax.lax.all_to_all(send_eid, data_axes, 0, 0,
                                  tiled=True).reshape(rows * send_cap)
    recv_valid = jax.lax.all_to_all(send_valid, data_axes, 0, 0,
                                    tiled=True).reshape(rows * send_cap)

    # second-level dispatch to my e_loc experts
    recv_eid = jnp.where(recv_valid, recv_eid, e_loc)
    pos2, valid2 = _positions(recv_eid[:, None], e_loc + 1, cap * rows)
    pos2, valid2 = pos2[:, 0], valid2[:, 0]
    n2 = recv.shape[0]
    slot2 = jnp.where(valid2 & (recv_eid < e_loc),
                      recv_eid * (cap * rows) + pos2, e_loc * cap * rows)
    table2 = jnp.full((e_loc * cap * rows + 1,), n2, jnp.int32)
    table2 = table2.at[slot2].set(jnp.arange(n2, dtype=jnp.int32),
                                  mode="drop")
    table2 = table2[: e_loc * cap * rows].reshape(e_loc, cap * rows)
    recv_pad = jnp.concatenate([recv, jnp.zeros((1, d), recv.dtype)])
    expert_in = recv_pad[table2]                              # [E_loc, C', D]

    out = _expert_ffn(expert_in, wg, wu, wd)  # F sliced over model ->
    out = jax.lax.psum(out, model_axis)       # partial sums of w_down

    # route outputs back to origin rows
    out_flat = jnp.concatenate(
        [out.reshape(e_loc * cap * rows, d),
         jnp.zeros((1, d), out.dtype)])
    back_slot = jnp.where(valid2 & (recv_eid < e_loc),
                          recv_eid * (cap * rows) + pos2,
                          e_loc * cap * rows)
    back = out_flat[back_slot]                                # [R*S, D]
    ret = jax.lax.all_to_all(back.reshape(rows, send_cap, d), data_axes,
                             split_axis=0, concat_axis=0,
                             tiled=True)                      # [R, S, D]

    # combine at origin: slot (t, k) sits at ret[dest, pos_r]
    flat_back = jnp.concatenate(
        [ret.reshape(rows * send_cap, d), jnp.zeros((1, d), ret.dtype)])
    slot_tk = jnp.where(valid_r, dest * send_cap + pos_r, rows * send_cap)
    per_k = flat_back[slot_tk]                                # [t, K, D]
    y = jnp.einsum("tkd,tk->td", per_k,
                   gate_vals.astype(per_k.dtype) *
                   valid_r.astype(per_k.dtype))
    aux = jax.lax.pmean(jax.lax.pmean(aux, data_axes), model_axis)
    return y, aux


# ---------------------------------------------------------------------------
# public entry: shape-polymorphic wrapper choosing mode + shard_map specs
# ---------------------------------------------------------------------------

def moe_ffn_ep(p, x, *, top_k: int, capacity_factor: float = 1.25,
               expert_axis: str = "model"):
    """Drop-in for moe.moe_ffn (same params pytree, same returns), running
    the sorted-dispatch expert-parallel path under the ambient mesh.  Falls
    back to a meshless local computation when no mesh context is active
    (CPU smoke tests): mathematically the single-device shard_map."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    mesh = current_mesh()

    if mesh is None or not mesh.axis_names:
        t = b * s
        cap = max(int(np.ceil(t * capacity_factor * top_k / e)), 1)
        y, aux = _moe_block_model_axis(
            x.reshape(t, d), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], top_k=top_k, cap=cap, n_experts=e,
            model_axis=None)  # type: ignore[arg-type]
        return y.reshape(b, s, d), aux

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_axis = "model" if "model" in mesh.axis_names else None
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    msize = mesh.shape.get("model", 1) if model_axis else 1

    t_loc = (b * s) // dsize if (b * s) % dsize == 0 else b * s
    cap = max(int(np.ceil(t_loc * capacity_factor * top_k / e)), 1)

    xt = x.reshape(b * s, d)
    batch_ok = (b * s) % dsize == 0

    if expert_axis == "model" and model_axis and e % msize == 0 and batch_ok:
        body = functools.partial(
            _moe_block_model_axis, top_k=top_k, cap=cap, n_experts=e,
            model_axis=model_axis)
        y, aux = _shmap(
            body, mesh,
            in_specs=(P(data_axes, None), P(None, None),
                      P(model_axis, None, None), P(model_axis, None, None),
                      P(model_axis, None, None)),
            out_specs=(P(data_axes, None), P()),
        )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        return y.reshape(b, s, d), aux

    if expert_axis == "data" and e % dsize == 0 and batch_ok:
        ffn_spec = model_axis if (model_axis and
                                  p["w_gate"].shape[-1] % msize == 0) \
            else None
        body = functools.partial(
            _moe_block_data_axis, top_k=top_k,
            cap=max(int(np.ceil(t_loc * capacity_factor * top_k / e)), 1),
            n_experts=e, data_axes=data_axes,
            model_axis=model_axis or ())
        y, aux = _shmap(
            body, mesh,
            in_specs=(P(data_axes, None), P(None, None),
                      P(data_axes, None, ffn_spec),
                      P(data_axes, None, ffn_spec),
                      P(data_axes, ffn_spec, None)),
            out_specs=(P(data_axes, None), P()),
        )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        return y.reshape(b, s, d), aux

    # layout not expressible on this mesh: einsum fallback
    from repro.layers.moe import moe_ffn
    return moe_ffn(p, x, top_k=top_k, capacity_factor=capacity_factor)
