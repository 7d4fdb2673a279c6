"""Pallas TPU kernel: flash attention (forward) — §Perf H5's real fix.

The XLA chunked path (layers/attention.chunked_attention) bounds TEMP
memory but still spills every [BQ, BK] logits tile to HBM at fusion
boundaries; only an on-chip kernel keeps the tiles in VMEM.  This kernel
implements the standard flash schedule:

  grid = (B, H, Sq/BLOCK_Q, Sk/BLOCK_K)   (K innermost, sequential on TPU)
  scratch (VMEM, persists across the K dimension of the grid):
      acc [BLOCK_Q, dh] f32, m [BLOCK_Q] , l [BLOCK_Q]
  per step: logits tile = q_tile @ k_tile^T on the MXU, online-softmax
  rescale, acc += p @ v_tile; the output block is written once at the last
  K step.  GQA is folded in the BlockSpec index_map (kv block = h // g) —
  no materialized head repeat.

HBM traffic per (b, h): Sq*dh (q) + Sk*dh*(Sq/BQ) (k/v re-reads) + Sq*dh
(out) — vs Sq*Sk logits for the materialized path.  VMEM per step:
(2*BQ*dh + 2*BK*dh + BQ*BK) * 4 B ≈ 0.4 MiB at BQ=BK=128, dh=128.

Backward falls back to jax.custom_vjp over the oracle recompute (standard
flash bwd is a follow-up; training uses the XLA path).  Validated
bit-tolerance against ref.flash_attention in interpret mode
(tests/test_kernels_flash.py) across shape/dtype/GQA/window sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, resolve_interpret

NEG_INF = -0.7 * float(np.finfo(np.float32).max)

BLOCK_Q = 128
BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, sq: int, sk: int,
                  block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, dh]
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, dh]
    v = v_ref[0, 0].astype(jnp.float32)            # [BK, dh]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [BQ, BK]

    # query absolute position: queries align with the END of the keys
    # (offset = sk - sq), matching the ref oracle / decode convention
    q_pos = qi * block_q + (sk - sq) + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])
    l_ref[...] = l_prev * alpha + p.sum(axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = -1,
                           block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                           interpret: bool | None = None):
    """q: [B, H, Sq, dh]; k/v: [B, Hkv, Sk, dh] (GQA folded via index_map).
    Returns [B, H, Sq, dh] in q.dtype."""
    interpret = resolve_interpret(interpret)
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / np.sqrt(dh)

    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    n_k = sk_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        sq=sq, sk=sk, block_q=block_q, block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq_p // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, i, j, g_=g: (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, i, j, g_=g: (b_, h_ // g_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),      # l (running sum)
        ],
        interpret=interpret,
        # K is innermost and sequential (scratch accumulates across it);
        # batch/head/Q-block steps are independent, so Mosaic may double-
        # buffer and reorder them.
        **compiler_params(interpret, "parallel", "parallel", "parallel",
                          "arbitrary"),
    )(q, k, v)
    return out[:, :, :sq]
