"""Pallas TPU kernels: counter-mode ARX memory-line cipher (paper §4.2.3).

Models the paper's memory encryption engine (<=1 cycle per 64 B cache line):
each 32-bit word at flat index i is XORed with a keystream generated by a
threefry-style 2x32 ARX block over (line = i//16, word = i%16).  Purely
elementwise on (8, 128) u32 tiles -> VPU-only, no MXU.  Encrypt == decrypt.

Two kernels:

  ``memcrypt_pallas``          — plain keystream XOR over a u32 buffer.
  ``checked_memcrypt_pallas``  — the fused egress path: tag check +
    adaptive range/permission lookup (the same flat/hier selector as
    ``permcheck_pallas``) + keystream XOR in a SINGLE ``pallas_call``.  The
    unfused path launches permcheck and memcrypt separately, paying two HBM
    round-trips over the same words; the fused kernel reads each word once,
    zeroes denied lanes, and emits per-word fault codes — the software
    analogue of the paper's checker and encryption engine sitting back to
    back at the memory egress point.

The fused kernel streams SUPER_BLOCKS x BLOCK words per grid step instead
of one BLOCK: the summary pass, candidate-tile walk, and per-step dispatch
are amortized over 8x the lanes, and on TPU the multi-block grid is marked
``dimension_semantics=("parallel",)`` so Mosaic double-buffers the operand
stream — block j+1's HBM->VMEM copy overlaps block j's check+keystream
compute.  The check is `permcheck.search`, shared with the permcheck and
fabric kernels; `checked_release` turns its verdict into fault codes and
the released words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.checker import (
    FAULT_NO_ABITS,
    FAULT_NO_ENTRY,
    FAULT_NONE,
    FAULT_NOT_LOCAL,
    FAULT_PERM,
)
from repro.core.crypto import arx_mac32
from repro.core.table import HWPID_SHIFT, PAGE_MASK
from repro.kernels import bucket_pad, compiler_params, resolve_interpret
from repro.kernels.permcheck import (ShardView, grant_sizes, hier_profitable,
                                     make_shard_view, search, smem_rows)

BLOCK = 1024        # u32 words per grid step, viewed (8, 128)
SUPER_BLOCKS = 8    # BLOCK-tiles streamed per fused-egress grid step


def _keystream(i, base_word: int, rows: int = 8):
    """Per-lane keystream counter words (line, word) for grid step i over
    an (rows, 128) tile; the flat word position is
    ``base_word + i * rows * 128 + lane``."""
    lane = jax.lax.broadcasted_iota(jnp.uint32, (rows, 128), 0) * 128 + \
        jax.lax.broadcasted_iota(jnp.uint32, (rows, 128), 1)
    idx = lane + jnp.uint32(base_word) + \
        i.astype(jnp.uint32) * jnp.uint32(rows * 128)
    return idx // jnp.uint32(16), idx % jnp.uint32(16)


def _memcrypt_kernel(data_ref, out_ref, *, key0: int, key1: int,
                     base_word: int):
    i = pl.program_id(0)
    d = data_ref[...].reshape(8, 128)
    line, word = _keystream(i, base_word)
    ks0, _ = arx_mac32(jnp.uint32(key0), jnp.uint32(key1), line, word)
    out_ref[...] = (d ^ ks0).reshape(out_ref.shape)


@functools.partial(jax.jit,
                   static_argnames=("key0", "key1", "base_word", "interpret"))
def memcrypt_pallas(data, *, key0: int, key1: int, base_word: int = 0,
                    interpret: bool | None = None):
    """XOR-keystream encrypt/decrypt of a u32 buffer of any shape.

    Pads to a power-of-two number of BLOCK tiles (jit-cache friendly);
    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    d = jnp.asarray(data, jnp.uint32)
    flat = d.reshape(-1)
    n = flat.shape[0]
    npad = bucket_pad(n, BLOCK)
    buf = jnp.zeros((npad,), jnp.uint32).at[:n].set(flat)
    kernel = functools.partial(_memcrypt_kernel, key0=int(key0),
                               key1=int(key1), base_word=int(base_word))
    out = pl.pallas_call(
        kernel,
        grid=(npad // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.uint32),
        interpret=interpret,
        # every BLOCK of the keystream is independent — let Mosaic double-
        # buffer the stream
        **compiler_params(interpret, "parallel"),
    )(buf)
    return out[:n].reshape(d.shape)


# ---------------------------------------------------------------------------
# Fused permission check ⊕ memcrypt egress kernel
# ---------------------------------------------------------------------------

def checked_release(d, ext, hwpid, ent, summary, n_tiles: int, use_hier,
                    step, *, key0: int, key1: int, base_word: int):
    """Check ⊕ decrypt of one (rows, 128) block, shared by the single-host
    and the fabric-batched kernels: `search` for the verdict, FAULT_* codes
    on denied lanes, and the keystream XOR for grid step ``step``.
    Response-side enforcement: denied lanes read as zero.  Returns
    (out u32, fault i32)."""
    tag = ext >> HWPID_SHIFT
    page = ext & PAGE_MASK
    tag_ok = tag == hwpid
    any_ok, idx = search(page, ent, summary, n_tiles, use_hier)
    allowed = tag_ok & any_ok
    fault = jnp.where(
        allowed, FAULT_NONE,
        jnp.where(tag <= 0, FAULT_NO_ABITS,
                  jnp.where(~tag_ok, FAULT_NOT_LOCAL,
                            jnp.where(idx < 0, FAULT_NO_ENTRY, FAULT_PERM))))
    line, word = _keystream(step, base_word, d.shape[0])
    ks0, _ = arx_mac32(jnp.uint32(key0), jnp.uint32(key1), line, word)
    return jnp.where(allowed, d ^ ks0, jnp.uint32(0)), fault.astype(jnp.int32)


def _checked_memcrypt_kernel(data_ref, addr_ref, sel_ref, starts_ref,
                             sizes_ref, sizes_ok_ref, tmin_ref, tmax_ref,
                             out_ref, fault_ref, *, hwpid: int,
                             key0: int, key1: int, base_word: int,
                             n_tiles: int, rows: int):
    out, fault = checked_release(
        data_ref[...].reshape(rows, 128), addr_ref[...].reshape(rows, 128),
        jnp.int32(hwpid), (starts_ref, sizes_ref, sizes_ok_ref),
        (tmin_ref, tmax_ref), n_tiles, sel_ref[0, 0] > 0, pl.program_id(0),
        key0=key0, key1=key1, base_word=base_word)
    out_ref[...] = out.reshape(out_ref.shape)
    fault_ref[...] = fault.reshape(fault_ref.shape)


@functools.partial(jax.jit,
                   static_argnames=("hwpid", "need", "key0", "key1",
                                    "base_word", "interpret"))
def checked_memcrypt_view_pallas(data, ext_addrs, view: ShardView, *,
                                 hwpid: int, need: int, key0: int, key1: int,
                                 base_word: int = 0,
                                 interpret: bool | None = None):
    """Fused egress over a prepared `ShardView`: permission check + decrypt
    in one ``pallas_call``.

    ``data[i]`` is the ciphertext u32 word whose page-tagged address is
    ``ext_addrs[i]``; the word is decrypted with the counter keystream at
    flat position ``base_word + i`` and released only if the two-level
    permission lookup grants ``need`` to ``hwpid``.  Returns
    ``(out u32[B], fault i32[B])`` with ``out`` zeroed and a
    ``repro.core.checker.FAULT_*`` code on every denied lane.

    Matches ``kernels.ref.checked_memcrypt`` (the composition of the
    permcheck and memcrypt oracles) bit-exactly.  Epoch-aware callers hold
    the view in a `ShardViewCache` so a table commit re-resolves the
    operands exactly once per epoch, not per batch.

    Each grid step streams up to SUPER_BLOCKS x BLOCK words (double-buffered
    across steps on TPU via ``dimension_semantics``); the flat/hier search
    is picked per call by the same selector as ``permcheck_pallas``.
    """
    interpret = resolve_interpret(interpret)
    d = jnp.asarray(data, jnp.uint32).reshape(-1)
    b = d.shape[0]
    if ext_addrs.shape != (b,):
        raise ValueError(
            f"ext_addrs shape {ext_addrs.shape} != data words ({b},)")
    bp = bucket_pad(b, BLOCK)
    sb = min(SUPER_BLOCKS, bp // BLOCK) * BLOCK   # both are powers of two
    rows = sb // 128
    buf = jnp.zeros((bp,), jnp.uint32).at[:b].set(d)
    ext = jnp.full((bp,), -1, jnp.int32).at[:b].set(
        jnp.asarray(ext_addrs, jnp.int32))
    sz, szok = grant_sizes(view.starts, view.ends, view.permbits,
                           jnp.uint32(need))
    sel = hier_profitable(ext, view.tile_min, view.tile_max, block=sb)
    block = pl.BlockSpec((sb,), lambda i: (i,))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _checked_memcrypt_kernel, hwpid=hwpid, key0=int(key0),
        key1=int(key1), base_word=int(base_word), n_tiles=view.n_tiles,
        rows=rows)
    out, fault = pl.pallas_call(
        kernel,
        grid=(bp // sb,),
        in_specs=[block, block] + [smem] * 6,
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), jnp.uint32),
            jax.ShapeDtypeStruct((bp,), jnp.int32),
        ],
        interpret=interpret,
        **compiler_params(interpret, "parallel"),
    )(buf, ext, sel.astype(jnp.int32).reshape(1, 1),
      *smem_rows(view.starts, sz, szok, view.tile_min, view.tile_max))
    return out[:b], fault[:b]


@functools.partial(jax.jit,
                   static_argnames=("hwpid", "need", "key0", "key1",
                                    "base_word", "interpret"))
def checked_memcrypt_pallas(data, ext_addrs, starts, ends, permbits, *,
                            hwpid: int, need: int, key0: int, key1: int,
                            base_word: int = 0,
                            interpret: bool | None = None):
    """Raw-array convenience wrapper around `checked_memcrypt_view_pallas`
    (derives the padded ShardView per call; jitted so the derivation
    traces into the call's graph)."""
    return checked_memcrypt_view_pallas(
        data, ext_addrs, make_shard_view(starts, ends, permbits),
        hwpid=hwpid, need=need, key0=key0, key1=key1, base_word=base_word,
        interpret=interpret)
