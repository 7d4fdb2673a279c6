"""Pallas kernel: fabric-wide batched egress (check ⊕ decrypt, R rows).

The single-host fused kernel (`checked_memcrypt_view_pallas`) launches once
per host per step — at the paper's 255-host deployment that is 255 dispatches
of identical structure.  This kernel batches the whole fabric step into ONE
``pallas_call`` over a 2-D grid ``(row, super_block)``, where a **row is one
(host, tenant) pair**: a host serving T co-resident tenants contributes T
consecutive rows that repeat its shard arrays with per-tenant permbits
(`repro.core.fabric.ShardedFabric.fabric_rows` defines the ordering):

  * each row carries one host's resident table shard (see
    `repro.core.fabric.HostRuntime`) in the stacked ``[R, N]`` entry
    arrays, so grid step ``(h, j)`` has row ``h``'s shard in SMEM and
    evaluates the same adaptive search as the single-host kernel
    (`memcrypt.checked_release` is shared code);
  * the tenant HWPID is a *dynamic* per-row operand (``hwpids[h]``, scalar
    prefetch) rather than the single-host kernel's static argument — one
    compiled kernel serves every (host, tenant) pair in the fleet, and
    admitting a tenant with a fresh HWPID does not recompile;
  * rows are fully independent: revoking one tenant re-derives only that
    tenant's permbits rows, and its lanes zero out while a co-resident
    tenant's rows — same host, same shard arrays — are untouched (pinned
    bit-exactly by the multi-tenant oracle test in tests/test_fabric.py);
  * flat-vs-hier selection is *per row*: the wrapper scores every row's
    batch against that row's shard summary (`summary_candidate_tiles`
    vectorized over rows) and ships a ``use_hier i32[R]`` scalar-prefetch
    operand — a host serving uniform traffic runs the flat scan while its
    neighbor with a hot working set keeps the two-level win, in the same
    launch;
  * each grid step streams SUPER_BLOCKS x BLOCK words (double-buffered
    across steps on TPU via ``dimension_semantics``), and the keystream
    counter stays the flat word position ``h * padded_B + j * sb + lane`` —
    exactly the single-host kernel at ``base_word = h * padded_B`` — pinned
    by the differential test in tests/test_fabric.py.

Per-row semantics match ``kernels.ref.checked_memcrypt`` for that row's
shard/hwpid bit-exactly: denied lanes read zero and carry a FAULT_* code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.table import PAGE_MASK
from repro.kernels import bucket_pad, compiler_params, resolve_interpret
from repro.kernels.memcrypt import BLOCK, SUPER_BLOCKS, checked_release
from repro.kernels.permcheck import (HIER_DENSITY_DEN, HIER_DENSITY_NUM,
                                     grant_sizes)


def _fabric_egress_kernel(hwpid_ref, sel_ref, data_ref, addr_ref, starts_ref,
                          sizes_ref, sizes_ok_ref, tmin_ref, tmax_ref,
                          out_ref, fault_ref, *, key0: int, key1: int,
                          n_tiles: int, n_steps: int):
    h = pl.program_id(0)
    j = pl.program_id(1)
    out, fault = checked_release(
        data_ref[...], addr_ref[...],
        hwpid_ref[h],                          # dynamic per-row tenant tag
        (starts_ref, sizes_ref, sizes_ok_ref), (tmin_ref, tmax_ref), n_tiles,
        sel_ref[h] > 0,                        # per-row adaptive selection
        h * n_steps + j, key0=key0, key1=key1, base_word=0)
    out_ref[...] = out
    fault_ref[...] = fault


def _per_host_use_hier(pages, tmin, tmax, *, block: int):
    """Vectorized per-host selector: ``use_hier[h]`` iff host h's batch
    keeps its candidate-tile density below HIER_DENSITY of that host's
    shard tiles (the row-wise form of `permcheck.hier_profitable`).
    ``pages`` i32[H, Bp] (padded), summaries i32[H, T]."""
    n_tiles = tmin.shape[1]
    if n_tiles <= 1:
        return jnp.zeros((pages.shape[0],), jnp.int32)
    cand = (pages[:, :, None] >= tmin[:, None, :]) & \
        (pages[:, :, None] < tmax[:, None, :])          # (H, Bp, T)
    n_steps = pages.shape[1] // block
    needed = cand.reshape(pages.shape[0], n_steps, block, n_tiles) \
        .any(axis=2).sum(axis=(1, 2))                   # i32[H]
    use = HIER_DENSITY_DEN * needed <= HIER_DENSITY_NUM * n_steps * n_tiles
    return use.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("need", "key0", "key1",
                                             "interpret"))
def _fabric_egress_impl(data, ext, hwpids, starts, ends, permbits, tmin,
                        tmax, *, need: int, key0: int, key1: int,
                        interpret: bool | None):
    interpret = resolve_interpret(interpret)
    h, b = data.shape
    bp = bucket_pad(b, BLOCK)
    sb = min(SUPER_BLOCKS, bp // BLOCK) * BLOCK   # both are powers of two
    n_steps = bp // sb
    rows = sb // 128
    buf = jnp.zeros((h, bp), jnp.uint32).at[:, :b].set(
        jnp.asarray(data, jnp.uint32))
    # -1 padding: tag 0 -> denied (FAULT_NO_ABITS), zero output word
    extp = jnp.full((h, bp), -1, jnp.int32).at[:, :b].set(
        jnp.asarray(ext, jnp.int32))
    np_ = starts.shape[1]
    n_tiles = tmin.shape[1]
    sizes, sizes_ok = grant_sizes(starts, ends, permbits, jnp.uint32(need))
    sel = _per_host_use_hier(extp & PAGE_MASK, tmin, tmax, block=sb)

    # words as (rows, 128) tiles, the row dimension squeezed out of the
    # block; each row's shard arrays as (1, N) SMEM rows, single-buffered
    # (they change only when the grid moves to the next row)
    words = pl.BlockSpec((None, rows, 128), lambda i, j, *_: (i, j, 0))

    def shard_row(n):
        return pl.BlockSpec((None, 1, n), lambda i, j, *_: (i, 0, 0),
                            memory_space=pltpu.SMEM,
                            pipeline_mode=pl.Buffered(1))

    kernel = functools.partial(
        _fabric_egress_kernel, key0=int(key0), key1=int(key1),
        n_tiles=n_tiles, n_steps=n_steps)
    out, fault = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,             # hwpids, sel
            grid=(h, n_steps),
            in_specs=[words, words] + [shard_row(np_)] * 3
            + [shard_row(n_tiles)] * 2,
            out_specs=[words, words],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, bp // 128, 128), jnp.uint32),
            jax.ShapeDtypeStruct((h, bp // 128, 128), jnp.int32),
        ],
        interpret=interpret,
        **compiler_params(interpret, "parallel", "parallel"),
    )(jnp.asarray(hwpids, jnp.int32), sel,
      buf.reshape(h, bp // 128, 128), extp.reshape(h, bp // 128, 128),
      *(jnp.asarray(a, jnp.int32)[:, None, :]
        for a in (starts, sizes, sizes_ok, tmin, tmax)))
    out = out.reshape(h, bp)
    fault = fault.reshape(h, bp)
    return out[:, :b], fault[:, :b]


def fabric_egress_pallas(data, ext_addrs, view, *, need: int,
                         key0: int, key1: int,
                         interpret: bool | None = None):
    """Batched multi-host fused egress over a `repro.core.fabric.FabricView`.

    ``data`` u32[R, B] / ``ext_addrs`` i32[R, B]: row ``i`` is the step
    batch of tenant ``view.hwpids[i]`` on host ``view.host_ids[i]``, checked
    against that host's resident shard (flat or hierarchical search chosen
    per row from that row's shard summary) and decrypted with the keystream
    at flat position ``i * padded_B + lane``.  A multi-tenant host owns
    several consecutive rows (see `ShardedFabric.fabric_rows`).  Returns
    ``(out u32[R, B], fault i32[R, B])``.
    """
    data = jnp.asarray(data, jnp.uint32)
    ext = jnp.asarray(ext_addrs, jnp.int32)
    if data.ndim != 2 or ext.shape != data.shape:
        raise ValueError(
            f"expected matching [R, B] operands, got data {data.shape} / "
            f"ext {ext.shape}")
    if data.shape[0] != view.starts.shape[0]:
        raise ValueError(
            f"{data.shape[0]} batch rows vs {view.starts.shape[0]} fabric "
            "view (host, tenant) rows")
    return _fabric_egress_impl(
        data, ext, view.hwpids, view.starts, view.ends, view.permbits,
        view.tile_min, view.tile_max, need=need, key0=key0, key1=key1,
        interpret=interpret)
