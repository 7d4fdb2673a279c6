"""Pallas TPU kernel: Space-Control permission check (paper §4.2.3).

TPU-native rethinking of the paper's binary-search checker (DESIGN.md §7):
instead of log2(N) serialized DRAM probes per access (the CPU/CXL cost
structure), the sorted table shard sits in SMEM and the VPU evaluates the
range/permission predicate for an (8, 128) block of tagged addresses, one
entry per step of a scalar loop.  On-chip residency plays the role of the
paper's permission cache: the table is loaded from HBM once per call, not
per access.

Three search modes share one kernel (see `search`):

  mode="adaptive" (default) — batch-aware selection between the two fixed
    modes below.  The wrapper estimates the batch's candidate-tile density
    from the tile summary it already holds (`summary_candidate_tiles`) and
    passes the verdict into the kernel as a scalar operand: dense batches
    (uniform traces, where the hierarchical summary scan is pure overhead)
    run the flat scan, sparse batches (hot/locality traces) keep the
    two-level win.  One compiled kernel serves both; the branch is a
    per-grid-step ``lax.cond`` on the selector scalar.

  mode="hier" — two-level hierarchical search.  A precomputed per-tile
    summary (min-start / max-end per ENTRY_TILE consecutive entries, see
    ``repro.core.table.tile_summary``) is tested first, and the tile's
    ENTRY_TILE entries are folded only when some lane of the block falls
    in its window (``lax.cond``-skipped otherwise).  Work drops from O(N)
    to O(N/ENTRY_TILE + k·ENTRY_TILE) per block, where k is the number of
    candidate tiles — 1-2 for the locality-heavy access patterns the
    paper's 16 KiB cache exploits.

  mode="flat" — the brute-force O(B·N) scan: the baseline for
    benchmarks/kernels_bench.py, and the better choice when nearly every
    tile is a candidate anyway.

Layout:
  addresses  i32[B]   -> grid-blocked (ADDR_BLOCK,) tiles, viewed (8, 128)
  starts     i32[N]   -> whole-shard SMEM row (1, N)
  sizes/sizes_ok i32[N] -> diff-form spans (see `grant_sizes`): the range
    and permission tests each collapse to one unsigned compare against
    ``(page - start) as u32``, with a denied entry carrying a zero window
  tile_min/max i32[n_tiles] -> SMEM rows, the summary
  outputs    allowed u32[B] (0/1), idx i32[B]

N is the *per-shard* entry count.  The ceiling is MAX_ENTRIES = 65536: the
three entry rows take 768 KiB of the chip's 1 MiB of SMEM.  The global
table is range-partitioned across the "model" mesh axis (see
repro.launch.sharding), mirroring the paper's table-in-SDM with per-host
checkers.
"""
from __future__ import annotations

import functools
from typing import Callable, Hashable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.table import (HWPID_SHIFT, PAGE_MASK, SUMMARY_TILE,
                              summary_candidate_tiles, tenant_permbits,
                              tile_summary)
from repro.kernels import bucket_pad, compiler_params, resolve_interpret

ADDR_BLOCK = 1024          # addresses per grid step = (8, 128) lanes
ENTRY_TILE = 1024          # table entries folded per inner loop step
MAX_ENTRIES = 65536        # per-shard ceiling (64 K entries, 768 KiB SMEM)
UNROLL = 8                 # entries folded per scalar-loop iteration

# Adaptive selector decision rule: the hierarchical kernel evaluates
# candidate tiles plus a summary pass + per-tile dispatch overhead, so it
# only wins while the mean candidate-tile count per kernel step stays below
# ~3/4 of the shard's tiles.  (Measured crossover: hot traces sit at
# 0.2-0.75 density and hier wins 1.1-4.4x; uniform traces sit at ~1.0 where
# hier is 8-19% slower than flat.)
HIER_DENSITY_NUM = 3
HIER_DENSITY_DEN = 4

assert ENTRY_TILE == SUMMARY_TILE, "kernel tile must match table summary tile"


# ---------------------------------------------------------------------------
# Epoch-stamped shard views
# ---------------------------------------------------------------------------
# The kernel operands (padded entry arrays + tile summary + per-tenant
# permbits) are derived data: rebuilding them on every call costs host-side
# dispatch work that dwarfs the kernel itself for small batches.  A
# `ShardView` snapshots them together with the table epoch they were derived
# at; `ShardViewCache` memoizes views per tenant and re-resolves whenever the
# FM commits a new epoch — the kernel-layer leg of the BISnp story: a
# stale-epoch batch never runs against stale operands, it rebuilds them.

class ShardView(NamedTuple):
    """Padded, summary-annotated table shard for one tenant at one epoch."""
    starts: jax.Array     # i32[padded_n], tail = INT32_MAX sentinels
    ends: jax.Array       # i32[padded_n]
    permbits: jax.Array   # u32[padded_n] 2-bit field for the view's tenant
    tile_min: jax.Array   # i32[n_tiles]
    tile_max: jax.Array   # i32[n_tiles]
    epoch: jax.Array | int = 0

    @property
    def n_tiles(self) -> int:
        return self.tile_min.shape[0]


def make_shard_view(starts, ends, permbits, *, epoch: int = 0) -> ShardView:
    """Pad a raw shard and precompute its tile summary, stamped with the
    table epoch the arrays were read at."""
    s, e, pb, np_ = _pad_shard(starts, ends, permbits)
    tmin, tmax = tile_summary(s, e, tile=ENTRY_TILE, n_tiles=np_ // ENTRY_TILE)
    return ShardView(s, e, pb, tmin, tmax, epoch)


def table_shard_view(table, hwpid: int, *,
                     cache: "ShardViewCache | None" = None) -> ShardView:
    """ShardView of a device `PermissionTable` for one tenant; with a
    `ShardViewCache` the padded arrays and summary are reused until the
    table's epoch moves."""
    epoch = int(table.epoch)

    def build() -> ShardView:
        return make_shard_view(table.starts, table.starts + table.sizes,
                               tenant_permbits(table, hwpid), epoch=epoch)

    if cache is None:
        return build()
    return cache.get(hwpid, epoch, build)


class ShardViewCache:
    """Epoch-keyed host-side memo: one ShardView per key (typically the
    tenant HWPID).  `get` returns the cached view while the epoch matches
    and transparently re-resolves after an FM commit bumps it — counters
    expose how much derivation work churn actually caused."""

    def __init__(self):
        self._views: dict[Hashable, ShardView] = {}
        self.rebuilds = 0
        self.reuses = 0

    def get(self, key: Hashable, epoch: int,
            build: Callable[[], ShardView]) -> ShardView:
        view = self._views.get(key)
        if view is not None and int(view.epoch) == int(epoch):
            self.reuses += 1
            return view
        view = build()
        self._views[key] = view
        self.rebuilds += 1
        return view

    def drop(self, key: Hashable) -> None:
        self._views.pop(key, None)


def grant_sizes(starts, ends, permbits, needv):
    """Per-entry diff-form operands: ``sizes[k] = ends[k] - starts[k]`` and
    ``sizes_ok[k]`` = the same span if entry k grants ``needv``, else 0.
    With these, the range test collapses to one unsigned compare per
    entry — ``(page - start) as u32 < size`` — because a page below the
    start wraps to a huge unsigned value and a denied entry has a zero
    window.  O(N) work, done once per wrapper trace, off the B x N path.
    Returned as i32 (SMEM holds 32-bit scalars; a span of 24-bit pages
    fits), compared unsigned in the kernel."""
    sizes = jnp.asarray(ends, jnp.int32) - jnp.asarray(starts, jnp.int32)
    permbits = jnp.asarray(permbits, jnp.uint32)
    sizes_ok = jnp.where((permbits & needv) == needv, sizes, jnp.int32(0))
    return sizes, sizes_ok


# ---------------------------------------------------------------------------
# In-kernel search (shared by permcheck, the fused egress kernel and the
# fabric-batched kernel)
# ---------------------------------------------------------------------------
# The entry arrays and the tile summary sit in SMEM as (1, N) rows and are
# read one scalar per entry: each entry costs a few VPU ops on the (R, 128)
# page block, and no intermediate grows with the entry count.  (A vectorized
# (R, 128, ENTRY_TILE) predicate needs 64·128·1024·4 B = 32 MiB of VMEM for a
# 64-row block, and its lane reductions do not lower.)  Loop carries are
# i32, not bool: Mosaic cannot carry i1 vectors through a loop.

def _fold_entries(page, ent, k0, n: int, carry):
    """Fold table entries [k0, k0 + n) into the per-lane search state.
    ``ent`` = (starts, sizes, sizes_ok) SMEM refs (see `grant_sizes`);
    ``carry`` = (ok, idx) i32: ok is 1 where some entry grants, idx the
    first entry covering the page (-1 if none).  ``n`` is a multiple of
    UNROLL."""
    s_ref, sz_ref, szok_ref = ent

    def one(k, c):
        ok, idx = c
        diff = (page - s_ref[0, k]).astype(jnp.uint32)
        in_r = diff < sz_ref[0, k].astype(jnp.uint32)
        ok = ok | (diff < szok_ref[0, k].astype(jnp.uint32)).astype(jnp.int32)
        return ok, jnp.where(in_r & (idx < 0), k, idx)

    def body(u, c):
        for r in range(UNROLL):
            c = one(k0 + u * UNROLL + r, c)
        return c

    return jax.lax.fori_loop(0, n // UNROLL, body, carry)


def search(page, ent, summary, n_tiles: int, use_hier):
    """Range/permission lookup of an (R, 128) page block.

    flat: fold every entry.  hier: test each tile's [min start, max end)
    summary window against the block and fold only the tiles some lane
    falls in (sorted, non-overlapping entries make the windows disjoint,
    so a superset of tiles is extra work, never a wrong answer).
    ``use_hier`` is a Python bool (fixed mode) or a traced bool scalar (the
    adaptive selector).  Returns (any_ok bool(R,128), idx i32(R,128))."""
    init = (jnp.zeros(page.shape, jnp.int32),
            jnp.full(page.shape, -1, jnp.int32))

    def flat(c):
        return _fold_entries(page, ent, 0, n_tiles * ENTRY_TILE, c)

    def hier(c):
        tmin_ref, tmax_ref = summary

        def tile(t, c):
            cand = (page >= tmin_ref[0, t]) & (page < tmax_ref[0, t])
            return jax.lax.cond(
                jnp.max(cand.astype(jnp.int32)) > 0,
                lambda c: _fold_entries(page, ent, t * ENTRY_TILE,
                                        ENTRY_TILE, c),
                lambda c: c, c)

        return jax.lax.fori_loop(0, n_tiles, tile, c)

    if n_tiles <= 1 or use_hier is False:  # one tile: nothing to skip
        ok, idx = flat(init)
    elif use_hier is True:
        ok, idx = hier(init)
    else:
        ok, idx = jax.lax.cond(use_hier, hier, flat, init)
    return ok > 0, idx


def _permcheck_kernel(addr_ref, sel_ref, starts_ref, sizes_ref, sizes_ok_ref,
                      tmin_ref, tmax_ref, allowed_ref, idx_ref, *,
                      hwpid: int, n_tiles: int, mode: str):
    """One ADDR_BLOCK of addresses; ``mode="adaptive"`` reads the selector
    `sel_ref[0, 0]` (computed by the wrapper from the tile summary), so one
    compiled kernel covers every trace class."""
    ext = addr_ref[...].reshape(8, 128)
    tag = ext >> HWPID_SHIFT
    page = ext & PAGE_MASK
    use_hier = sel_ref[0, 0] > 0 if mode == "adaptive" else mode == "hier"
    any_hit, idx = search(page, (starts_ref, sizes_ref, sizes_ok_ref),
                          (tmin_ref, tmax_ref), n_tiles, use_hier)
    allowed = (tag == jnp.int32(hwpid)) & any_hit
    allowed_ref[...] = allowed.astype(jnp.uint32).reshape(allowed_ref.shape)
    idx_ref[...] = idx.reshape(idx_ref.shape)


def hier_profitable(ext_addrs, tile_min, tile_max, *,
                    block: int = ADDR_BLOCK):
    """Adaptive selector decision (traced bool scalar): run the
    hierarchical search iff the batch's mean candidate-tile density per
    ``block``-lane kernel step stays below HIER_DENSITY (3/4) of the
    shard's tiles.  Uses only the tile summary the hier kernel needs
    anyway; single-tile shards always pick flat (nothing to skip).
    ``ext_addrs`` must already be padded to a multiple of ``block``."""
    n_tiles = tile_min.shape[0]
    if n_tiles <= 1:
        return jnp.asarray(False)
    pages = jnp.asarray(ext_addrs, jnp.int32) & PAGE_MASK
    needed = summary_candidate_tiles(pages, tile_min, tile_max, block=block)
    n_steps = needed.shape[0]
    return (HIER_DENSITY_DEN * jnp.sum(needed)
            <= HIER_DENSITY_NUM * n_steps * n_tiles)


def selected_mode(ext_addrs, view: ShardView, *,
                  block: int = ADDR_BLOCK) -> str:
    """Host-side readout of the adaptive decision for a batch (concretizes
    the selector; benchmarks record it next to the timings so selector
    regressions are visible in the JSON)."""
    b = jnp.asarray(ext_addrs, jnp.int32).reshape(-1)
    bp = bucket_pad(b.shape[0], block)
    ext = jnp.full((bp,), -1, jnp.int32).at[:b.shape[0]].set(b)
    return "hier" if bool(hier_profitable(
        ext, view.tile_min, view.tile_max, block=block)) else "flat"


def smem_rows(*arrays):
    """Entry and summary arrays as the (1, N) i32 rows the kernels read
    from SMEM."""
    return tuple(jnp.asarray(a, jnp.int32).reshape(1, -1) for a in arrays)


def _pad_shard(starts, ends, permbits):
    """Pad a table shard to a power-of-two multiple of ENTRY_TILE with
    never-matching sentinels; returns (s, e, pb, padded_n)."""
    n = starts.shape[0]
    np_ = bucket_pad(n, ENTRY_TILE)
    if np_ > MAX_ENTRIES:
        raise ValueError(
            f"table shard has {n} entries > MAX_ENTRIES={MAX_ENTRIES}; "
            "range-partition the table across the model axis")
    smax = jnp.int32(np.iinfo(np.int32).max)
    s = jnp.full((np_,), smax, jnp.int32).at[:n].set(
        jnp.asarray(starts, jnp.int32))
    e = jnp.full((np_,), smax, jnp.int32).at[:n].set(
        jnp.asarray(ends, jnp.int32))
    pb = jnp.zeros((np_,), jnp.uint32).at[:n].set(
        jnp.asarray(permbits, jnp.uint32))
    return s, e, pb, np_


@functools.partial(jax.jit,
                   static_argnames=("hwpid", "need", "interpret", "mode"))
def permcheck_view_pallas(ext_addrs, view: ShardView, *, hwpid: int,
                          need: int, interpret: bool | None = None,
                          mode: str = "adaptive"):
    """Blocked Pallas permission check over a prepared `ShardView`.

    The view's entry arrays are already padded and summarized (see
    `make_shard_view` / `table_shard_view`), so repeated batches at one
    epoch skip all operand derivation.  Pads B to a power-of-two multiple
    of ADDR_BLOCK (bucketed -> varying batch sizes reuse jit caches).
    ``mode="adaptive"`` (default) lets `hier_profitable` pick the search
    per call; "hier"/"flat" force a fixed kernel (oracles for the property
    tests, baselines for the benches).  ``interpret=None`` auto-selects:
    compiled on TPU, interpreter elsewhere.
    """
    if mode not in ("adaptive", "hier", "flat"):
        raise ValueError(f"unknown permcheck mode {mode!r}")
    interpret = resolve_interpret(interpret)
    b = ext_addrs.shape[0]
    bp = bucket_pad(b, ADDR_BLOCK)
    ext = jnp.full((bp,), -1, jnp.int32).at[:b].set(
        jnp.asarray(ext_addrs, jnp.int32))
    sz, szok = grant_sizes(view.starts, view.ends, view.permbits,
                           jnp.uint32(need))
    sel = (hier_profitable(ext, view.tile_min, view.tile_max)
           if mode == "adaptive" else jnp.asarray(False))
    block = pl.BlockSpec((ADDR_BLOCK,), lambda i: (i,))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_permcheck_kernel, hwpid=hwpid,
                               n_tiles=view.n_tiles, mode=mode)
    allowed, idx = pl.pallas_call(
        kernel,
        grid=(bp // ADDR_BLOCK,),
        in_specs=[block] + [smem] * 6,
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), jnp.uint32),
            jax.ShapeDtypeStruct((bp,), jnp.int32),
        ],
        interpret=interpret,
        # each ADDR_BLOCK of addresses is checked independently against the
        # (replicated) entry arrays — the grid is embarrassingly parallel
        **compiler_params(interpret, "parallel"),
    )(ext, sel.astype(jnp.int32).reshape(1, 1),
      *smem_rows(view.starts, sz, szok, view.tile_min, view.tile_max))
    return allowed[:b].astype(bool), idx[:b]


@functools.partial(jax.jit,
                   static_argnames=("hwpid", "need", "interpret", "mode"))
def permcheck_pallas(ext_addrs, starts, ends, permbits, *, hwpid: int,
                     need: int, interpret: bool | None = None,
                     mode: str = "adaptive"):
    """Raw-array convenience wrapper: derives a ShardView per call (padding
    entries use INT32_MAX sentinels that never match) and runs
    `permcheck_view_pallas`.  Jitted so the derivation traces into the
    call's graph (no eager per-call dispatch); epoch-aware callers should
    still hold a `ShardViewCache` and use the view entry point, which
    skips the derivation entirely across batches."""
    return permcheck_view_pallas(
        ext_addrs, make_shard_view(starts, ends, permbits),
        hwpid=hwpid, need=need, interpret=interpret, mode=mode)
