"""Permission checker (paper §4.2.3).

On-chip unit placed after the LLC.  Every LD/ST of a trusted process carries
A-bits (HWPID) tagged into the extended physical address.  The checker:

  1. verifies the A-bits against HWPID_local (per-host trusted bit-vector),
  2. binary-searches the sorted permission table for the address's entry,
  3. extracts the 2-bit permission for (HWPID) and enforces R/W,
  4. raises a fault code on violation (paper: interrupt on access violation).

The jnp implementation below is the framework's *functional* checker (used by
checked_gather and the property tests); the Pallas kernel in
``repro.kernels.permcheck`` is the TPU hot-path implementation of step 2-3 and
is validated against ``repro.kernels.ref``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .table import (
    EMPTY_START,
    PermissionTable,
    extract_perm,
    unpack_ext_addr,
)

# Fault codes
FAULT_NONE = 0
FAULT_NO_ABITS = 1        # untagged access to SDM (untrusted process)
FAULT_NOT_LOCAL = 2       # HWPID not in HWPID_local (wrong host / revoked)
FAULT_NO_ENTRY = 3        # no permission entry covers the address
FAULT_PERM = 4            # entry found but R/W bits deny the access
FAULT_DESYNC = 5          # host lost BISnp events — fail closed until resync


class CheckResult(NamedTuple):
    """Per-access verdicts of one permission-check batch (B accesses)."""
    allowed: jax.Array      # bool[B]
    fault: jax.Array        # i32[B] fault codes
    entry_idx: jax.Array    # i32[B] matched entry (-1 if none)
    probes: jax.Array       # i32[B] binary-search probe count (occupancy stats)


def desync_check_result(n_accesses: int) -> CheckResult:
    """The fail-closed verdict: deny every access with `FAULT_DESYNC`.

    A host that detected a BISnp sequence gap (or sits in quarantine) can
    no longer trust ANY cached or freshly-derived grant — a lost event may
    have revoked exactly the page it is about to serve — so its checker
    answers this instead of consulting the table at all.  Zero probes,
    no cache traffic: the deny is free, the stall is the point."""
    return CheckResult(
        allowed=jnp.zeros((n_accesses,), jnp.bool_),
        fault=jnp.full((n_accesses,), FAULT_DESYNC, jnp.int32),
        entry_idx=jnp.full((n_accesses,), -1, jnp.int32),
        probes=jnp.zeros((n_accesses,), jnp.int32))


def binary_search(starts: jax.Array, n: jax.Array, pages: jax.Array):
    """Textbook binary search with early exit accounting.

    Returns (idx, probes): idx = index of last entry with start <= page
    (-1 if none); probes = number of table entries touched, matching the
    paper's 'binary-search occupancy' metric (Fig. 9).  Runs a fixed
    ceil(log2(cap))+1 iteration loop (jit-friendly) while counting only the
    iterations a sequential searcher would have executed.  Tables with at
    most one live entry short-circuit to a single compare — the common
    one-grant tenant pays no loop at all.
    """
    cap = starts.shape[0]
    steps = int(np.ceil(np.log2(max(cap, 2)))) + 1
    pages = jnp.asarray(pages, jnp.int32)
    n = jnp.asarray(n, jnp.int32)

    def single(_):
        has = (n >= 1) & (starts[0] <= pages)
        return (jnp.where(has, 0, -1).astype(pages.dtype),
                jnp.broadcast_to((n >= 1).astype(jnp.int32), pages.shape))

    def full(_):
        lo = jnp.zeros_like(pages)
        hi = jnp.broadcast_to(n - 1, pages.shape)
        idx = jnp.full_like(pages, -1)
        probes = jnp.zeros_like(pages)

        def body(_, carry):
            lo, hi, idx, probes = carry
            active = lo <= hi
            mid = (lo + hi) // 2
            s = starts[jnp.clip(mid, 0, cap - 1)]
            probes = probes + active.astype(jnp.int32)
            go_right = s <= pages
            idx = jnp.where(active & go_right, mid, idx)
            lo = jnp.where(active & go_right, mid + 1, lo)
            hi = jnp.where(active & ~go_right, mid - 1, hi)
            return lo, hi, idx, probes

        _, _, idx, probes = jax.lax.fori_loop(0, steps, body,
                                              (lo, hi, idx, probes))
        return idx, probes

    return jax.lax.cond(n <= 1, single, full, None)


def check_access(
    table: PermissionTable,
    hwpid_local: jax.Array,     # u32[4] bit-vector of trusted HWPIDs on host
    ext_addrs: jax.Array,       # i32[B] A-bit tagged page addresses
    is_write: jax.Array,        # bool[B]
) -> CheckResult:
    """Vectorized permission check for a batch of tagged accesses."""
    hwpid, page = unpack_ext_addr(ext_addrs)
    is_write = jnp.asarray(is_write, bool)
    # (2) sorted-table search; (1)+(3)+(4) shared with the cached path
    idx, probes = binary_search(table.starts, table.n, page)
    return _finalize(table, hwpid_local, hwpid, page, is_write, idx, probes)


def make_hwpid_local(hwpids) -> jax.Array:
    """Build the per-host trusted HWPID bit-vector (u32[4])."""
    v = np.zeros((4,), np.uint32)
    for h in hwpids:
        v[h // 32] |= np.uint32(1) << np.uint32(h % 32)
    return jnp.asarray(v)


# ---------------------------------------------------------------------------
# Vectorized permission cache (paper §4.2.3: 16 KiB cache in the checker)
# ---------------------------------------------------------------------------
# The paper's checker hides table-walk latency behind a small SRAM cache of
# recently matched entries.  `PermCache` is the batched jnp analogue: an
# N-way set-associative map page -> matched entry index (default 4-way x 64
# sets within the same 16 KiB budget), held as plain arrays so the whole
# probe/refill runs inside jit.  Replacement is tree-PLRU — one (ways-1)-bit
# binary tree per set, victim found by following the bits, every access
# repointing its path away from the touched way — the standard SRAM policy
# the Simu3-style simulators model, and cheap enough to update on the all-hit
# fast path.  The cache is EPOCH-FENCED against the table it mirrors (paper
# §4.1.3/§7.1.7): when `cache.epoch == table.epoch` the FM's BISnp protocol
# guarantees every surviving mapping is current, so probe hits skip
# live-table revalidation entirely and an all-hit batch does no table reads
# in the probe stage at all.  When the epochs diverge (an unwired cache, or
# a missed back-invalidate) the probe falls back to revalidating each hit
# against the live table — a stale mapping then fails validation and
# degrades to a miss, never to a stale grant.  When EVERY lane of a batch
# hits, the log2(N) binary search is skipped entirely via `lax.cond` — the
# vectorized fast path for the repeated-page traffic the paper's cache
# exploits.  The exact fully-associative LRU model lives in
# `repro.core.cache.LruCache` / memsim; this cache trades full associativity
# for a branch-free vector probe, and ways=1 degenerates to the old
# direct-mapped layout (kept for the Fig. 13 comparison column).

PERM_CACHE_BYTES = 16 * 1024    # paper default: 16 KiB
CACHE_ENTRY_BYTES = 64          # one 64 B table entry per cache slot
PERM_CACHE_WAYS = 4             # default associativity (4-way x 64 sets)


class PermCache(NamedTuple):
    """Set-associative (page -> table entry) cache with tree-PLRU
    replacement and an epoch fence: mappings are trusted only while
    `epoch` matches the table's (paper's 16 KiB permission cache)."""
    tag: jax.Array      # i32[n_sets, n_ways] cached page address (-1 invalid)
    entry: jax.Array    # i32[n_sets, n_ways] table entry index matched
    plru: jax.Array     # u32[n_sets] tree-PLRU bits (low n_ways-1 bits used)
    hits: jax.Array     # i32[] cumulative probe hits
    misses: jax.Array   # i32[] cumulative probe misses
    epoch: jax.Array    # i32[] table epoch the surviving mappings are valid at

    @property
    def n_sets(self) -> int:
        """Number of sets (pages index by ``page % n_sets``)."""
        return self.tag.shape[0]

    @property
    def n_ways(self) -> int:
        """Associativity (lines per set)."""
        return self.tag.shape[1]

    @property
    def capacity_bytes(self) -> int:
        """Total capacity at 64 B per cached entry."""
        return self.n_sets * self.n_ways * CACHE_ENTRY_BYTES

    @property
    def hit_rate(self) -> float:
        """Lifetime probe hit fraction (0.0 before any probe)."""
        t = int(self.hits) + int(self.misses)
        return int(self.hits) / t if t else 0.0


def plru_victim(bits, n_ways: int):
    """Tree-PLRU victim way for each set's bit word (vectorized).

    The replacement tree is a perfect binary tree stored breadth-first in
    the low ``n_ways - 1`` bits: node 0 is the root, node ``i``'s children
    are ``2i+1`` / ``2i+2``, and bit value = the direction the next victim
    walk takes (0 left, 1 right).  Leaves map to ways in order.
    """
    bits = jnp.asarray(bits, jnp.uint32)
    node = jnp.zeros(bits.shape, jnp.int32)
    for _ in range(max(n_ways.bit_length() - 1, 0)):
        d = ((bits >> node.astype(jnp.uint32)) & 1).astype(jnp.int32)
        node = 2 * node + 1 + d
    return node - (n_ways - 1)


def plru_touch(bits, way, n_ways: int):
    """Repoint the PLRU tree away from ``way`` (MRU protection): every node
    on the accessed way's root-to-leaf path is set to the *opposite*
    direction, so the victim walk avoids the most recent access.  Vectorized
    over matching ``bits``/``way`` shapes."""
    bits = jnp.asarray(bits, jnp.uint32)
    way = jnp.asarray(way, jnp.int32)
    levels = max(n_ways.bit_length() - 1, 0)
    node = jnp.zeros(way.shape, jnp.int32)
    for lvl in range(levels):
        d = (way >> (levels - 1 - lvl)) & 1
        mask = jnp.uint32(1) << node.astype(jnp.uint32)
        bits = jnp.where(d == 1, bits & ~mask, bits | mask)
        node = 2 * node + 1 + d
    return bits


def make_perm_cache(capacity_bytes: int = PERM_CACHE_BYTES,
                    *, epoch: int = 0,
                    ways: int = PERM_CACHE_WAYS) -> PermCache:
    """Fresh (all-invalid) set-associative cache.  The 16 KiB default holds
    256 entries as 64 sets x 4 ways; ``ways=1`` gives the direct-mapped
    layout.  Pass ``epoch=table.epoch`` (or wire `invalidate_perm_cache` to
    the FM's BISnp broadcasts) to enable the fenced fast path; a cache left
    at an older epoch still returns correct verdicts via per-hit
    revalidation."""
    if ways < 1 or ways & (ways - 1):
        raise ValueError("perm cache ways must be a power of two")
    if capacity_bytes % (CACHE_ENTRY_BYTES * ways):
        raise ValueError(
            "capacity must be a multiple of 64 B entries x ways")
    n_sets = capacity_bytes // (CACHE_ENTRY_BYTES * ways)
    if n_sets & (n_sets - 1):
        raise ValueError("perm cache set count must be a power of two")
    return PermCache(
        tag=jnp.full((n_sets, ways), -1, jnp.int32),
        entry=jnp.full((n_sets, ways), -1, jnp.int32),
        plru=jnp.zeros((n_sets,), jnp.uint32),
        hits=jnp.zeros((), jnp.int32),
        misses=jnp.zeros((), jnp.int32),
        epoch=jnp.asarray(epoch, jnp.int32),
    )


def invalidate_perm_cache(
    cache: PermCache,
    start_page,
    n_pages,
    epoch,
    *,
    min_shifted_entry: int | None = None,
) -> PermCache:
    """Apply one FM BISnp back-invalidate to the cache (targeted, no
    flush-the-world): drop mappings whose page falls in the dirty range
    ``[start_page, start_page + n_pages)`` and — when the commit shifted
    entry indices — mappings whose cached index is ``>= min_shifted_entry``.

    Epoch fencing rules (events may be duplicated or replayed by an
    adversary; both are harmless):
      * ``epoch == cache.epoch + 1`` — the expected next event: targeted
        drop, fence advances.
      * ``epoch <= cache.epoch`` — duplicate/replayed event: targeted drop
        (conservative, never unsafe), fence unchanged.
      * ``epoch > cache.epoch + 1`` — at least one event was missed: the
        intermediate dirty ranges are unknown, so every mapping is dropped
        (the resync path — NOT the normal path) and the fence jumps forward.
    """
    # None -> INT32_MAX sentinel (drops nothing) so the index is a traced
    # operand: churn broadcasts with ever-different indices reuse one jit
    # trace instead of recompiling per value.
    if min_shifted_entry is None:
        min_shifted_entry = np.iinfo(np.int32).max
    return _invalidate_perm_cache_jit(cache, start_page, n_pages, epoch,
                                      min_shifted_entry)


@jax.jit
def _invalidate_perm_cache_jit(cache, start_page, n_pages, epoch,
                               min_shifted_entry):
    start = jnp.asarray(start_page, jnp.int32)
    n = jnp.asarray(n_pages, jnp.int32)
    ev_epoch = jnp.asarray(epoch, jnp.int32)
    drop = (cache.tag >= start) & (cache.tag < start + n)
    drop = drop | (cache.entry >= jnp.asarray(min_shifted_entry, jnp.int32))
    gap = ev_epoch > cache.epoch + 1
    drop = drop | gap
    return cache._replace(
        tag=jnp.where(drop, -1, cache.tag),
        entry=jnp.where(drop, -1, cache.entry),
        epoch=jnp.maximum(cache.epoch, ev_epoch),
    )


def _finalize(table, hwpid_local, hwpid, page, is_write, idx, probes):
    """Steps 1+3+4 of the checker, shared by the cached and uncached paths."""
    has_abits = hwpid > 0
    word = hwpid_local[jnp.clip(hwpid // 32, 0, 3)]
    local_ok = ((word >> (hwpid % 32).astype(jnp.uint32)) & 1).astype(bool)

    safe_idx = jnp.clip(idx, 0, table.capacity - 1)
    s = table.starts[safe_idx]
    sz = table.sizes[safe_idx]
    in_range = (idx >= 0) & (page >= s) & (page < s + sz) & (s != EMPTY_START)

    pw = table.perms[safe_idx]
    perm = extract_perm(pw, hwpid)
    need = jnp.where(is_write, jnp.uint32(2), jnp.uint32(1))
    perm_ok = (perm & need) == need

    allowed = has_abits & local_ok & in_range & perm_ok
    fault = jnp.where(
        ~has_abits, FAULT_NO_ABITS,
        jnp.where(~local_ok, FAULT_NOT_LOCAL,
                  jnp.where(~in_range, FAULT_NO_ENTRY,
                            jnp.where(~perm_ok, FAULT_PERM, FAULT_NONE))))
    fault = jnp.where(allowed, FAULT_NONE, fault).astype(jnp.int32)
    return CheckResult(allowed, fault, jnp.where(in_range, idx, -1), probes)


def cached_check_access(
    table: PermissionTable,
    hwpid_local: jax.Array,
    ext_addrs: jax.Array,
    is_write: jax.Array,
    cache: PermCache,
) -> tuple[CheckResult, PermCache]:
    """`check_access` with the set-associative permission-cache fast path.

    Semantically identical to `check_access` (same CheckResult fields except
    `probes`, which is 0 on cache-hit lanes — the search was skipped);
    additionally returns the updated cache.  Purely functional: thread the
    returned cache into the next call, and apply `invalidate_perm_cache` for
    every FM BISnp event to keep the epoch fence closed.
    """
    hwpid, page = unpack_ext_addr(ext_addrs)
    is_write = jnp.asarray(is_write, bool)
    n_sets, n_ways = cache.n_sets, cache.n_ways

    # probe: set-indexed on the low page bits, all ways compared at once.
    # Inside the epoch fence the BISnp protocol already guarantees
    # freshness, so the probe is just a tag compare; outside it every hit is
    # revalidated against the live table (a stale mapping then fails
    # validation and degrades to a miss, never to a wrong verdict).
    with jax.named_scope("permcache_probe"):
        set_idx = page & (n_sets - 1)
        ctags = cache.tag[set_idx]                    # (B, ways)
        cents = cache.entry[set_idx]                  # (B, ways)
        way_match = (ctags == page[..., None]) & (cents >= 0)
        probe_ok = jnp.any(way_match, axis=-1)
        hit_way = jnp.argmax(way_match, axis=-1).astype(jnp.int32)
        cent = jnp.take_along_axis(cents, hit_way[..., None], axis=-1)[..., 0]
        safe_cent = jnp.clip(cent, 0, table.capacity - 1)
        fenced = cache.epoch == jnp.asarray(table.epoch, jnp.int32)

        def probe_fenced(_):
            return probe_ok

        def probe_revalidate(_):
            cs = table.starts[safe_cent]
            csz = table.sizes[safe_cent]
            return (probe_ok & (page >= cs) & (page < cs + csz)
                    & (cs != EMPTY_START))

        hit = jax.lax.cond(fenced, probe_fenced, probe_revalidate, None)

    # fast path: when the whole batch hits, skip the binary search entirely
    def slow(_):
        return binary_search(table.starts, table.n, page)

    def fast(_):
        return cent, jnp.zeros_like(page)

    bs_idx, bs_probes = jax.lax.cond(jnp.all(hit), fast, slow, None)
    idx = jnp.where(hit, cent, bs_idx)
    probes = jnp.where(hit, 0, bs_probes)

    result = _finalize(table, hwpid_local, hwpid, page, is_write, idx, probes)

    bits = cache.plru[set_idx]                    # (B,) gathered PLRU words

    def scatter_plru(upd, way_used):
        """Repoint touched sets' trees away from the way each lane used
        (duplicate sets in one batch: last lane wins, like any
        single-ported SRAM update; n_sets is the drop slot)."""
        new_bits = plru_touch(bits, way_used, n_ways)
        upd_set = jnp.where(upd, set_idx, n_sets)
        plru1 = jnp.concatenate([cache.plru, jnp.zeros((1,), jnp.uint32)])
        return plru1.at[upd_set].set(new_bits)[:n_sets]

    # all-hit fast path: tags/entries unchanged, and the PLRU scatter is
    # skipped too — replacement state only matters when a refill has to
    # pick a victim, and an all-hit batch performs none.  Any batch that
    # DOES miss refreshes recency for its hit lanes as well (the refill
    # branch touches hit and filled ways alike), so the victim walk still
    # sees current recency whenever it actually runs.  Skipping the
    # scatter here is what keeps the steady-state hot path at probe +
    # verdict cost only.
    def allhit_update(_):
        return cache.tag, cache.entry, cache.plru

    # refill: install missed lanes that resolved to a live entry, filling
    # an invalid way first and the tree-PLRU victim once the set is full.
    # Distinct pages aliasing into one set within the SAME batch are fanned
    # out across consecutive ways (a sequential SRAM would install each in
    # turn; without the rank they would all target the same way and only
    # the last would survive the scatter).
    def refill(_):
        inv = cents < 0
        inv_way = jnp.argmax(inv, axis=-1).astype(jnp.int32)
        victim = plru_victim(bits, n_ways)
        base_way = jnp.where(jnp.any(inv, axis=-1), inv_way, victim)
        found = ~hit & (result.entry_idx >= 0)
        # rank of each lane's page among the distinct filling pages of its
        # set: sort on (set, page), count page changes within set runs
        skey = jnp.where(found, (set_idx << 24) | page,
                         jnp.int32(np.iinfo(np.int32).max))
        order = jnp.argsort(skey)
        sk = skey[order]
        one = jnp.ones((1,), bool)
        fresh = jnp.concatenate([one, sk[1:] != sk[:-1]])
        set_run = jnp.concatenate([one, (sk[1:] >> 24) != (sk[:-1] >> 24)])
        distinct = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        run_base = jax.lax.cummax(jnp.where(set_run, distinct, -1))
        rank = jnp.zeros_like(distinct).at[order].set(distinct - run_base)
        fill_way = (base_way + rank) % n_ways
        way_used = jnp.where(hit, hit_way, fill_way)
        upd_set = jnp.where(found, set_idx, n_sets)  # n_sets = drop slot
        tag1 = jnp.concatenate(
            [cache.tag, jnp.full((1, n_ways), -1, jnp.int32)])
        ent1 = jnp.concatenate(
            [cache.entry, jnp.full((1, n_ways), -1, jnp.int32)])
        return (tag1.at[upd_set, fill_way].set(page)[:n_sets],
                ent1.at[upd_set, fill_way].set(result.entry_idx)[:n_sets],
                scatter_plru(hit | found, way_used))

    new_tag, new_ent, new_plru = jax.lax.cond(
        jnp.all(hit), allhit_update, refill, None)
    n_hits = jnp.sum(hit).astype(jnp.int32)
    new_cache = PermCache(
        tag=new_tag,
        entry=new_ent,
        plru=new_plru,
        hits=cache.hits + n_hits,
        misses=cache.misses + (jnp.int32(page.size) - n_hits),
        # refills never advance the fence: only BISnp events do.  Entries
        # installed while the fence is open are validated per-hit until the
        # missing events arrive (or forever, for an unwired cache).
        epoch=cache.epoch,
    )
    return result, new_cache


check_access_jit = jax.jit(check_access)
cached_check_access_jit = jax.jit(cached_check_access)
