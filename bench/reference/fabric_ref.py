"""Plain reference of checked egress on a sharded deployment.

It follows the semantics that Space-Control states, from the grants the
benchmark itself committed, and uses nothing that the program made (no
table, shard, view or cache):

* a tagged word is ``tag << 24 | page``; a word whose tag is 0 or negative
  is untagged (FAULT_NO_ABITS, 1), a word tagged with another process's
  HWPID is not local to the row's process (FAULT_NOT_LOCAL, 2);
* host ``h`` holds the table entries that overlap its resident page
  ranges: its own shard ``[h * S, (h + 1) * S)`` with
  ``S = ceil(sdm_pages / n_hosts)``, and every shared region made resident
  on it; a page that no such entry covers has no entry (FAULT_NO_ENTRY, 3);
* an entry grants each HWPID the union of the permissions proposed for it
  and not revoked since; a revoked range stays in the table as an entry
  without permissions, so its pages answer FAULT_PERM (4) like any entry
  that does not grant ``need``;
* a released word is the stored word XORed with the keystream of its
  position (counter-mode ARX, Threefry-2x32 with 12 rounds); row ``r``,
  lane ``i`` of a launch of ``B`` words per row sits at position
  ``r * B + i`` (B a power-of-two multiple of 1024, as the pool lays out
  its lines); a denied word reads 0.
"""
from __future__ import annotations

import numpy as np

HWPID_SHIFT = 24
PAGE_MASK = (1 << HWPID_SHIFT) - 1
NONE, NO_ABITS, NOT_LOCAL, NO_ENTRY, PERM = 0, 1, 2, 3, 4

_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA
N_ROUNDS = 12


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def keystream(key0: int, key1: int, positions: np.ndarray) -> np.ndarray:
    """u32 keystream word at each flat word position: block function over
    (line = pos // 16, word = pos % 16) with key (key0, key1)."""
    pos = positions.astype(np.uint32)
    k0, k1 = np.uint32(key0), np.uint32(key1)
    k2 = np.uint32(key0 ^ key1 ^ _PARITY)
    ks = (k0, k1, k2)
    x0 = (pos // np.uint32(16)) + k0
    x1 = (pos % np.uint32(16)) + k1
    for rnd in range(N_ROUNDS):
        x0 = x0 + x1
        x1 = _rotl(x1, _ROTATIONS[rnd % 8]) ^ x0
        if rnd % 4 == 3:
            j = rnd // 4 + 1
            x0 = x0 + ks[j % 3]
            x1 = x1 + ks[(j + 1) % 3] + np.uint32(j)
    return x0


class Deployment:
    """The grants a benchmark committed, kept as plain data."""

    def __init__(self, sdm_pages: int, n_hosts: int):
        self.sdm_pages = sdm_pages
        self.n_hosts = n_hosts
        self.shard = -(-sdm_pages // n_hosts)
        # (start, end) -> {hwpid: perm}; entries are never dropped
        self.entries: dict[tuple[int, int], dict[int, int]] = {}
        self.shared: list[tuple[int, int]] = []   # resident on every host
        self._keys: dict[int, list] = {}             # host -> resident keys

    def grant(self, start: int, n: int, hwpid: int, perm: int) -> None:
        if (start, start + n) not in self.entries:
            self._keys.clear()
        e = self.entries.setdefault((start, start + n), {})
        e[hwpid] = e.get(hwpid, 0) | perm

    def revoke(self, start: int, n: int, hwpid: int) -> None:
        self.entries[(start, start + n)][hwpid] = 0

    def add_shared(self, start: int, n: int) -> None:
        self.shared.append((start, start + n))
        self._keys.clear()

    def copy(self) -> "Deployment":
        d = Deployment(self.sdm_pages, self.n_hosts)
        d.entries = {k: dict(v) for k, v in self.entries.items()}
        d.shared = list(self.shared)
        d._keys = dict(self._keys)
        return d

    def resident(self, host: int):
        """(starts, ends, perm-dicts) of the entries host `host` holds,
        sorted by start."""
        keys = self._keys.get(host)
        if keys is None:
            ranges = [(host * self.shard, (host + 1) * self.shard)] \
                + self.shared
            keys = self._keys[host] = sorted(
                k for k in self.entries
                if any(k[0] < hi and k[1] > lo for lo, hi in ranges))
        return (np.array([k[0] for k in keys], np.int64),
                np.array([k[1] for k in keys], np.int64),
                [self.entries[k] for k in keys])


def check_rows(dep: Deployment, rows, data: np.ndarray, ext: np.ndarray, *,
               need: int, key0: int, key1: int, ks: np.ndarray | None = None):
    """Reference (out u32[R, B], fault i32[R, B]) for rows [(host, hwpid)]
    of one launch.  `ks` may pass the launch's keystream, which depends
    only on the shape."""
    n_rows, b = ext.shape
    if ks is None:
        ks = keystream(key0, key1, np.arange(n_rows * b)).reshape(n_rows, b)
    out = np.zeros((n_rows, b), np.uint32)
    fault = np.zeros((n_rows, b), np.int32)
    by_host = {}
    for r, (host, hwpid) in enumerate(rows):
        if host not in by_host:
            by_host[host] = dep.resident(host)
        starts, ends, perms = by_host[host]
        e = ext[r].astype(np.int32)
        tag = e >> HWPID_SHIFT
        page = (e & PAGE_MASK).astype(np.int64)
        i = np.searchsorted(starts, page, side="right") - 1
        ic = np.clip(i, 0, max(len(starts) - 1, 0))
        covered = (i >= 0) & (page < ends[ic]) if len(starts) else \
            np.zeros(b, bool)
        grant = np.array([p.get(hwpid, 0) for p in perms], np.int64)
        has = (grant[ic] & need) == need if len(starts) else \
            np.zeros(b, bool)
        allowed = (tag == hwpid) & covered & has
        fault[r] = np.where(allowed, NONE, np.where(
            tag <= 0, NO_ABITS, np.where(
                tag != hwpid, NOT_LOCAL, np.where(~covered, NO_ENTRY, PERM))))
        out[r] = np.where(allowed, data[r] ^ ks[r], np.uint32(0))
    return out, fault
