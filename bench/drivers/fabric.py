"""General driver of the fabric cells: a `ShardedFabric` deployment (hosts,
tenants, shared region) from the configuration file, tagged-word egress
traffic from the traffic file, and optionally a stream of range commits
(revoke, then re-grant) under that traffic.

Traffic parameters (bench/traffic/<mix>.json, "driver": "fabric"):

    words_per_row    tagged words each tenant row pulls per launch
    batches          distinct batches made at set-up and cycled
    in_flight        launches the host keeps queued on the device
    shares           {"own", "granted", "shared", "anywhere"}: where a
                     word's page lies (its own span, a shared chunk granted
                     to it, any shared page, any page of the pool)
    forged, untagged share of words tagged with another tenant's HWPID /
                     with no tag
    need, key0, key1 permission needed and the pool's line key
    commit_period_s  null for a static table; else a commit is due this
                     long after the previous one was enforced
    compare_launches launches of the window, drawn from the seed over the
                     whole window (a reservoir sample), whose words and
                     fault codes are compared with the reference (the
                     first and the last launch and every enforcing launch
                     always are)

The timed entry is `ShardedFabric.step_egress`; each enforcing launch is
timed from the start of the FM call to its own completion.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench.reference import fabric_ref
from bench.roofline import egress_bytes

PERM_R, PERM_RW = 1, 3


class Layout:
    """The deployment the configuration states, built through the program
    and recorded independently for the reference."""

    def __init__(self, cfg: dict):
        from repro.core import ShardedFabric
        self.cfg = cfg
        n_hosts, n_tenants = cfg["n_hosts"], cfg["n_tenants"]
        private = cfg["private_pages"]
        chunks, stride = cfg["shared_chunks"], cfg["shared_stride"]
        chunk = cfg["shared_chunk_pages"]
        self.fab = fab = ShardedFabric(cfg["sdm_pages"],
                                       table_capacity=cfg["table_capacity"],
                                       n_shards=n_hosts)
        self.dep = dep = fabric_ref.Deployment(cfg["sdm_pages"], n_hosts)
        for h in range(n_hosts):
            fab.enroll(h)
        self.hosts = [p * n_hosts // n_tenants for p in range(n_tenants)]
        self.tenants = [fab.admit(h, private) for h in self.hosts]
        for hwpid, start in self.tenants:
            dep.grant(start, private, hwpid, PERM_RW)
        # the shared region lies in the last host's shard: gapped chunks,
        # each read-granted to one tenant and read/write-granted to another
        self.shared_lo = (n_hosts - 1) * dep.shard
        self.holders = [(c % n_tenants, (5 * c + 1) % n_tenants)
                        for c in range(chunks)]
        with fab.fm.transaction():
            for c, (pr, prw) in enumerate(self.holders):
                for p, perm in ((pr, PERM_R), (prw, PERM_RW)):
                    self.propose(c, p, perm)
        for rt in fab.runtimes.values():
            rt.add_resident_range(self.shared_lo, chunks * stride)
        dep.add_shared(self.shared_lo, chunks * stride)
        fab.quiesce()
        self.hwpids = np.array([w for w, _ in self.tenants], np.int32)
        self.starts = np.array([s for _, s in self.tenants], np.int64)
        self.assign = {self.hosts[p]: int(self.hwpids[p])
                       for p in range(n_tenants)}
        self.rows = [(self.hosts[p], int(self.hwpids[p]))
                     for p in range(n_tenants)]
        if fab.fabric_rows(self.assign) != self.rows:
            raise RuntimeError("fabric row order differs from tenant order")

    def chunk_start(self, c: int) -> int:
        return self.shared_lo + c * self.cfg["shared_stride"]

    def propose(self, c: int, p: int, perm: int):
        from repro.core import Proposal
        hwpid = int(self.tenants[p][0])
        n = self.cfg["shared_chunk_pages"]
        if self.fab.fm.propose(Proposal(
                self.hosts[p], hwpid, 0x2000 + hwpid, self.chunk_start(c), n,
                perm)) is None:
            raise RuntimeError(f"FM rejected the grant of chunk {c}")
        self.dep.grant(self.chunk_start(c), n, hwpid, perm)

    def revoke(self, c: int, p: int) -> None:
        hwpid = int(self.tenants[p][0])
        n = self.cfg["shared_chunk_pages"]
        self.fab.fm.release_range(hwpid, self.chunk_start(c), n)
        self.dep.revoke(self.chunk_start(c), n, hwpid)

    def shard_entries(self) -> list[int]:
        """Entries of each row's resident shard, as the reference holds it."""
        return [len(self.dep.resident(h)[0]) for h, _ in self.rows]


def make_batches(layout: Layout, traffic: dict, rng: np.random.Generator):
    """[(data u32[R, B], ext i32[R, B])] * batches: each row mostly its own
    span and its granted shared chunks, then other shared pages
    (FAULT_PERM), gaps and far pages (FAULT_NO_ENTRY), forged tags
    (FAULT_NOT_LOCAL) and untagged words (FAULT_NO_ABITS)."""
    cfg = layout.cfg
    n, words = cfg["n_tenants"], traffic["words_per_row"]
    chunks, stride = cfg["shared_chunks"], cfg["shared_stride"]
    chunk = cfg["shared_chunk_pages"]
    own = [[c for c, hs in enumerate(layout.holders) if p in hs]
           for p in range(n)]
    width = max(len(o) for o in own)
    own_pad = np.array([o + o[:1] * (width - len(o)) for o in own], np.int64)
    own_len = np.array([len(o) for o in own], np.int64)
    sh = traffic["shares"]
    t_granted = sh["own"]
    t_shared = t_granted + sh["granted"]
    t_any = t_shared + sh["shared"]
    r = np.arange(n)[:, None]
    out = []
    for _ in range(traffic["batches"]):
        kind = rng.random((n, words))
        pages = layout.starts[:, None] + rng.integers(
            0, cfg["private_pages"], (n, words))
        pick = (rng.random((n, words)) * own_len[:, None]).astype(np.int64)
        granted = own_pad[r, pick]
        pages = np.where(kind >= t_granted, layout.shared_lo + granted * stride
                         + rng.integers(0, chunk, (n, words)), pages)
        pages = np.where(kind >= t_shared, layout.shared_lo + rng.integers(
            0, chunks * stride, (n, words)), pages)
        pages = np.where(kind >= t_any, rng.integers(
            0, cfg["sdm_pages"], (n, words)), pages)
        tags = np.broadcast_to(layout.hwpids[:, None], (n, words)).copy()
        tag_kind = rng.random((n, words))
        tags = np.where(tag_kind < traffic["forged"],
                        layout.hwpids[(r + 1) % n], tags)
        tags = np.where(tag_kind >= 1.0 - traffic["untagged"], 0, tags)
        ext = ((tags.astype(np.int64) << fabric_ref.HWPID_SHIFT)
               | pages).astype(np.int32)
        data = rng.integers(0, 1 << 32, (n, words), dtype=np.uint32)
        out.append((data, ext))
    return out


def commit_plan(layout: Layout, rng: np.random.Generator, n: int):
    """n (chunk, tenant) pairs: commit 2k revokes pair k, 2k + 1 re-grants
    it with the permission it had."""
    cs = rng.integers(0, layout.cfg["shared_chunks"], n)
    side = rng.integers(0, 2, n)
    return [(int(c), layout.holders[c][s]) for c, s in zip(cs, side)]


def regrant_perm(layout: Layout, c: int, p: int) -> int:
    """The permission tenant p held on chunk c at set-up."""
    return (PERM_R if layout.holders[c][0] == p else 0) | \
        (PERM_RW if layout.holders[c][1] == p else 0)


def replay(layout: Layout, first, plan, n: int):
    """Reference states after 0..n commits of `plan`, from `first`."""
    n_pages = layout.cfg["shared_chunk_pages"]
    states, dep = [first], first.copy()
    for k in range(n):
        c, p = plan[k // 2]
        hwpid = int(layout.tenants[p][0])
        if k % 2 == 0:
            dep.revoke(layout.chunk_start(c), n_pages, hwpid)
        else:
            dep.grant(layout.chunk_start(c), n_pages, hwpid,
                      regrant_perm(layout, c, p))
        states.append(dep.copy())
    return states


def run(ctx, *, egress=None) -> dict:
    """One run of a fabric cell.  `egress` replaces the timed entry for the
    fault tests: egress(layout, data, ext) -> (out, fault)."""
    import jax

    cfg, tr = ctx.config, ctx.traffic
    spans = ctx.spans
    need, key0, key1 = tr["need"], tr["key0"], tr["key1"]
    words = tr["words_per_row"]
    if words % 1024 or (words // 1024) & (words // 1024 - 1):
        raise ValueError("words_per_row must be a power-of-two multiple of "
                         "1024 (the pool's line layout)")

    with spans("setup.fabric"):
        layout = Layout(cfg)
    fab = layout.fab
    ctx.log(f"fabric set up: {cfg['n_hosts']} hosts, {cfg['n_tenants']} "
            f"tenants, {fab.fm.table.n} table entries")
    with spans("setup.traffic"):
        host_batches = make_batches(layout, tr, ctx.rng("traffic"))
        dev_batches = [(jax.device_put(d), jax.device_put(e))
                       for d, e in host_batches]
        jax.block_until_ready(dev_batches)

    if egress is None:
        def egress(layout, data, ext):
            return layout.fab.step_egress(data, ext, layout.assign,
                                          need=need, key0=key0, key1=key1)

    period = tr.get("commit_period_s")
    plan = commit_plan(layout, ctx.rng("commits"), 512)

    def commit(k: int, plan=plan) -> None:
        c, p = plan[k // 2]
        with spans("bench.fm_commit"):
            if k % 2 == 0:
                layout.revoke(c, p)
            else:
                layout.propose(c, p, regrant_perm(layout, c, p))
        with spans("bench.quiesce"):
            fab.quiesce()

    # warm-up: every shape the window uses; the churn cell also runs one
    # revoke / re-grant cycle so that the delivery and view-rebuild paths
    # have compiled (the table returns to its first state)
    with spans("setup.warmup"):
        for data, ext in dev_batches[:2]:
            jax.block_until_ready(egress(layout, data, ext))
        if period is not None:
            warm_plan = commit_plan(layout, ctx.rng("warm-commit"), 1)
            for k in range(2):
                commit(k, warm_plan)
                jax.block_until_ready(egress(layout, *dev_batches[0]))
    first_state = layout.dep.copy()

    n_rows = len(layout.rows)
    pick = ctx.rng("sample")
    n_sample = tr["compare_launches"]
    kept = []          # (launch index, batch, state, out, fault)
    reservoir, seen, last = [], 0, None
    enforce_s, inflight = [], collections.deque()
    launches, commits = 0, 0
    ok = True
    err = None

    ctx.begin_window()
    t0 = time.perf_counter()
    deadline = ctx.deadline()
    next_commit = t0 + period if period is not None else None
    try:
        while time.perf_counter() < deadline:
            if next_commit is not None and time.perf_counter() >= next_commit:
                tc = time.perf_counter()
                commit(commits)
                commits += 1
                while len(inflight) >= tr["in_flight"]:
                    jax.block_until_ready(inflight.popleft())
                b = launches % len(dev_batches)
                with spans("bench.step_egress"):
                    res = egress(layout, *dev_batches[b])
                jax.block_until_ready(res)
                t_enf = time.perf_counter()
                enforce_s.append(t_enf - tc)
                inflight.clear()
                kept.append((launches, b, commits, *res))
                launches += 1
                next_commit = t_enf + period
                continue
            while len(inflight) >= tr["in_flight"]:
                jax.block_until_ready(inflight.popleft())
            b = launches % len(dev_batches)
            with spans("bench.step_egress"):
                res = egress(layout, *dev_batches[b])
            inflight.append(res)
            last = (launches, b, commits, *res)
            if launches == 0:
                kept.append(last)
            elif len(reservoir) < n_sample:
                reservoir.append(last)
            else:
                j = int(pick.integers(0, seen + 1))
                if j < n_sample:
                    reservoir[j] = last
            seen += launches > 0
            launches += 1
        while inflight:
            jax.block_until_ready(inflight.popleft())
        kept += reservoir
        if last is not None and all(k[0] != last[0] for k in kept):
            kept.append(last)
        kept.sort(key=lambda k: k[0])
    except Exception as e:  # noqa: BLE001 - a failed step is a wrong run
        ok, err = False, e
        ctx.log(f"the timed path raised: {e!r}")
    window_s = ctx.end_window()

    # -- checks: every kept launch, bit-exact against the reference ---------
    ks = fabric_ref.keystream(key0, key1, np.arange(n_rows * words)) \
        .reshape(n_rows, words)
    mismatched_lanes, failed_launches = 0, 0
    by_fault = np.zeros(5, np.int64)
    states = replay(layout, first_state, plan, commits)
    for k, b, s, out, fault in kept:
        data, ext = host_batches[b]
        r_out, r_fault = fabric_ref.check_rows(
            states[s], layout.rows, data, ext, need=need, key0=key0,
            key1=key1, ks=ks)
        o, f = np.asarray(out), np.asarray(fault)
        bad = int(np.count_nonzero((o != r_out) | (f != r_fault)))
        mismatched_lanes += bad
        failed_launches += bad > 0
        by_fault += np.bincount(r_fault.reshape(-1), minlength=5)[:5]
    ctx.log(f"compared {len(kept)} launches with the reference: lanes by "
            f"fault code NONE/NO_ABITS/NOT_LOCAL/NO_ENTRY/PERM = "
            f"{by_fault.tolist()}, {mismatched_lanes} lanes differ")
    if not kept or any(by_fault == 0):
        ok = False
        ctx.log("the compared launches miss a verdict or are none")
    if err is not None:
        failed_launches += 1

    words_done = launches * n_rows * words
    metrics = {"checked_words_per_s": words_done / window_s}
    if period is not None:
        # a commit never enforced in the window stalls all of it
        metrics["enforce_ms"] = 1e3 * (float(np.mean(enforce_s))
                                       if enforce_s else window_s)
        ok = ok and bool(enforce_s)
    counters = {
        "launches": launches, "rows": n_rows, "words_per_row": words,
        "commits": commits, "enforce_ms": [1e3 * x for x in enforce_s],
        "bytes_per_launch": egress_bytes(n_rows, words,
                                         layout.shard_entries()),
        "window_s": window_s, "compared_launches": len(kept),
    }
    return {"metrics": metrics, "attempted": launches + commits,
            "failed": failed_launches, "ok": ok, "counters": counters,
            "checks": {"mismatched_lanes": (mismatched_lanes, 0)}}
