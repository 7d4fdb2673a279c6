"""Serving launcher: continuous-batching multi-tenant decode on the
sharded fabric — ONE data plane for serving, churn, and the scale bench.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --preset smoke --requests 8 --prompt-len 32 --gen 16

The engine demonstrates the paper's serving-side integration end to end,
now on the same `ShardedFabric` the 255-host scale bench drives:

  * each tenant is ADMITTED on a fabric host: `ShardedFabric.admit`
    allocates its KV page span inside the host's shard (coalescing free
    list — churn reuses pages without fragmenting), assigns a
    deployment-unique HWPID, and commits the RW grant; the KV block is
    registered in the shared tensor pool AT that span (`register_at`), so
    pool regions and fabric grants name the same pages;
  * hosts are MULTI-TENANT: several untrusting processes share one
    `HostRuntime` — one resident shard, one epoch-fenced PermCache, one
    `hwpid_local` set covering all co-resident tenants;
  * every decode step's KV-page touch set is validated through
    `HostRuntime.check` — the identical checked egress path the fabric
    bench uses — after the host's BISnp queue is drained up to the table
    epoch (`bus.deliver_until`, the per-step fence close);
  * with ``fused_egress=True`` the step additionally pulls every active
    tenant's KV lines through ONE `ShardedFabric.step_egress` launch
    (one row per (host, tenant) pair) and cross-checks the kernel's
    fault lanes against the framework verdicts;
  * eviction flows through `ShardedFabric.evict`: one revocation commit
    (index-stable tombstones, targeted BISnp), the page span returns to
    the host's coalescing free list, and the HWPID returns to the pool;
  * mid-run revocation kills a tenant's decoding at its very next
    KV-page touch while co-resident tenants on the SAME host keep their
    all-hit fast path — the isolation property, live.

Batching: the engine interleaves all tenants each `step()` (continuous
batching at tenant-group granularity): every active tenant decodes one
token per engine step, finished request groups retire and their slots
refill from the tenant's queue, and tenants can join or leave between any
two steps.

Observability (docs/observability.md): `step` writes one profiler span
per phase (`serve.step`, `serve.gather`, `serve.prefill`, `serve.fence`,
`serve.check`, `serve.egress`, then `serve.verdict`, `serve.emit` and
`serve.decode` per tenant).  They cost about a microsecond each while no
profiler runs; `host_reads` counts the results `step` reads back from the
device.  Each tenant's served tokens leave the chip in one transfer,
started when its token array is made and collected under `serve.emit`;
`token_transfers` counts those transfers.  A model that holds a share of
its experts (`experts_held`) also sums its routing counts on the device,
per tenant, inside the decode step: `expert_slots` and `experts_hit` read
nothing back until the caller converts them.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import ARCHS, smoke_config
from repro.core import (FAULT_DESYNC, FAULT_NONE, SharedTensorPool,
                        pack_ext_addr)
from repro.core.fabric import ShardedFabric
from repro.core.table import PAGE_BYTES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry


def _next_tokens(logits: jax.Array) -> jax.Array:
    """The greedy next token of each row, int32 [B, 1], with its copy to
    the host already started: the device fills it as soon as the argmax
    ends, so the emit that serves it finds it there."""
    cur = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    cur.copy_to_host_async()
    return cur


@dataclass
class Tenant:
    name: str
    hwpid: int
    host_id: int
    queue: list = field(default_factory=list)   # prompt arrays
    done: list = field(default_factory=list)    # (prompt, generated)
    aborted: list = field(default_factory=list)  # prompts killed in flight
    kv_start_page: int = 0
    kv_n_pages: int = 0
    revoked: bool = False
    # in-flight decode group (continuous-batching slot state)
    group: list | None = None
    cache: object = None
    cur: jax.Array | None = None
    out: list | None = None
    plen: int = 0
    pos: int = 0
    gen_left: int = 0
    last_fault: int = FAULT_NONE
    # logits [B, V] behind the group's newest token (prefill, then each
    # decode step): what a reference forward pass is compared with
    last_logits: jax.Array | None = None
    # int32[2] on the device, models with held experts only: held (token,
    # expert) slots computed and held experts hit, summed over layers and
    # decode steps
    routed: jax.Array | None = None


class ServeEngine:
    """Continuous-batching multi-tenant decode on a `ShardedFabric`:
    per-step KV-page checks through each host's fenced PermCache, with an
    optional single-launch fused egress across every (host, tenant) row."""

    def __init__(self, cfg, params, *, batch: int, cap: int,
                 fused_egress: bool = False, n_hosts: int = 4,
                 sdm_pages: int = 1 << 20, table_capacity: int = 8192):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.cap = cap
        # optional: pull each step's KV lines through the batched fabric
        # check⊕decrypt kernel (one launch for ALL tenants on all hosts)
        # on top of the cached framework check
        self.fused_egress = fused_egress
        self.pool = SharedTensorPool()
        self.fabric = ShardedFabric(sdm_pages, table_capacity,
                                    n_shards=n_hosts)
        self.fm = self.fabric.fm
        self.tenants: dict[str, Tenant] = {}

        if cfg.experts_held:
            def serve_decode(p, c, t, pos, routed):
                logits, c, counts = registry.decode_step_routed(
                    cfg, p, c, t, pos)
                return logits, c, routed + counts
        else:
            def serve_decode(p, c, t, pos):
                return registry.decode_step(cfg, p, c, t, pos)

        self._decode = jax.jit(serve_decode)
        self.faults = 0
        self.steps = 0
        # results `step` reads back from the device: each tenant's
        # cross-check and verdict, the fault on a deny, and each served token
        self.host_reads = 0
        # device-to-host transfers that carry served tokens: one per tenant
        # emit, whatever the group's size
        self.token_transfers = 0
        # fail-closed stalls: step ticks where a tenant's host was desynced
        # (lost BISnp events) and denied the batch WITHOUT aborting the
        # group — the tenant retries next tick and recovers after resync
        self.stalls = 0

    # -- observability ---------------------------------------------------------
    @property
    def expert_slots(self) -> jax.Array:
        """Held (token, expert) slots computed in decode steps, over the
        admitted tenants: a device scalar (models with held experts)."""
        return sum(t.routed[0] for t in self.tenants.values())

    @property
    def experts_hit(self) -> jax.Array:
        """Held experts with at least one slot, summed over layers and
        decode steps and the admitted tenants: a device scalar."""
        return sum(t.routed[1] for t in self.tenants.values())

    @property
    def bisnp_events(self) -> int:
        """Back-invalidates observed across every enrolled host."""
        return sum(rt.bisnp_seen for rt in self.fabric.runtimes.values())

    def cache_stats(self) -> dict:
        """Aggregate PermCache counters over the fabric's hosts."""
        hits = sum(int(rt.permcache.hits)
                   for rt in self.fabric.runtimes.values())
        misses = sum(int(rt.permcache.misses)
                     for rt in self.fabric.runtimes.values())
        total = hits + misses
        return {"hits": hits, "misses": misses,
                "hit_rate": hits / total if total else 0.0}

    def view_stats(self) -> dict:
        """Aggregate view-memo counters (kernel-operand derivation): the
        fabric's stacked-view memo plus each host's per-tenant ShardView
        cache behind it — plus the control-plane health counters the bus
        used to swallow (`error_count` is total handler failures ever;
        `stalls` is fail-closed desync ticks absorbed by the engine)."""
        return {
            "rebuilds": self.fabric.view_rebuilds
            + sum(rt.views.rebuilds for rt in self.fabric.runtimes.values()),
            "reuses": self.fabric.view_reuses
            + sum(rt.views.reuses for rt in self.fabric.runtimes.values()),
            "error_count": self.fm.bus.error_count,
            "stalls": self.stalls,
        }

    # -- tenancy ---------------------------------------------------------------
    def add_tenant(self, name: str, host_id: int) -> Tenant:
        """Admission through the fabric: allocate the KV span inside the
        host's shard (coalescing free list reuses evicted tenants' pages),
        grant it RW to a fresh deployment-unique HWPID (one commit), and
        join the serving loop.  Hosts are multi-tenant — admitting onto an
        occupied host co-locates with its existing tenants."""
        if name in self.tenants:
            raise ValueError(f"tenant {name} already admitted")
        if host_id not in self.fabric.runtimes:
            self.fabric.enroll(host_id)
        kv_bytes = self.batch * self.cap * 64  # page-accounting granularity
        n_pages = max(1, -(-kv_bytes // PAGE_BYTES))
        hwpid, start = self.fabric.admit(host_id, n_pages,
                                         base_p=hash(name) & 0xFFFF)
        self.pool.register_at(
            f"kv:{name}",
            jnp.zeros((n_pages, PAGE_BYTES // 4), jnp.float32),
            start_page=start)
        t = Tenant(name, hwpid, host_id,
                   kv_start_page=start, kv_n_pages=n_pages)
        if self.cfg.experts_held:
            t.routed = jnp.zeros((2,), jnp.int32)
        self.tenants[name] = t
        return t

    def evict_tenant(self, name: str) -> Tenant:
        """Eviction through the fabric: abort in-flight work, revoke every
        grant in ONE commit (index-stable tombstones, one targeted BISnp
        batch), recycle the KV span onto the host's coalescing free list,
        and return the HWPID to the deployment pool."""
        t = self.tenants.pop(name)
        if t.group is not None:
            t.aborted += t.group
            t.group = None
        t.queue.clear()
        self.fabric.evict(t.host_id, t.hwpid)
        self.pool.unregister(f"kv:{name}")
        t.revoked = True
        return t

    def revoke(self, name: str) -> None:
        """Mid-flight revocation: the FM drops the tenant's grants and
        broadcasts the BISnp; the tenant's next KV-page touch faults and
        aborts only its requests (they stay admitted, but powerless) while
        co-resident tenants on the same host keep serving."""
        self.fm.revoke_hwpid(self.tenants[name].hwpid)
        self.tenants[name].revoked = True

    def submit(self, name: str, prompt: np.ndarray) -> None:
        self.tenants[name].queue.append(prompt)

    # -- the serving loop --------------------------------------------------------
    def _kv_pages_for_step(self, t: Tenant) -> jax.Array:
        """Pages this step's KV writes touch (one line per active slot)."""
        b = max(len(t.group or ()), 1)
        off = (t.pos * b + np.arange(b)) * 64 % (t.kv_n_pages * PAGE_BYTES)
        return jnp.asarray(t.kv_start_page + off // PAGE_BYTES, jnp.int32)

    def _start_group(self, t: Tenant, gen: int) -> None:
        group = [t.queue.pop(0) for _ in range(
            min(self.batch, len(t.queue)))]
        plen = max(len(p) for p in group)
        toks = np.full((self.batch, plen), 2, np.int32)
        for i, p in enumerate(group):
            toks[i, :len(p)] = p
        logits, cache = registry.prefill(
            self.cfg, self.params, {"tokens": jnp.asarray(toks)},
            cache_dtype=self.cfg.pdtype, cap=plen + gen)
        t.group = group
        t.cache = cache
        t.out = [list(p) for p in group]
        t.cur = _next_tokens(logits)
        t.last_logits = logits[:, -1]
        t.plen = plen
        t.pos = plen
        t.gen_left = gen

    def _abort_group(self, t: Tenant, fault: int) -> None:
        self.faults += 1
        t.last_fault = fault
        t.aborted += t.group
        t.group = None
        t.cache = None

    def _fused_step_egress(self, active: list) -> list:
        """One batched kernel launch for the whole step: every active
        (tenant, ext) pair becomes one fabric row (per-(host, tenant) row
        layout), ragged batches padded with -1 (denied, zeroed).  Returns
        the per-row fault slices, row-aligned with `active`."""
        assign: dict[int, list[int]] = {}
        for t, _ in sorted(active, key=lambda a: a[0].host_id):
            assign.setdefault(t.host_id, []).append(t.hwpid)
        order = sorted(active, key=lambda a: a[0].host_id)
        bmax = max(int(e.shape[0]) for _, e in order)
        ext = jnp.full((len(order), bmax), -1, jnp.int32)
        for i, (_, e) in enumerate(order):
            ext = ext.at[i, :e.shape[0]].set(e)
        data = jnp.zeros((len(order), bmax), jnp.uint32)
        _, fault = self.fabric.step_egress(data, ext, assign, need=2)
        by_tenant = {t.name: (i, int(e.shape[0]))
                     for i, (t, e) in enumerate(order)}
        out = []
        for t, e in active:
            i, b = by_tenant[t.name]
            out.append(fault[i, :b])
        return out

    def step(self, *, gen: int, only: str | None = None) -> dict:
        """One engine tick: every tenant with work decodes one token.

        Returns {tenant: {"aborted": bool, "fault": int, "retired": int}}
        for tenants that made progress this tick.
        """
        with TraceAnnotation("serve.step"):
            return self._step(gen, only)

    def _step(self, gen: int, only: str | None) -> dict:
        results: dict[str, dict] = {}
        # phase 1: start groups, collect every active tenant's KV touch set
        active: list[tuple[Tenant, jax.Array]] = []
        with TraceAnnotation("serve.gather"):
            for name, t in list(self.tenants.items()):
                if only is not None and name != only:
                    continue
                if self.fabric.runtimes[t.host_id].crashed:
                    # fail-stop host: its tenants stall (queued + in-flight
                    # work held) until rejoin_host brings it back cold
                    if t.queue or t.group is not None:
                        self.stalls += 1
                        t.last_fault = FAULT_DESYNC
                        results[name] = {"aborted": False, "stalled": True,
                                         "fault": FAULT_DESYNC, "retired": 0}
                    continue
                if t.group is None:
                    if not t.queue:
                        continue
                    with TraceAnnotation("serve.prefill", tenant=name):
                        self._start_group(t, gen)
                pages = self._kv_pages_for_step(t)
                ext = pack_ext_addr(
                    jnp.full(pages.shape, t.hwpid, jnp.int32), pages)
                active.append((t, ext))
        if not active:
            return results
        # phase 2: close each involved host's BISnp fence up to the table
        # epoch it is about to check against (no fabric-wide quiesce).
        # Crashed hosts are detached from the bus — nothing to close there
        # (their tenants raise/stall in phase 3/4, not here).
        with TraceAnnotation("serve.fence"):
            for host_id in {t.host_id for t, _ in active}:
                if host_id in self.fm.bus.hosts:
                    self.fm.bus.deliver_until(host_id, self.fm.epoch)
        # phase 3: framework egress check per tenant, through the host's
        # fenced PermCache and resident shard (THE checked egress path).
        # A desynced host answers a uniform FAULT_DESYNC deny here.
        with TraceAnnotation("serve.check"):
            checks = [self.fabric.runtimes[t.host_id].check(
                ext, jnp.ones(ext.shape, bool)) for t, ext in active]
            # device-level egress: one batched launch for all tenants; the
            # kernel's fault lanes must agree with the framework verdicts.
            # Desynced hosts are excluded — their deny is a control-plane
            # stall, not a permission verdict, and the kernel (which only
            # knows the table) cannot be expected to reproduce it.
            fusable = [(t, e) for t, e in active
                       if not self.fabric.runtimes[t.host_id].desynced] \
                if self.fused_egress else []
            if fusable:
                with TraceAnnotation("serve.egress"):
                    chk_by_name = {t.name: chk
                                   for (t, _), chk in zip(active, checks)}
                    for (t, _), kfault in zip(
                            fusable, self._fused_step_egress(fusable)):
                        chk = chk_by_name[t.name]
                        self.host_reads += 1
                        if not bool(jnp.all((kfault > 0) == ~chk.allowed)):
                            raise AssertionError(
                                "fused kernel and cached checker disagree "
                                f"for tenant {t.name}")
        # phase 4: enforce verdicts, decode survivors
        for (t, _), chk in zip(active, checks):
            with TraceAnnotation("serve.verdict", tenant=t.name):
                self.host_reads += 1
                allowed = bool(chk.allowed.all())
                if not allowed:
                    self.host_reads += 1
                    fault = int(np.asarray(chk.fault).max())
            if not allowed:
                if fault == FAULT_DESYNC:
                    # fail-closed stall: the host lost BISnp events, so it
                    # denies everything until it resyncs.  The in-flight
                    # group is NOT aborted — it stalls in place and retries
                    # next tick; co-resident hosts are untouched.
                    self.stalls += 1
                    t.last_fault = fault
                    results[t.name] = {"aborted": False, "stalled": True,
                                       "fault": fault, "retired": 0}
                    continue
                # response-side enforcement: the denied KV lines read as
                # zero and the tenant's in-flight group aborts
                self._abort_group(t, fault)
                results[t.name] = {"aborted": True, "stalled": False,
                                   "fault": fault, "retired": 0}
                continue
            # the token fed this tick is the one served: its KV line is
            # what the check above released.  One read of the copy started
            # when the token was made; rows past the group are padding
            with TraceAnnotation("serve.emit", tenant=t.name):
                for out, tok in zip(t.out, np.asarray(t.cur)[:, 0].tolist()):
                    out.append(tok)
                self.token_transfers += 1
                self.host_reads += len(t.group)
            with TraceAnnotation("serve.decode", tenant=t.name):
                pos = jnp.asarray(t.pos, jnp.int32)
                if t.routed is None:
                    logits, t.cache = self._decode(
                        self.params, t.cache, t.cur, pos)
                else:
                    logits, t.cache, t.routed = self._decode(
                        self.params, t.cache, t.cur, pos, t.routed)
                t.cur = _next_tokens(logits)
                t.last_logits = logits[:, -1]
                t.pos += 1
                t.gen_left -= 1
                self.steps += 1
                retired = 0
                if t.gen_left == 0:
                    t.done += [(g, o[len(g):])
                               for g, o in zip(t.group, t.out)]
                    retired = len(t.group)
                    t.group = None
                    t.cache = None
            results[t.name] = {"aborted": False, "stalled": False,
                               "fault": FAULT_NONE, "retired": retired}
        return results

    def has_work(self, only: str | None = None) -> bool:
        for name, t in self.tenants.items():
            if only is not None and name != only:
                continue
            if t.queue or t.group is not None:
                return True
        return False

    def run(self, *, gen: int, max_steps: int | None = None) -> dict:
        """Drive the continuous loop until every queue drains (or
        max_steps).  Returns per-tenant retirement/abort counts."""
        ticks = 0
        while self.has_work() and (max_steps is None or ticks < max_steps):
            self.step(gen=gen)
            ticks += 1
        return {name: {"served": len(t.done), "aborted": len(t.aborted)}
                for name, t in self.tenants.items()}

    def run_tenant(self, name: str, gen: int) -> dict:
        """Decode all queued prompts for one tenant, `gen` tokens each
        (single-tenant drain of the continuous loop)."""
        t = self.tenants[name]
        served0 = len(t.done)
        while self.has_work(only=name):
            out = self.step(gen=gen, only=name).get(name)
            if out and out["aborted"]:
                return {"tenant": name, "served": len(t.done) - served0,
                        "aborted": True, "fault": out["fault"]}
        return {"tenant": name, "served": len(t.done) - served0,
                "aborted": False}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list(ARCHS))
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ARCHS[args.arch] if args.preset == "full" \
        else smoke_config(ARCHS[args.arch])
    params = registry.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params, batch=args.batch,
                         cap=args.prompt_len + args.gen)

    rng = np.random.default_rng(0)
    # co-resident tenants: a and b share host 0 (multi-tenant data plane)
    engine.add_tenant("tenant-a", host_id=0)
    engine.add_tenant("tenant-b", host_id=0)
    for i in range(args.requests):
        who = "tenant-a" if i % 2 == 0 else "tenant-b"
        engine.submit(who, rng.integers(3, cfg.vocab - 1, args.prompt_len))

    t0 = time.time()
    res = engine.run(gen=args.gen)
    dt = time.time() - t0
    print(f"continuous run: {res}")
    tok = engine.steps * args.batch
    cs = engine.cache_stats()
    print(f"{engine.steps} decode steps, ~{tok/dt:,.0f} tok/s, "
          f"faults={engine.faults}, bisnp={engine.bisnp_events}, "
          f"perm-cache hit rate {cs['hit_rate']:.2f}")

    # live revocation: tenant-a loses access mid-service while its
    # co-resident neighbor on the same host keeps serving
    engine.submit("tenant-a", rng.integers(3, cfg.vocab - 1, args.prompt_len))
    engine.submit("tenant-b", rng.integers(3, cfg.vocab - 1, args.prompt_len))
    engine.revoke("tenant-a")
    ra2 = engine.run_tenant("tenant-a", args.gen)
    assert ra2["aborted"], "revoked tenant must fault at the KV egress check"
    rb2 = engine.run_tenant("tenant-b", args.gen)
    assert not rb2["aborted"], "co-resident tenant must keep serving"
    print(f"after revocation: {ra2} (isolation enforced; "
          f"co-resident {rb2['tenant']} served {rb2['served']})")

    # churn: evict the revoked tenant, admit a replacement reusing its pages
    evicted = engine.evict_tenant("tenant-a")
    fresh = engine.add_tenant("tenant-c", host_id=0)
    print(f"evicted {evicted.name} (pages [{evicted.kv_start_page},"
          f"+{evicted.kv_n_pages})); admitted {fresh.name} at "
          f"[{fresh.kv_start_page},+{fresh.kv_n_pages})")
    engine.submit("tenant-c", rng.integers(3, cfg.vocab - 1, args.prompt_len))
    rc = engine.run_tenant("tenant-c", args.gen)
    assert not rc["aborted"]
    print(f"replacement tenant served: {rc}")


if __name__ == "__main__":
    main()
