#!/usr/bin/env python3
"""Chip smoke test: the checked serving path and the 255-host fabric egress
step, run once on one TPU through the normal entry points.

    python chip_smoke.py              # on a TPU host: full width, both phases
    python chip_smoke.py --rehearse   # any host: tiny sizes, never prints ok

Phase ``serve`` builds a `ServeEngine` with ``fused_egress=True`` over
qwen1.5-0.5b at its published widths (weights drawn from ``--seed``) and
admits three tenants on two hosts: a and b co-resident on host 0, c on
host 1.  Every tick launches the compiled `fabric_egress` kernel and the
engine asserts that its fault lanes agree with `HostRuntime.check`.
Tenant a is revoked while its second request group is in flight: it must
abort with FAULT_PERM while b and c finish.  Served tokens are checked
against a teacher-forced forward pass of the same model.

Phase ``fabric`` builds a `ShardedFabric` of 255 hosts with 127 tenants,
each host resident for a shared region of 4096 granted ranges (8192-entry
padded shards, 8 summary tiles), pulls 8192 tagged words per tenant row
through one `step_egress` launch and compares every row bit-exactly with
`kernels.ref.checked_memcrypt`, denied lanes and fault codes included.  It
then revokes one tenant and launches again: only that tenant's row may
turn to zero and FAULT_*.

Every check is fatal.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when both phases passed on a TPU;
anything else exits non-zero without it.  One process, no children.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

# served logits are compared with the reference forward pass relative to
# the reference's scale, max|ref|: decode logits by max|engine - ref|, and
# the prefill's greedy token by its gap to the reference's top logit (a
# bf16 near-tie may pick a different argmax; a wrong model misses by O(1)).
# bf16 weights and activations give a few 2**-8 roundings per layer, so
# 24 layers stay well inside this
LOGIT_RTOL = 0.05


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- phase serve --------------------------------------------------------------

def phase_serve(args, jax, np) -> None:
    import jax.numpy as jnp

    from repro.configs import ARCHS, smoke_config
    from repro.core import FAULT_PERM
    from repro.launch.serve import ServeEngine
    from repro.models import registry

    cfg = ARCHS["qwen1.5-0.5b"]
    plen, gen, batch, per_tenant = 128, 16, 4, 8
    if args.rehearse:       # tiny widths, the published dtypes
        cfg = dataclasses.replace(smoke_config(cfg),
                                  param_dtype=cfg.param_dtype)
        plen, gen, batch, per_tenant = 16, 4, 2, 4
    log(f"serve: {cfg.arch_id} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} params={cfg.param_dtype} prompt={plen} "
        f"gen={gen} batch={batch} requests/tenant={per_tenant}")

    t0 = time.perf_counter()
    params = registry.init_params(cfg, jax.random.key(args.seed))
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    engine = ServeEngine(cfg, params, batch=batch, cap=plen + gen,
                         fused_egress=True, n_hosts=2)
    hosts = {"tenant-a": 0, "tenant-b": 0, "tenant-c": 1}
    for name, host in hosts.items():
        engine.add_tenant(name, host_id=host)
    rng = np.random.default_rng(args.seed)
    for name in hosts:
        for _ in range(per_tenant):
            engine.submit(name, rng.integers(3, cfg.vocab - 1, plen))

    # per served group (keyed by its token lists): decode logits per tick
    groups: dict[int, tuple[list, list]] = {}
    tick_s = []
    revoke_after = gen + gen // 4      # a's second group is in flight
    while engine.has_work():
        t0 = time.perf_counter()
        res = engine.step(gen=gen)
        for name, r in res.items():
            t = engine.tenants[name]
            if r["aborted"] or r.get("stalled"):
                continue
            groups.setdefault(id(t.out), (t.out, []))[1].append(
                t.last_logits)
        jax.block_until_ready([t.cur for t in engine.tenants.values()
                               if t.cur is not None])
        tick_s.append(time.perf_counter() - t0)
        if len(tick_s) == revoke_after:
            check(engine.tenants["tenant-a"].group is not None,
                  "tenant-a has no group in flight at revocation")
            engine.revoke("tenant-a")
            log(f"serve: revoked tenant-a after tick {revoke_after}")

    a, b, c = (engine.tenants[n] for n in hosts)
    check(len(a.done) == batch and len(a.aborted) == per_tenant - batch,
          f"revoked tenant: {len(a.done)} done / {len(a.aborted)} aborted")
    check(a.last_fault == FAULT_PERM,
          f"revoked tenant fault {a.last_fault} != FAULT_PERM")
    for t in (b, c):
        check(len(t.done) == per_tenant and not t.aborted,
              f"{t.name}: {len(t.done)} done / {len(t.aborted)} aborted")
    check(engine.faults == 1, f"engine faults {engine.faults} != 1")
    vs = engine.view_stats()
    check(vs["error_count"] == 0, f"bus error_count {vs['error_count']}")
    check(vs["stalls"] == 0, f"stalls {vs['stalls']}")
    log(f"serve: kernel and checker agreed on every tick; tenant-a denied "
        f"(FAULT_PERM, {len(a.aborted)} requests aborted); co-resident "
        f"tenant-b served {len(b.done)}, tenant-c served {len(c.done)}; "
        f"bus errors 0, stalls 0")

    # reference: one teacher-forced forward over prompt + served tokens
    forward = jax.jit(lambda p, tok: registry.model_module(cfg).forward(
        cfg, p, tok)[0])
    served = [(out, lg) for out, lg in groups.values() if len(lg) == gen]
    check(len(served) == (3 * per_tenant - (per_tenant - batch)) // batch,
          f"{len(served)} complete groups")
    worst, first_gap, first_eq, top1 = 0.0, 0.0, 0, 0
    t0 = time.perf_counter()
    for out, logits in served:
        tokens = np.asarray(out, np.int32)            # [B, plen + gen]
        check(tokens.shape == (batch, plen + gen), f"tokens {tokens.shape}")
        ref = forward(params, jnp.asarray(tokens)).astype(jnp.float32)
        ref = ref[:, plen - 1:plen + gen]             # predicts g0..g_gen
        scale = float(jnp.max(jnp.abs(ref)))
        g0 = jnp.asarray(tokens[:, plen])
        first_eq += int(jnp.sum(jnp.argmax(ref[:, 0], -1) == g0))
        gap = jnp.max(ref[:, 0], -1) - jnp.take_along_axis(
            ref[:, 0], g0[:, None], -1)[:, 0]
        first_gap = max(first_gap, float(jnp.max(gap)) / scale)
        eng = jnp.stack(logits, axis=1).astype(jnp.float32)  # [B, gen, V]
        worst = max(worst, float(jnp.max(jnp.abs(eng - ref[:, 1:]))) / scale)
        top1 += int(jnp.sum(jnp.argmax(eng, -1) == jnp.argmax(ref[:, 1:], -1)))
    t_ref = time.perf_counter() - t0
    n_first = len(served) * batch
    n_dec = n_first * gen
    log(f"serve: reference forward over {len(served)} groups (tolerance "
        f"{LOGIT_RTOL} of max|ref|): first token = reference argmax "
        f"{first_eq}/{n_first}, its largest gap to the reference top logit "
        f"{first_gap:.3g}; decode logits max|diff| {worst:.3g}, top-1 "
        f"agreement {top1}/{n_dec}")
    check(first_gap <= LOGIT_RTOL,
          f"first token misses the reference top logit by {first_gap:.3g}")
    check(worst <= LOGIT_RTOL, f"decode logits off by {worst:.3g} of scale")
    log(f"serve: seconds: init {t_init:.2f}, first tick (compiles) "
        f"{tick_s[0]:.2f}, later ticks {sum(tick_s[1:]):.2f} over "
        f"{len(tick_s) - 1}, reference {t_ref:.2f}")


# -- phase fabric -------------------------------------------------------------

def phase_fabric(args, jax, np) -> None:
    import jax.numpy as jnp

    from repro.core import PERM_R, PERM_RW, Proposal, ShardedFabric
    from repro.core.table import HWPID_SHIFT
    from repro.kernels import bucket_pad, ref
    from repro.kernels.memcrypt import BLOCK

    n_hosts, n_tenants, words, chunks = 255, 127, 8192, 4096
    if args.rehearse:
        words, chunks = 1024, 1100
    sdm_pages = 1 << HWPID_SHIFT       # the whole 24-bit page space
    private, stride, chunk = 64, 16, 15
    need, key0, key1 = 1, 0xAB, 0xCD

    t0 = time.perf_counter()
    fab = ShardedFabric(sdm_pages, table_capacity=8192, n_shards=n_hosts)
    for h in range(n_hosts):
        fab.enroll(h)
    tenant_hosts = [p * n_hosts // n_tenants for p in range(n_tenants)]
    tenants = [fab.admit(h, private) for h in tenant_hosts]   # (hwpid, start)
    # shared region in the last host's shard (no tenant there): gapped
    # ranges, each read-granted to one tenant and read/write to another
    shared_lo, _ = fab.shard_range(n_hosts - 1)
    with fab.fm.transaction():
        for c in range(chunks):
            for p, perm in ((c % n_tenants, PERM_R),
                            ((5 * c + 1) % n_tenants, PERM_RW)):
                hwpid = tenants[p][0]
                check(fab.fm.propose(Proposal(
                    tenant_hosts[p], hwpid, 0x2000 + hwpid,
                    shared_lo + c * stride, chunk, perm)) is not None,
                    "FM rejected a shared grant")
    for rt in fab.runtimes.values():
        rt.add_resident_range(shared_lo, chunks * stride)
    fab.quiesce()
    t_setup = time.perf_counter() - t0

    # traffic: each row mostly its own span and its granted shared ranges,
    # plus other shared ranges (FAULT_PERM), gaps and far pages
    # (FAULT_NO_ENTRY), forged tags (FAULT_NOT_LOCAL) and untagged words
    # (FAULT_NO_ABITS)
    rng = np.random.default_rng(args.seed)
    hwpids = np.array([w for w, _ in tenants], np.int32)
    starts = np.array([s for _, s in tenants], np.int64)
    r = np.arange(n_tenants)[:, None]
    cs = np.arange(chunks)
    own = [cs[(cs % n_tenants == p) | ((5 * cs + 1) % n_tenants == p)]
           for p in range(n_tenants)]
    kind = rng.random((n_tenants, words))
    pages = starts[:, None] + rng.integers(0, private, (n_tenants, words))
    granted = np.stack([rng.choice(o, words) for o in own])
    pages = np.where(kind > 0.5, shared_lo + granted * stride
                     + rng.integers(0, chunk, (n_tenants, words)), pages)
    pages = np.where(kind > 0.75, shared_lo + rng.integers(
        0, chunks * stride, (n_tenants, words)), pages)
    pages = np.where(kind > 0.9, rng.integers(0, sdm_pages,
                                              (n_tenants, words)), pages)
    tags = np.broadcast_to(hwpids[:, None], (n_tenants, words)).copy()
    tag_kind = rng.random((n_tenants, words))
    tags = np.where(tag_kind < 0.03, hwpids[(r + 1) % n_tenants], tags)
    tags = np.where(tag_kind > 0.98, 0, tags)
    ext = ((tags.astype(np.int64) << HWPID_SHIFT) | pages).astype(np.int32)
    data = rng.integers(0, 1 << 32, (n_tenants, words), dtype=np.uint32)
    assign = {tenant_hosts[p]: int(hwpids[p]) for p in range(n_tenants)}
    check(fab.fabric_rows(assign) == list(zip(tenant_hosts,
                                              hwpids.tolist())),
          "fabric row order")

    def launch():
        t = time.perf_counter()
        out, fault = fab.step_egress(data, ext, assign, need=need,
                                     key0=key0, key1=key1)
        jax.block_until_ready((out, fault))
        return np.asarray(out), np.asarray(fault), time.perf_counter() - t

    ref_row = jax.jit(lambda d, e, s, en, pb, w, base: ref.checked_memcrypt(
        d, e, s, en, pb, hwpid=w, need=need, key0=key0, key1=key1,
        base_word=base))
    bp = bucket_pad(words, BLOCK)

    def compare(out, fault, label):
        view = fab.fabric_view(assign)
        for i in range(n_tenants):
            o_ref, f_ref = ref_row(data[i], ext[i], view.starts[i],
                                   view.ends[i], view.permbits[i],
                                   jnp.int32(hwpids[i]),
                                   jnp.uint32(i * bp))
            check(np.array_equal(out[i], np.asarray(o_ref)),
                  f"{label}: row {i} words differ from the reference")
            check(np.array_equal(fault[i], np.asarray(f_ref)),
                  f"{label}: row {i} fault codes differ from the reference")
        return view

    out1, fault1, t_first = launch()
    _, _, t_warm = launch()
    view = compare(out1, fault1, "launch")
    codes = np.bincount(fault1.reshape(-1), minlength=5)
    check(all(codes[k] > 0 for k in range(5)),
          f"traffic misses a verdict: fault counts {codes.tolist()}")
    log(f"fabric: {n_hosts} hosts, {n_tenants} tenants, {fab.fm.table.n}"
        f" table entries, rows x words = {n_tenants} x {words}, shard "
        f"entries padded to {view.starts.shape[1]} ({view.tile_min.shape[1]}"
        f" tiles); bit-exact vs ref.checked_memcrypt on every row; lanes "
        f"by fault code NONE/NO_ABITS/NOT_LOCAL/NO_ENTRY/PERM = "
        f"{codes.tolist()}")

    victim = n_tenants // 2
    fab.fm.revoke_hwpid(int(hwpids[victim]))
    fab.quiesce()
    out2, fault2, t_revoked = launch()
    compare(out2, fault2, "after revocation")
    others = np.arange(n_tenants) != victim
    check(not out2[victim].any() and (fault2[victim] > 0).all(),
          "revoked tenant's row still releases words")
    check(np.array_equal(out2[others], out1[others])
          and np.array_equal(fault2[others] == 0, fault1[others] == 0),
          "revocation changed another tenant's released words")
    log(f"fabric: revoked hwpid {hwpids[victim]} (row {victim}): its row is "
        f"all zero with faults on every lane, the other {n_tenants - 1} "
        f"rows release the same words; bit-exact vs ref.checked_memcrypt")
    log(f"fabric: seconds: setup {t_setup:.2f}, first launch (compile + "
        f"view) {t_first:.3f}, warm launch {t_warm:.4f}, launch after "
        f"revocation (view rebuild) {t_revoked:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints the ok "
                         "line")
    args = ap.parse_args()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU found: run on a TPU host, or pass --rehearse",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    failed = []
    for name, phase in (("serve", phase_serve), ("fabric", phase_fabric)):
        t0 = time.perf_counter()
        try:
            phase(args, jax, np)
        except CheckFailed as e:
            failed.append(name)
            log(f"{name}: FAILED: {e}")
        log(f"{name}: {'FAIL' if name in failed else 'PASS'} in "
            f"{time.perf_counter() - t0:.1f} s")
    if failed:
        return 1
    if args.rehearse or dev.platform != "tpu":
        print("rehearsal passed; the ok line is printed only by a full run "
              "on a TPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
