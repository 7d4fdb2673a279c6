"""The program's own trace: control-plane spans of one revocation, in the
order the work happens, and the names the device trace gives the decode
step, the model's layers and the permission-cache probe."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.core import ShardedFabric, pack_ext_addr
from repro.core.checker import cached_check_access, make_perm_cache
from repro.core.table import PermissionTable
from repro.launch.serve import ServeEngine
from repro.models import registry

CONTROL_PLANE = ("fm.", "bus.", "host.", "fabric.")


def _host_spans(trace_dir, prefixes):
    """[(name, start_ns, end_ns)] of the host events under `prefixes` in
    the newest trace written under `trace_dir`, in start order."""
    f = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(f)
    out = [(e.name, e.start_ns, e.end_ns)
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(prefixes)]
    return sorted(out, key=lambda s: s[1])


def test_one_evict_traces_commit_delivery_snoop_and_extraction(tmp_path):
    fabric = ShardedFabric(1 << 12, 64, n_shards=2)
    rt = fabric.enroll(0)
    fabric.enroll(1)
    hwpid, start = fabric.admit(0, 4)
    fabric.fm.bus.quiesce()
    rt.shard_entries()                 # extracted at the admit's epoch
    jax.profiler.start_trace(str(tmp_path))
    fabric.evict(0, hwpid)
    fabric.fm.bus.deliver_until(0, fabric.fm.epoch)
    rt.check(pack_ext_addr(jnp.full((4,), hwpid, jnp.int32),
                           jnp.arange(start, start + 4, dtype=jnp.int32)),
             jnp.zeros((4,), bool))
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, CONTROL_PLANE)
    first = {}
    for name, s0, _ in spans:
        first.setdefault(name, s0)
    order = ["fm.commit", "bus.deliver", "host.on_bisnp",
             "host.shard_extract"]
    assert set(order) <= set(first), spans
    assert [first[n] for n in order] == sorted(first[n] for n in order)
    # the snoop runs inside its delivery; host 1 delivered nothing
    (deliver,) = [s for s in spans if s[0] == "bus.deliver"]
    (snoop,) = [s for s in spans if s[0] == "host.on_bisnp"]
    assert deliver[1] <= snoop[1] and snoop[2] <= deliver[2]


def test_a_delivery_of_nothing_writes_no_span(tmp_path):
    fabric = ShardedFabric(1 << 12, 64, n_shards=1)
    fabric.enroll(0)
    fabric.fm.bus.quiesce()
    jax.profiler.start_trace(str(tmp_path))
    assert fabric.fm.bus.deliver_until(0, fabric.fm.epoch) == 0
    assert fabric.fm.bus.deliver(0) == 0
    with jax.profiler.TraceAnnotation("fm.marker"):
        pass
    jax.profiler.stop_trace()
    assert [s[0] for s in _host_spans(tmp_path, CONTROL_PLANE)] == \
        ["fm.marker"]


def test_the_decode_step_and_the_model_layers_carry_their_names():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = registry.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params, batch=2, cap=8)
    cache = registry.model_module(cfg).init_cache(cfg, 2, 8, cfg.pdtype)
    lowered = engine._decode.lower(params, cache,
                                   jnp.zeros((2, 1), jnp.int32),
                                   jnp.asarray(3, jnp.int32))
    text = lowered.as_text(debug_info=True)
    assert "jit_serve_decode" in text
    # op locations name their scope path: "attention/kv_write/add"
    for scope in ('"attention/', '"attention/kv_write/', '"mlp/'):
        assert scope in text, scope


def test_the_permission_cache_probe_carries_its_name():
    n = 8
    table = PermissionTable(
        starts=jnp.arange(n, dtype=jnp.int32) * 4,
        sizes=jnp.full((n,), 4, jnp.int32),
        perms=jnp.zeros((n, 1), jnp.uint32), meta=jnp.zeros((n,), jnp.uint32),
        n=jnp.asarray(n, jnp.int32), epoch=0)
    ext = pack_ext_addr(jnp.full((4,), 1, jnp.int32),
                        jnp.arange(4, dtype=jnp.int32))
    text = jax.jit(cached_check_access).lower(
        table, jnp.zeros((4,), jnp.uint32), ext, jnp.zeros((4,), bool),
        make_perm_cache(1 << 14, epoch=0)).as_text(debug_info=True)
    assert "permcache_probe" in text


def test_served_tokens_leave_the_chip_in_one_transfer_a_tenant():
    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = registry.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params, batch=2, cap=12, fused_egress=True)
    rng = np.random.default_rng(0)
    for name, host, n in (("a", 0, 2), ("b", 0, 2), ("c", 1, 1)):
        engine.add_tenant(name, host_id=host)
        for _ in range(n):
            engine.submit(name, rng.integers(3, cfg.vocab - 1, 8))

    def tick():
        reads, moved = engine.host_reads, engine.token_transfers
        res = engine.step(gen=4)
        return (res, engine.token_transfers - moved,
                engine.host_reads - reads)

    # every tenant: cross-check, verdict and one read a served token
    _, moved, reads = tick()
    assert (moved, reads) == (3, (2 + 2) + (2 + 2) + (2 + 1))
    engine.revoke("a")
    engine.fabric.crash_host(1)
    res, moved, reads = tick()
    assert res["a"]["aborted"] and res["c"]["stalled"]
    # b alone serves; the denied a reads its cross-check, verdict and fault
    assert (moved, reads) == (1, (2 + 2) + 3)
    engine.fabric.rejoin_host(1)
    res, moved, reads = tick()
    assert not res["c"]["stalled"] and "a" not in res
    assert (moved, reads) == (2, (2 + 2) + (2 + 1))
