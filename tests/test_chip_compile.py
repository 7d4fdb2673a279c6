"""The main-path Pallas kernels compile for a TPU v5e at real widths, and
the serve decode step compiles there without copying its cache.

Mosaic refuses what interpret mode accepts (value-level dynamic slices,
blocks off the (8, 128) tiling, VMEM or SMEM beyond the chip), so each
kernel is compiled here for a described v5e chip that is not attached.
Nothing runs: these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core.fabric import FabricView
from repro.kernels.fabric_egress import fabric_egress_pallas
from repro.kernels.memcrypt import checked_memcrypt_view_pallas, memcrypt_pallas
from repro.kernels.permcheck import ShardView, permcheck_view_pallas
from repro.launch.serve import ServeEngine
from repro.models import registry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def spec(one_chip, no_persistent_cache):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _shard_view(spec, n_entries):
    n_tiles = n_entries // 1024
    return ShardView(spec((n_entries,), jnp.int32),
                     spec((n_entries,), jnp.int32),
                     spec((n_entries,), jnp.uint32),
                     spec((n_tiles,), jnp.int32),
                     spec((n_tiles,), jnp.int32),
                     spec((), jnp.int32))


def _compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,words,entries", [
    (3, 1024, 1024),        # the served path: 3 tenants, batch 4 x gen 16
    (127, 8192, 8192),      # paper headline: 127 tenants, 8192-entry shards
])
def test_fabric_egress_compiles(spec, rows, words, entries):
    n_tiles = entries // 1024

    def step(data, ext, hwpids, starts, ends, permbits, tmin, tmax):
        view = FabricView(starts, ends, permbits, tmin, tmax, hwpids,
                          host_ids=tuple(range(rows)))
        return fabric_egress_pallas(data, ext, view, need=2, key0=0xAB,
                                    key1=0xCD, interpret=False)

    _compiles(step, spec((rows, words), jnp.uint32),
              spec((rows, words), jnp.int32), spec((rows,), jnp.int32),
              spec((rows, entries), jnp.int32),
              spec((rows, entries), jnp.int32),
              spec((rows, entries), jnp.uint32),
              spec((rows, n_tiles), jnp.int32),
              spec((rows, n_tiles), jnp.int32))


@pytest.mark.parametrize("mode", ["flat", "hier", "adaptive"])
def test_permcheck_view_compiles(spec, mode):
    _compiles(lambda a, v: permcheck_view_pallas(
        a, v, hwpid=3, need=1, mode=mode, interpret=False),
        spec((8192,), jnp.int32), _shard_view(spec, 65536))


def test_checked_memcrypt_view_compiles(spec):
    _compiles(lambda d, a, v: checked_memcrypt_view_pallas(
        d, a, v, hwpid=3, need=1, key0=1, key1=2, interpret=False),
        spec((8192,), jnp.uint32), spec((8192,), jnp.int32),
        _shard_view(spec, 8192))


def test_memcrypt_compiles(spec):
    _compiles(lambda d: memcrypt_pallas(d, key0=1, key1=2, interpret=False),
              spec((1 << 20,), jnp.uint32))


def _copies(hlo: str) -> list[tuple[bool, tuple]]:
    """(inside the body of a while loop, dimensions less leading ones) of
    each copy in `hlo`."""
    bodies = set(re.findall(r"while\(.*?body=(%[\w.\-]+)", hlo))
    found, comp = [], None
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            comp = line.split(" ")[0]
        m = re.match(r"\s+(?:ROOT )?\S+ = \w+\[([\d,]*)\]\S* copy\(", line)
        if m:
            dims = [int(d) for d in filter(None, m.group(1).split(","))]
            while dims and dims[0] == 1:
                dims.pop(0)
            found.append((comp in bodies, tuple(dims)))
    return found


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b",         # 64-wide heads: the cache lies positions-minor
    "glm4-9b",              # GQA
    "deepseek-v2-lite",     # latent cache, a lead layer ahead of the loop
])
def test_the_serve_decode_updates_its_cache_in_place(spec, arch_id):
    """The engine's decode step at published widths (4 scanned layers, a
    small vocabulary and expert count, batch 8, 512 positions): the cache
    is aliased to the output, the layer loop copies no layer's cache, and
    no stacked cache is copied whole: no layer is re-laid-out around its
    write, inside the loop or out of it."""
    full = ARCHS[arch_id]
    cfg = dataclasses.replace(
        full, n_layers=4 + full.first_k_dense, vocab=1024,
        n_experts=min(full.n_experts, 8),
        experts_held=full.experts_held and (0, min(full.n_experts, 8)))
    b, cap = 8, 512
    on_chip = lambda t: jax.tree.map(lambda x: spec(x.shape, x.dtype), t)
    cache = on_chip(registry.cache_shapes(cfg, b, cap, cfg.pdtype))
    args = [on_chip(registry.param_shapes(cfg)), cache,
            spec((b, 1), jnp.int32), spec((), jnp.int32)]
    if cfg.experts_held:
        args.append(spec((2,), jnp.int32))
    compiled = ServeEngine(cfg, None, batch=b, cap=cap)._decode.lower(
        *args).compile()
    leaves = jax.tree.leaves(cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in leaves)
    stacked = {x.shape for x in leaves if x.shape[0] > 1}
    layers = {x.shape[1:] for x in leaves} | stacked
    assert [d for in_loop, d in _copies(compiled.as_text())
            if d in (layers if in_loop else stacked)] == []
