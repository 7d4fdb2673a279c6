"""The fabric cells at test size on the CPU: the generators repeat from
the seed, a run prints the contract's line, and a run whose timed path is
broken underneath, or is the control of `bench/tools/control.py`, is not
correct."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness
from bench.drivers import fabric
from bench.reference import fabric_ref
from bench.tests import tiny
from bench.tools import control

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def layout():
    return fabric.Layout(tiny.cell("fabric-255h-egress").config)


def test_traffic_and_commits_repeat_from_the_seed(layout):
    tr = tiny.cell("fabric-255h-egress").traffic
    a = fabric.make_batches(layout, tr, harness.rng(2**33 + 1, "traffic"))
    b = fabric.make_batches(layout, tr, harness.rng(2**33 + 1, "traffic"))
    c = fabric.make_batches(layout, tr, harness.rng(2**33 + 2, "traffic"))
    assert all(np.array_equal(x, y) for p, q in zip(a, b)
               for x, y in zip(p, q))
    assert not np.array_equal(a[0][1], c[0][1])
    assert fabric.commit_plan(layout, harness.rng(9, "c"), 5) == \
        fabric.commit_plan(layout, harness.rng(9, "c"), 5)


def test_reference_agrees_with_the_kernel_oracle(layout):
    """The plain reference and the program's own oracle give the same
    words and fault codes for every row."""
    import jax.numpy as jnp
    from repro.kernels import ref
    tr = tiny.cell("fabric-255h-egress").traffic
    data, ext = fabric.make_batches(layout, tr, harness.rng(3, "t"))[0]
    out, fault = fabric_ref.check_rows(layout.dep, layout.rows, data, ext,
                                       need=1, key0=171, key1=205)
    view = layout.fab.fabric_view(layout.assign)
    for i, (_, hwpid) in enumerate(layout.rows):
        o, f = ref.checked_memcrypt(
            data[i], ext[i], view.starts[i], view.ends[i], view.permbits[i],
            hwpid=jnp.int32(hwpid), need=1, key0=171, key1=205,
            base_word=jnp.uint32(i * data.shape[1]))
        assert np.array_equal(out[i], np.asarray(o))
        assert np.array_equal(fault[i], np.asarray(f))
    assert set(np.unique(fault)) == {0, 1, 2, 3, 4}


@pytest.mark.parametrize("workload,seconds", [("fabric-255h-egress", 0.5),
                                              ("fabric-255h-churn", 2.5)])
def test_a_run_prints_the_contract_line(workload, seconds, capsys):
    out = tiny.run(workload, seconds=seconds)
    harness.print_result(out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [k for k in KEYS if k in line] == KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["checks"]["mismatched_lanes"] == {"value": 0.0, "limit": 0.0}
    assert set(line["metrics"]) == {m["name"] for m in
                                    tiny.cell(workload).end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])


def _altered(layout, data, ext):
    out, fault = layout.fab.step_egress(data, ext, layout.assign, need=1,
                                        key0=171, key1=205)
    return out.at[1, 7].set(out[1, 7] ^ 1), fault


def _half_rows(layout, data, ext):
    out, fault = layout.fab.step_egress(data, ext, layout.assign, need=1,
                                        key0=171, key1=205)
    half = out.shape[0] // 2
    return out.at[half:].set(0), fault.at[half:].set(0)


def _stale(layout, data, ext):
    """The view of the first launch, never refreshed: commits are not
    enforced (the step returns its state unchanged)."""
    from repro.kernels.fabric_egress import fabric_egress_pallas
    if not hasattr(layout, "_first_view"):
        layout._first_view = layout.fab.fabric_view(layout.assign)
    return fabric_egress_pallas(data, ext, layout._first_view, need=1,
                                key0=171, key1=205)


@pytest.mark.parametrize("workload,egress", [
    ("fabric-255h-egress", _altered),
    ("fabric-255h-egress", _half_rows),
    ("fabric-255h-churn", _stale),
    ("fabric-255h-egress", control.forged_as_own),
    ("fabric-255h-churn", control.one_launch_late),
])
def test_a_broken_timed_path_is_not_correct(workload, egress):
    out = tiny.run(workload, egress=egress, seconds=2.5)
    assert out["correct"] is False
    assert out["checks"]["mismatched_lanes"]["value"] > 0
