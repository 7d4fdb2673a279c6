"""The reduction from a device trace to busy time, idle share, kernel
time and idle gaps by span, on a small trace recorded on the chip."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny
from bench.trace import Reduced, short_op_name, union_length

DATA = Path(__file__).resolve().parent / "data"


def test_union_of_overlapping_intervals():
    total, merged = union_length([(0, 10), (5, 12), (20, 25), (25, 26)])
    assert total == 18
    assert merged == [(0, 12), (20, 26)]


def test_short_op_names():
    assert short_op_name(
        '%_fabric_egress_impl.1 = (u32[127,64,128]{2,1,0}, s32[127,64,128]'
        '{2,1,0}) custom-call(s32[127]{0} %a), custom_call_target="tpu_cus'
        'tom_call", x') == "_fabric_egress_impl.1 custom-call:tpu_custom_call"
    assert short_op_name("%copy.24 = u32[16,8]{1,0} copy(u32[16,8] %b)") \
        == "copy.24 copy"
    assert short_op_name("fusion.3") == "fusion.3"


def synthetic():
    # window [100, 200); ops on one device; spans: a step with a nested
    # commit inside it
    return {"devices": [[["k.1 custom-call:tpu_custom_call", "jit_k", 110, 20],
                         ["f.2 fusion", "jit_k", 120, 20],
                         ["f.3 fusion", "jit_g", 170, 10],
                         ["early", "jit_g", 50, 60]]],
            "spans": [["bench.window", 100, 100],
                      ["bench.step", 140, 40],
                      ["bench.commit", 145, 10]]}


def test_busy_idle_and_blame_on_a_synthetic_trace():
    r = Reduced(synthetic())
    assert r.window_s == pytest.approx(100e-9)
    # busy: [100,110) from the clipped early op, [110,140), [170,180)
    assert r.busy_s == pytest.approx(50e-9)
    # [110, 130) and [120, 140) overlap: the union counts once
    assert r.op_time(lambda n, m: m == "jit_k") == pytest.approx(30e-9)
    assert r.op_count(lambda n, m: n.endswith("tpu_custom_call")) == 1
    assert r.idle_gaps() == [(140, 170), (180, 200)]
    idle = r.idle_by_span()
    assert idle["bench.commit"] == pytest.approx(10e-9)
    assert idle["bench.step"] == pytest.approx(20e-9)
    assert idle["none"] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "jit_g/early"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_chip_trace():
    """A slice of a fabric-255h-egress trace taken on one TPU v5e."""
    data = json.load(gzip.open(DATA / "trace_egress.json.gz", "rt"))
    r = Reduced(data)
    assert 0 < r.busy_s <= r.window_s
    idle = r.idle_by_span()
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s,
                                               rel=1e-9, abs=1e-12)
    read = harness.load_metric_reader("egress_roofline.fabric")
    cell = tiny.cell("fabric-255h-egress")
    mctx = harness.MetricContext(
        cell, {"bytes_per_launch": 22889972}, {}, [], r,
        harness.load_peaks("TPU v5 lite"), r.window_s)
    share = read(mctx)
    assert 0 < share < 100
    idle_share = harness.load_metric_reader("idle_share.fabric")(mctx)
    assert 0 <= idle_share < 100
