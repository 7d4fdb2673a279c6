"""The program's spans in a traced serving run at test size: every phase
of `ServeEngine.step` nests inside the benchmark's step span, idle time
goes to the innermost program span (`bench/tools/phases.py`), and the
engine's host-read counter matches the reads the step makes."""
from __future__ import annotations

import functools
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace
from bench.tests import tiny
from bench.tools import phases

PHASES = ("serve.step", "serve.gather", "serve.prefill", "serve.fence",
          "serve.check", "serve.egress", "serve.verdict", "serve.emit",
          "serve.decode")
# blocking reads a tick: each tenant's cross-check and verdict, and one
# read per served token (tiny 8t: 8 tenants of 2; tiny 1t: 1 of 4)
READS_PER_TICK = {"qwen05b-serve-8t": 8 * (2 + 2),
                  "qwen05b-serve-1t": 1 * (2 + 4)}
WARMUP_TICKS = 2


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.mark.parametrize("workload", sorted(READS_PER_TICK))
def test_a_traced_run_nests_every_phase_in_the_benchmark_step(
        workload, monkeypatch, tmp_path):
    got = {}

    def keep(pb_path):
        got["data"] = phases.extract(pb_path)
        return extract(pb_path)

    extract = trace.extract
    monkeypatch.setattr(trace, "extract", keep)
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    out = tiny.run(workload, seconds=1.0, trace=True)
    assert out["correct"] is True
    spans = got["data"]["spans"]
    assert set(PHASES) <= {s[0] for s in spans}
    steps = [s for s in spans if s[0] == "bench.serve_step"]
    ticks = [s for s in spans if s[0] == "serve.step"]
    assert len(ticks) == len(steps) >= out["counters"]["ticks"]
    for s in spans:
        if s[0].startswith("serve."):
            assert any(_inside(s, b) for b in steps), s
            assert any(_inside(s, t) for t in ticks), s


@pytest.mark.parametrize("workload", sorted(READS_PER_TICK))
def test_the_engine_counts_its_host_reads(workload):
    engines = []
    c = tiny.cell(workload)
    drive = functools.partial(harness.load_driver(c.traffic).run,
                              tamper=engines.append)
    out = harness.run_cell(workload, 5_000_000_017, 1.0, False,
                           require_accel=False, cell=c,
                           driver=SimpleNamespace(run=drive),
                           peaks=tiny.PEAKS)
    ticks = out["counters"]["ticks"]
    assert ticks > 0
    assert engines[0].host_reads == \
        READS_PER_TICK[workload] * (ticks + WARMUP_TICKS)


def nested():
    """Window [0, 1000) ns; the device busy in [0, 100), [130, 150),
    [330, 380), [720, 800) and [900, 1000); one tick with every phase
    and control-plane spans nested in them."""
    spans = [["bench.window", 0, 1000], ["bench.serve_step", 100, 800],
             ["serve.step", 110, 780],
             ["serve.gather", 110, 90], ["serve.prefill", 120, 50],
             ["serve.fence", 200, 30], ["bus.deliver", 205, 20],
             ["host.on_bisnp", 210, 10],
             ["serve.check", 230, 170], ["host.shard_extract", 240, 20],
             ["serve.egress", 300, 100], ["fabric.view", 300, 20],
             ["serve.verdict", 400, 50], ["serve.emit", 450, 250],
             ["serve.decode", 700, 100]]
    ops = [["a", "m", 0, 100], ["b", "m", 130, 20], ["c", "m", 330, 50],
           ["d", "m", 720, 80], ["e", "m", 900, 100]]
    return {"devices": [ops], "spans": spans}


def test_idle_goes_to_the_innermost_program_span():
    r = trace.Reduced(nested())
    idle = r.idle_by_span()
    want = {"bench.serve_step": 20, "serve.step": 90, "serve.gather": 40,
            "serve.prefill": 30, "serve.fence": 10, "bus.deliver": 10,
            "host.on_bisnp": 10, "serve.check": 50,
            "host.shard_extract": 20, "serve.egress": 30,
            "fabric.view": 20, "serve.verdict": 50, "serve.emit": 250,
            "serve.decode": 20}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


@pytest.mark.parametrize("group,idle_ns", [
    # fence, delivery, snoop, check, extraction, view, egress, verdict
    ("check", 10 + 10 + 10 + 50 + 20 + 20 + 30 + 50),
    ("emit", 250),
    ("prefill", 30),
])
def test_each_idle_group_reads_its_spans(group, idle_ns):
    # window 1000 ns: a tenth of a percent per nanosecond of idle
    got = phases.phases(nested())["idle_groups"][group]
    assert got == pytest.approx(idle_ns / 10)


def test_scope_paths_come_from_the_op_metadata(tmp_path):
    """`op_scopes` reads the "tf_op" stat of each device operation's
    metadata, as a string or as a reference to a stat name."""
    space = phases._xspace_subset()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in [(1, "tf_op"), (2, "flops"),
                      (3, "jit(f)/mlp/add:")]:
        dev.stat_metadata.add(key=key).value.name = name
    fusion = dev.event_metadata.add(key=10).value
    fusion.name = "%fusion.1 = f32[8] fusion(f32[8] %p)"
    fusion.stats.add(metadata_id=2, str_value="64")
    fusion.stats.add(metadata_id=1,
                     str_value="jit(f)/attention/dot_general:")
    dev.event_metadata.add(key=11).value.name = "%copy.2 = f32[8] copy()"
    add = dev.event_metadata.add(key=12).value
    add.name = "%add.3 = f32[8] add()"
    add.stats.add(metadata_id=1, ref_value=3)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "serve.step"
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(space.SerializeToString())
    assert phases.op_scopes(pb) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(f32[8] %p)":
            "jit(f)/attention/dot_general:",
        "%add.3 = f32[8] add()": "jit(f)/mlp/add:"}}


def test_the_top_ops_carry_their_scope_and_hlo_head():
    data = nested()
    data["devices"][0][3] += ["jit(f)/while/body/attention/dot_general:"]
    data["op_text"] = {"d": "%d = bf16[4,8] fusion()"}
    detail = {row[0]: row[2:] for row in phases.top_ops(data, 5)}
    assert detail["m/d"] == ["jit(f)/while/body/attention/dot_general:",
                             "%d = bf16[4,8] fusion()"]
    assert detail["m/a"] == ["", ""]
