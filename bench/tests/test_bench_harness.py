"""The harness finds every file by name, refuses an unknown chip, and
counts bytes and operations from shapes alone."""
from __future__ import annotations

import json
import shutil

import pytest

from bench import harness
from bench.roofline import decoder_flops_per_token, egress_bytes


def test_every_committed_cell_resolves():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.resolve_cell(w["name"])
        assert cell.traffic["driver"] in ("fabric", "serve")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        harness.load_driver(cell.traffic)
        for m in cell.per_layer:
            assert callable(harness.load_metric_reader(m["name"]))


def test_files_dropped_in_by_name_are_found(tmp_path):
    """A new configuration, traffic mix and metric, added as files and
    entries only, resolve without an edit to any existing file."""
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_benchmark()
    cfg = json.loads((harness.ROOT / "bench/configs/fabric-255h-127p.json")
                     .read_text())
    cfg.update(name="fabric-9h-4p", n_hosts=9, n_tenants=4)
    (tmp_path / "bench/configs/fabric-9h-4p.json").write_text(json.dumps(cfg))
    mix = json.loads((harness.ROOT / "bench/traffic/egress.json").read_text())
    mix["words_per_row"] = 2048
    (tmp_path / "bench/traffic/egress-small.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/launches.fabric.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('launches')\n")
    bench["configs"].append({"name": "fabric-9h-4p", "source": "x",
                             "file": "bench/configs/fabric-9h-4p.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "fabric-9h-small", "config":
                               "fabric-9h-4p", "traffic": "egress-small",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "launches.fabric", "unit": "1",
                               "better": "higher", "source":
                               "program_counter", "layer": "x", "moves":
                               "checked_words_per_s",
                               "workloads": ["fabric-9h-small"]})
    bench["end_to_end"].append({"name": "checked_words_per_s", "unit":
                                "words/s", "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["fabric-9h-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve_cell("fabric-9h-small", tmp_path)
    assert cell.config["n_hosts"] == 9
    assert cell.traffic["words_per_row"] == 2048
    assert [m["name"] for m in cell.per_layer] == ["launches.fabric"]
    assert {m["name"] for m in cell.end_to_end} == {"checked_words_per_s",
                                                    "setup_s"}
    read = harness.load_metric_reader("launches.fabric", tmp_path)
    mctx = harness.MetricContext(cell, {"launches": 7}, {}, [], None, {}, 1)
    assert read(mctx) == 7
    assert harness.load_driver(cell.traffic, tmp_path).run


def test_unknown_device_kind_is_refused():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")


def test_egress_bytes_of_the_fabric_call():
    # 127 rows x 8192 words: data and ext in, out and fault out (16 B a
    # lane); 127 resident shards of 4097 entries, 12 B an entry
    assert egress_bytes(127, 8192, [4097] * 127) == \
        127 * 8192 * 16 + 127 * 4097 * 12
    assert egress_bytes(1, 1024, [0]) == 16 * 1024


def test_decoder_flops_of_qwen():
    cfg = json.loads((harness.ROOT / "bench/configs/qwen1.5-0.5b.json")
                     .read_text())
    per_layer = 2 * (1024 * 64 * 64 + 3 * 1024 * 2816)
    head = 2 * 1024 * 151936
    assert decoder_flops_per_token(cfg, 0) == 24 * per_layer + head
    # attention over the cache adds 4 * d flops per cached position
    assert decoder_flops_per_token(cfg, 10) - \
        decoder_flops_per_token(cfg, 0) == 24 * 4 * 1024 * 10


def test_seed_words_take_large_seeds():
    a = harness.seed_words(2**31 + 12345, 4, "x")
    assert a == harness.seed_words(2**31 + 12345, 4, "x")
    assert a != harness.seed_words(2**31 + 12346, 4, "x")
    assert a != harness.seed_words(2**31 + 12345, 4, "y")
    assert all(0 <= w < 2**32 for w in a)


def test_quartile_spread():
    assert harness.quartile_spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert harness.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
