"""Cells of the benchmark at test size: the committed workloads with a
tiny configuration and shortened traffic, run on the CPU with the check
for a chip skipped."""
from __future__ import annotations

import functools
import json
from pathlib import Path
from types import SimpleNamespace

from bench import harness

DATA = Path(__file__).resolve().parent / "data"
PEAKS = harness.load_peaks("TPU v5 lite")

# traffic overrides that keep each run to a second or two on the CPU
SHRINK = {
    "fabric": {"words_per_row": 1024, "batches": 2},
    "serve-8t": {"prompt_len": 16, "gen": 6, "batch": 2},
    "serve-1t": {"prompt_len": 16, "gen": 6, "batch": 4},
}


def benchmark() -> dict:
    """BENCHMARK.json with the cells held out of it until their traffic has
    a public source (data/held-cells.json), whose drivers, references and
    readers stay under test."""
    bench = harness.load_benchmark()
    held = json.loads((DATA / "held-cells.json").read_text())
    for key, entries in held.items():
        bench[key] = bench[key] + entries
    return bench


def cell(workload: str) -> harness.Cell:
    real = harness.resolve_cell(workload, bench=benchmark())
    traffic = dict(real.traffic)
    if traffic["driver"] == "fabric":
        config = json.loads((DATA / "tiny-fabric.json").read_text())
        traffic.update(SHRINK["fabric"])
    else:
        config = json.loads((DATA / "tiny-qwen.json").read_text())
        traffic.update(SHRINK["serve-8t" if traffic["tenants"] > 1
                              else "serve-1t"])
    return harness.Cell(real.name, real.chips, config["name"], config,
                        real.traffic_name, traffic, real.end_to_end,
                        real.per_layer)


def run(workload: str, seed: int = 5_000_000_017, seconds: float = 0.5,
        trace: bool = False, **driver_kw) -> dict:
    """One run of the test-size cell; `driver_kw` go to the driver's run()
    (a broken timed path, or the control)."""
    c = cell(workload)
    driver = None
    if driver_kw:
        drive = harness.load_driver(c.traffic).run
        driver = SimpleNamespace(run=functools.partial(drive, **driver_kw))
    return harness.run_cell(workload, seed, seconds, trace,
                            require_accel=False, cell=c, driver=driver,
                            peaks=PEAKS)
