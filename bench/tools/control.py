#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip, at the
cell's own size, several seeds in one process:

    python3 bench/tools/control.py --workload <cell> --seeds 1 2 3 \
        --seconds 30 [--program]

For each seed it runs the cell with its control in the program's place and
prints one JSON line with the numbers compared and whether the run came
out correct (a control must not); with ``--program`` it also runs the
program itself on the same seeds (sound runs).  The controls:

* serving cells: the reference computed in fp8 (float8_e4m3fn operands,
  per-tensor scales), the precision below the bfloat16 the configuration
  states, read at each position of the served tokens; the line also gives
  the program's own gap on those tokens (`program_gap`), a sound reading;
* fabric-255h-egress: the reference with the isolation guarantee broken,
  a word with a forged tag checked as the row's own;
* fabric-255h-churn: the reference with the revocation guarantee broken,
  each launch answered by the grants as they stood at the launch before.
  The two fabric cells are held out of `BENCHMARK.json` until their
  traffic has a public source; the tests run these controls at test size.

The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]


def forged_as_own(layout, data, ext):
    import numpy as np
    from bench.reference import fabric_ref
    d, e = np.asarray(data), np.asarray(ext)
    tag = e >> fabric_ref.HWPID_SHIFT
    hw = np.array([w for _, w in layout.rows], np.int64)[:, None]
    forged = (tag > 0) & (tag != hw)
    e = np.where(forged, (hw << fabric_ref.HWPID_SHIFT)
                 | (e & fabric_ref.PAGE_MASK), e).astype(np.int32)
    return fabric_ref.check_rows(layout.dep, layout.rows, d, e, need=1,
                                 key0=171, key1=205)


def one_launch_late(layout, data, ext):
    import numpy as np
    from bench.reference import fabric_ref
    state = getattr(layout, "_late_state", None) or layout.dep.copy()
    out = fabric_ref.check_rows(state, layout.rows, np.asarray(data),
                                np.asarray(ext), need=1, key0=171, key1=205)
    layout._late_state = layout.dep.copy()
    return out


CONTROLS = {"fabric-255h-egress": {"egress": forged_as_own},
            "fabric-255h-churn": {"egress": one_launch_late}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="also run the program itself on the same seeds")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = harness.resolve_cell(args.workload, ROOT)
    driver = harness.load_driver(cell.traffic, ROOT)
    kw = CONTROLS.get(cell.name, {"control": True})
    runs = [("control", kw)] + ([("program", {})] if args.program else [])
    for seed in args.seeds:
        for label, extra in runs:
            bound = SimpleNamespace(run=functools.partial(driver.run, **extra))
            out = harness.run_cell(cell.name, seed, args.seconds, False,
                                   root=ROOT, cell=cell, driver=bound)
            rec = {"workload": cell.name, "seed": seed, "run": label,
                   "correct": out["correct"], "checks": out["checks"],
                   "device": out["device"]}
            if "program_gap" in out["counters"]:
                rec["program_gap"] = out["counters"]["program_gap"]
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
