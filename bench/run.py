#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up every shape the cell's traffic uses (all of it counts as
set-up), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON line as the last line of
standard output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the JAX profiler and the
metrics are the cell's per-layer metrics.  A machine whose JAX finds no TPU,
or fewer chips than the cell asks for, gets exit code 2 and no result.
Traces and span records go under ``chiprun_out/bench/``; JAX's persistent
compilation cache is the checkout's ``.jax_cache/`` (or
``$JAX_COMPILATION_CACHE_DIR``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.resolve_cell(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        print(f"cannot resolve workload {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    try:
        import jax
        harness.device_record(cell, require_accel=True)
    except (harness.NoAccelerator, RuntimeError) as e:
        print(f"no accelerator for this cell: {e}", file=sys.stderr)
        return 2
    print(f"JAX imported and the chip found {time.perf_counter() - T_START:.3f}"
          f" s after start", file=sys.stderr)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"the system under test is not in this checkout: {e!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    # every program goes to the cache, however fast it compiled, so that
    # only the first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {cache_dir}", file=sys.stderr)

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), root=ROOT, cell=cell,
                           t_start=T_START)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
