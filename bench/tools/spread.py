#!/usr/bin/env python3
"""Spreads of a cell's measurement sets and the bounds they give.

    python3 bench/tools/spread.py runs.jsonl

Each line of the input is a run's result line, optionally preceded by the
name of its set and a space (``A {...}``, ``B {...}``).  For every
end-to-end metric it prints each set's median and spread (the distance
between the first and third quartile, as ``statistics.quantiles(n=4)``
gives them, over the median), and five times the widest spread, never
under 1%: the bound that rule gives.  ``setup_s`` is held to whether its
median got worse, so its spread is printed and no bound derived.
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.harness import quartile_spread  # noqa: E402


def read(path: str) -> dict[str, list[dict]]:
    sets: dict[str, list[dict]] = defaultdict(list)
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        tag, _, rest = line.partition(" ")
        if tag.startswith("{"):
            tag, rest = "all", line
        sets[tag].append(json.loads(rest))
    return sets


def main() -> int:
    sets = read(sys.argv[1])
    widest: dict[str, float] = defaultdict(float)
    for tag, runs in sorted(sets.items()):
        ok = sum(r["correct"] is True for r in runs)
        print(f"set {tag}: {len(runs)} runs, {ok} correct")
        by_metric = defaultdict(list)
        for r in runs:
            for k, v in r["metrics"].items():
                by_metric[k].append(v["value"])
        for k, vals in sorted(by_metric.items()):
            if len(vals) < 2:
                continue
            sp = quartile_spread(vals)
            if len(vals) >= 4:
                widest[k] = max(widest[k], sp)
            print(f"  {k}: median {statistics.median(vals)!r} spread "
                  f"{sp:.4%} min {min(vals)!r} max {max(vals)!r}")
    for k, sp in sorted(widest.items()):
        if k != "setup_s":
            print(f"bound for {k}: {max(0.01, 5 * sp):.4f} (widest spread "
                  f"{sp:.4%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
