"""Reduction of a JAX profiler trace to the numbers the per-layer readers
use: device busy time (the union of the intervals in which an operation
ran on the device), the device time of named operations, and the idle
gaps blamed on the benchmark span that was open on the host.

`extract` reads an ``.xplane.pb`` into plain lists, so that the reduction
itself (`Reduced`) can be checked on a small recorded trace kept as JSON.
"""
from __future__ import annotations

import json
import shutil
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def short_op_name(hlo: str) -> str:
    """``"<instruction> <opcode>"`` from the HLO text the trace gives as an
    operation's name (a custom call also names its target)."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    if rhs.startswith("("):                 # a tuple result type
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    elif " " in rhs:
        rhs = rhs.split(" ", 1)[1]
    op = rhs.split("(", 1)[0]
    if 'custom_call_target="' in hlo:
        op += ":" + hlo.split('custom_call_target="', 1)[1].split('"', 1)[0]
    return f"{lhs.lstrip('%')} {op}"


def _in_modules(ops, modules):
    """The module each op ran in: the module event that covers its start."""
    modules = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, start, dur in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(modules) and modules[j + 1][1] <= start:
            j += 1
        m = modules[j] if modules and modules[j][1] <= start < \
            modules[j][1] + modules[j][2] else None
        out.append([name, m[0] if m else "", start, dur])
    return out


def extract(pb_path: Path) -> dict:
    """{"devices": [[[op, module, start_ns, dur_ns], ...] per device],
    "spans": [[name, start_ns, dur_ns], ...]} from one xplane file: the
    events of each device's "XLA Ops" line, each with the "XLA Modules"
    event that covers it, and the host events that the benchmark's own
    spans wrote."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(pb_path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops = [[short_op_name(e.name), float(e.start_ns),
                    float(e.duration_ns)] for e in lines["XLA Ops"].events]
            mods = [[e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            devices.append(_in_modules(ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"devices": devices, "spans": spans}


def union_length(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of [start, end) intervals, and the merged
    intervals in order."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


class Reduced:
    """A trace reduced to what the readers need.  Times in seconds."""

    def __init__(self, data: dict):
        self.devices = data["devices"]
        self.spans = data["spans"]
        win = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if win:
            self.t0, self.t1 = win[0][1], win[0][1] + win[0][2]
        else:
            ts = [o[2] for d in self.devices for o in d]
            te = [o[2] + o[3] for d in self.devices for o in d]
            self.t0, self.t1 = (min(ts), max(te)) if ts else (0.0, 0.0)
        self._merged = []
        busy = []
        for ops in self.devices:
            total, merged = union_length(
                (max(o[2], self.t0), min(o[2] + o[3], self.t1)) for o in ops
                if o[2] < self.t1 and o[2] + o[3] > self.t0)
            busy.append(total)
            self._merged.append(merged)
        self.busy_s = (sum(busy) / len(busy) / 1e9) if busy else 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def n_ops(self) -> int:
        return sum(len(d) for d in self.devices)

    def op_time(self, match) -> float:
        """Device seconds, averaged over devices, in which an operation
        for which ``match(name, module)`` is true ran: the union of their
        intervals, so that an operation nested in another (the body of a
        while loop) is not counted twice."""
        if not self.devices:
            return 0.0
        tot = sum(union_length((o[2], o[2] + o[3]) for o in d
                               if match(o[0], o[1]))[0]
                  for d in self.devices)
        return tot / len(self.devices) / 1e9

    def op_count(self, match) -> int:
        return sum(1 for d in self.devices for o in d if match(o[0], o[1]))

    def idle_gaps(self) -> list[tuple[float, float]]:
        """Gaps of the first device between busy intervals in the window."""
        if not self._merged:
            return []
        gaps, cur = [], self.t0
        for a, b in self._merged[0]:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        return gaps

    def _span_segments(self) -> list[tuple[float, float, str]]:
        """The window cut at every span edge, each piece labelled with the
        innermost (shortest) benchmark span open over it, or "none"."""
        edges = []
        for name, s0, d in self.spans:
            if name != WINDOW_SPAN:
                edges.append((s0, 1, name, d))
                edges.append((s0 + d, 0, name, d))
        edges.sort(key=lambda e: (e[0], e[1]))
        active: list[tuple[str, float]] = []
        segs, cur = [], self.t0
        for x, is_start, name, d in edges:
            if x > cur:
                label = min(active, key=lambda a: a[1])[0] if active \
                    else "none"
                segs.append((cur, x, label))
                cur = x
            if is_start:
                active.append((name, d))
            elif (name, d) in active:
                active.remove((name, d))
        if cur < self.t1:
            segs.append((cur, self.t1, "none"))
        return segs

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of the first device, split by the innermost
        benchmark span open on the host at the time ("none" where no span
        but the window was open)."""
        out: dict[str, float] = defaultdict(float)
        segs = self._span_segments()
        i = 0
        for a, b in self.idle_gaps():
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                x, y, label = segs[j]
                out[label] += (min(y, b) - max(x, a)) / 1e9
                j += 1
        return dict(out)

    def top_ops(self, n: int = 10) -> list[list]:
        agg: dict[str, float] = defaultdict(float)
        for o in (self.devices[0] if self.devices else []):
            key = f"{o[1]}/{o[0]}" if o[1] else o[0]
            agg[key] += o[3] / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])
                [:n]]

    def breakdown(self) -> dict:
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": self.top_ops(10),
                "idle_gaps": [[k, v] for k, v in idle[:10]]}


def reduce_dir(trace_dir: Path) -> Reduced | None:
    """Reduce the newest xplane file under `trace_dir`, keep a summary of
    it there and delete the raw trace (the profiler's xplane and JSON
    files); None if there is none or it holds no device operation."""
    raw = Path(trace_dir) / "plugins"
    files = sorted(raw.glob("profile/*/*.xplane.pb"))
    data = extract(files[-1]) if files else None
    shutil.rmtree(raw, ignore_errors=True)
    if data is None or not any(data["devices"]):
        return None
    r = Reduced(data)
    (Path(trace_dir) / "summary.json").write_text(json.dumps(
        {"busy_s": r.busy_s, "window_s": r.window_s, "n_ops": r.n_ops,
         **r.breakdown()}))
    return r
