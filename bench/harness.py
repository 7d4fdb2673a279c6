"""The benchmark's harness: finds a cell's files by name, holds the run's
context (seed, window, spans, trace), reduces the trace through the
per-layer metric readers and prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name `BENCHMARK.json`
gives it:

    bench/configs/<config>.json    sizes, source, reduced, assumed
    bench/traffic/<traffic>.json   parameters; "driver" names the general
                                   generator in bench/drivers/<driver>.py
    bench/metrics/<metric>.py      a reader: read(ctx) -> float | None

A driver module exposes ``run(ctx) -> dict`` (see `Context`).  Adding a
cell, a mix or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = Path("chiprun_out") / "bench"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- finding a cell's files by name -------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list          # entries of BENCHMARK.json that this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, root: Path = ROOT,
                 bench: dict | None = None) -> Cell:
    """The workload entry `name` of `bench` (by default the checkout's
    BENCHMARK.json) with its configuration and traffic files read, and the
    metrics that it reports."""
    bench = bench or load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def _load_module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(traffic: dict, root: Path = ROOT):
    name = traffic["driver"]
    return _load_module(root / "bench" / "drivers" / f"{name}.py",
                        f"bench_driver_{name}")


def load_metric_reader(name: str, root: Path = ROOT):
    """The `read(ctx)` function of bench/metrics/<name>.py."""
    mod = _load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    """Peaks of one chip by `device_kind`; an unknown kind is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table['chips'])}")
    return table["chips"][kind]


# -- seeds ---------------------------------------------------------------------

def seed_words(seed: int, n: int, salt: str = "") -> list[int]:
    """`n` 32-bit words drawn from any whole-number seed (seeds may exceed
    32 bits); `salt` separates the streams of different uses."""
    entropy = [int(seed) & ((1 << 128) - 1)] + [ord(c) for c in salt]
    ss = np.random.SeedSequence(entropy)
    return [int(x) for x in ss.generate_state(n, np.uint32)]


def rng(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, 4, salt))


# -- spans ---------------------------------------------------------------------

class Spans:
    """The benchmark's own spans around each call it makes into a layer.
    Host-clock intervals are kept in memory; while a trace is on, each
    span is also a `jax.profiler.TraceAnnotation`, so that device gaps in
    the trace can be blamed on the span that was open."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation, as numpy's
    default; over every value given."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as `statistics.quantiles(n=4)`."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


# -- the run's context ----------------------------------------------------------

@dataclass
class Context:
    """What a driver sees.  A driver builds and warms up its system, calls
    `begin_window()`, drives the timed path until `deadline()`, calls
    `end_window()`, then checks what the window produced and returns

        {"metrics": {name: value}, "attempted": int, "failed": int,
         "checks": {name: (value, limit)}, "counters": {...}}

    where a check passes when value <= limit."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT
    out_dir: Path | None = None
    t_start: float = field(default_factory=time.perf_counter)
    spans: Spans = field(default_factory=Spans)
    t_window: tuple[float, float] | None = None
    setup_s: float | None = None
    memory_peak_bytes: int = 0
    trace_dir: Path | None = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def rng(self, salt: str) -> np.random.Generator:
        return rng(self.seed, salt)

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def begin_window(self) -> None:
        import jax
        _compile_listener.counting = True
        if self.trace:
            self.trace_dir = self.out_dir / "trace"
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._window_span = jax.profiler.TraceAnnotation(
                "bench.window")
            self._window_span.__enter__()
            self.spans.annotate = True
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        self.log(f"set-up {self.setup_s:.3f} s: " + ", ".join(
            f"{n} {b - a:.3f} s" for n, a, b in self.spans.records
            if n.startswith("setup.")))
        self.t_window = (t0, math.nan)
        self._deadline = t0 + self.seconds

    def deadline(self) -> float:
        return self._deadline

    def end_window(self) -> float:
        """Close the window (after the driver has waited for its last
        result); returns its length in seconds and reads the peak device
        memory before any checking runs."""
        import jax
        t1 = time.perf_counter()
        self.t_window = (self.t_window[0], t1)
        _compile_listener.counting = False
        if self.trace:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.spans.annotate = False
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()[:self.cell.chips])
        return t1 - self.t_window[0]

    @property
    def window_s(self) -> float:
        return self.t_window[1] - self.t_window[0]


class _CompileListener:
    """Counts programs lowered for the backend, and persistent-cache hits
    among them, while a window is open."""

    def __init__(self):
        self.counting = False
        self.count = 0
        self.cache_hits = 0
        self.installed = False

    def install(self) -> None:
        if not self.installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(self)
            jax.monitoring.register_event_listener(self.on_event)
            self.installed = True

    def __call__(self, event: str, _secs: float, **_kw) -> None:
        if self.counting and \
                event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def on_event(self, event: str, **_kw) -> None:
        if self.counting and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_compile_listener = _CompileListener()


# -- per-layer metrics -----------------------------------------------------------

@dataclass
class MetricContext:
    """What a per-layer reader sees: the cell, the driver's counters and
    end-to-end numbers from the traced run, the benchmark's span records,
    the reduced device trace (`bench.trace.Reduced`, or None) and the
    chip's peaks."""
    cell: Cell
    counters: dict
    metrics: dict
    spans: list
    trace: object
    peaks: dict
    window_s: float


def read_per_layer(cell: Cell, mctx: MetricContext, root: Path = ROOT) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_metric_reader(m["name"], root)(mctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the run --------------------------------------------------------------------

def device_record(cell: Cell, *, require_accel: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_accel and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoAccelerator(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": cell.chips if require_accel else len(devs)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_accel: bool = True,
             cell: Cell | None = None, t_start: float | None = None,
             driver=None, peaks: dict | None = None) -> dict:
    """One run of one cell: set-up, window, checks, per-layer readers.
    Returns the result object (the last line the CLI prints)."""
    cell = cell or resolve_cell(name, root)
    device = device_record(cell, require_accel=require_accel)
    if peaks is None:
        peaks = load_peaks(device["kind"], root)
    out_dir = root / OUT_DIR / f"{cell.name}-{seed}"
    if trace:
        out_dir.mkdir(parents=True, exist_ok=True)
    ctx = Context(cell, seed, seconds, trace, root=root, out_dir=out_dir)
    if t_start is not None:
        ctx.t_start = t_start
    driver = driver or load_driver(cell.traffic, root)
    _compile_listener.install()
    compiles0 = _compile_listener.count
    hits0 = _compile_listener.cache_hits
    res = driver.run(ctx)
    # JAX records a compile event for every program it lowers, also when
    # the persistent cache then supplies the executable: only the rest
    # compiled in the window
    hits = _compile_listener.cache_hits - hits0
    res.setdefault("counters", {}).update(
        compiles_in_window=_compile_listener.count - compiles0 - hits,
        cache_loads_in_window=hits)

    metrics = {}
    e2e = {m["name"]: m for m in cell.end_to_end}
    values = dict(res["metrics"], setup_s=ctx.setup_s)
    for mname, m in e2e.items():
        if mname not in values:
            raise RuntimeError(f"driver gave no {mname} for {cell.name}")
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    out = {"correct": None, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if trace:
        from bench import trace as trace_mod
        reduced = trace_mod.reduce_dir(ctx.trace_dir)
        in_window = [r for r in ctx.spans.records
                     if ctx.t_window[0] <= r[1] <= ctx.t_window[1]]
        mctx = MetricContext(cell, res.get("counters", {}), values,
                             in_window, reduced, peaks, ctx.window_s)
        metrics = read_per_layer(cell, mctx, root)
        device["busy_s"] = reduced.busy_s if reduced else 0.0
        device["window_s"] = ctx.window_s
        if reduced is not None:
            out["breakdown"] = reduced.breakdown()
        (out_dir / "spans.json").write_text(json.dumps(
            [[n, a - ctx.t_window[0], b - a] for n, a, b
             in ctx.spans.records]))
    else:
        metrics = {k: {"value": float(values[k]), "unit": e2e[k]["unit"]}
                   for k in e2e}
    checks = {k: {"value": float(v), "limit": float(lim)}
              for k, (v, lim) in res["checks"].items()}
    out["correct"] = bool(res.get("ok", True)) and all(
        c["value"] <= c["limit"] for c in checks.values())
    out["metrics"] = metrics
    out["device"] = device
    out["counters"] = res.get("counters", {})
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
