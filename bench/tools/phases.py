#!/usr/bin/env python3
"""Device idle time split by the program's own spans, and the top device
operations with their scope paths, from one JAX profiler trace.

    python3 bench/tools/phases.py <trace dir or .xplane.pb>

The benchmark's reduction (`bench.trace.extract`) keeps only the
benchmark's `bench.*` spans, so its traced runs put the serving loop's
idle time down to `bench.serve_step` as a whole.  This tool keeps the
program's spans too (`serve.*` phases of `ServeEngine.step`; `fm.*`,
`bus.*`, `host.*`, `fabric.*` of the control plane;
docs/observability.md) and reduces them with the same `Reduced`, so each
idle gap goes to the innermost phase open over it.  It also reads each
device operation's scope path (the "tf_op" stat of its event metadata,
which `jax.profiler.ProfileData` does not expose).  The window is the
`bench.window` span, or the device's activity where there is none.  It
prints one JSON object: the window, busy time, idle seconds by span, the
idle groups of `GROUPS` as shares of the window, and the top operations.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.trace import Reduced, _in_modules, short_op_name  # noqa: E402

PREFIXES = ("bench.", "serve.", "fm.", "bus.", "host.", "fabric.")
CONTROL_PLANE = ("fm.", "bus.", "host.", "fabric.")
# idle groups, by the innermost span: the checking phases with the
# control plane (the isolation tax as the chip pays it), the token reads,
# and the eager prefill
GROUPS = {
    "check": lambda s: s in ("serve.fence", "serve.check", "serve.egress",
                             "serve.verdict") or s.startswith(CONTROL_PLANE),
    "emit": lambda s: s == "serve.emit",
    "prefill": lambda s: s == "serve.prefill",
}


def _xspace_subset():
    """A message class for the part of the profiler's XSpace proto that
    `ProfileData` does not expose: each plane's event metadata with its
    stats, and the names of those stats.  Field numbers are those of
    xplane.proto; every other field is skipped on parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, of in fields:
            label = F.LABEL_REPEATED if of and of.endswith("*") \
                else F.LABEL_OPTIONAL
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if of:
                f.type_name = ".bench_xplane." + of.rstrip("*")

    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, None),
        ("str_value", 5, F.TYPE_STRING, None),
        ("ref_value", 7, F.TYPE_UINT64, None))
    msg("XEventMetadata", ("name", 2, F.TYPE_STRING, None),
        ("stats", 5, F.TYPE_MESSAGE, "XStat*"))
    msg("XStatMetadata", ("name", 2, F.TYPE_STRING, None))
    # a map<int64, V> field is, on the wire, a repeated {key, value}
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, None),
        ("value", 2, F.TYPE_MESSAGE, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, None),
        ("value", 2, F.TYPE_MESSAGE, "XStatMetadata"))
    msg("XPlane", ("name", 2, F.TYPE_STRING, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, "EventMetadataEntry*"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, "StatMetadataEntry*"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, "XPlane*"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(pb_path: Path) -> dict[str, dict[str, str]]:
    """{device plane: {op event name: scope path}}: the "tf_op" stat that
    the trace keeps for a device operation
    (``jit(serve_decode)/while/body/attention/dot_general:``).  Some that
    the compiler inserted carry none; a trace without the stat gives {}."""
    space = _xspace_subset()()
    space.ParseFromString(Path(pb_path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in names.items() if v == "tf_op"}
        scopes = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in tf_op:
                    scopes[e.value.name] = st.str_value or \
                        names.get(st.ref_value, "")
        out[plane.name] = scopes
    return out


def extract(pb_path: Path) -> dict:
    """As `bench.trace.extract`, with the program's spans kept, each
    device operation's scope path as a fifth field ("" where it has
    none) and the head of each operation's HLO text under "op_text"."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(pb_path))
    scopes = op_scopes(pb_path)
    devices, spans, text = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            scope = scopes.get(plane.name, {})
            ops = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in lines["XLA Ops"].events]
            mods = [[e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            short: dict[str, str] = {}
            rows = []
            for raw, module, start, dur in _in_modules(ops, mods):
                if raw not in short:
                    short[raw] = short_op_name(raw)
                    text[short[raw]] = raw[:160]
                rows.append([short[raw], module, start, dur,
                             scope.get(raw, "")])
            devices.append(rows)
        elif plane.name.startswith("/host:"):
            spans += [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(PREFIXES)]
    return {"devices": devices, "spans": spans, "op_text": text}


def top_ops(data: dict, n: int = 20) -> list[list]:
    """The `n` operations of the first device with the most device time:
    [module/op, seconds, scope path, head of its HLO text]."""
    agg: dict[str, float] = defaultdict(float)
    about: dict[str, list] = {}
    for o in (data["devices"][0] if data["devices"] else []):
        key = f"{o[1]}/{o[0]}" if o[1] else o[0]
        agg[key] += o[3] / 1e9
        about.setdefault(key, [o[4] if len(o) > 4 else "",
                               data.get("op_text", {}).get(o[0], "")])
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v, *about[k]] for k, v in top]


def phases(data: dict) -> dict:
    """The reduction of `data` (from `extract`): over the `bench.window`
    span, or over the device's activity where there is none."""
    r = Reduced(data)
    idle = r.idle_by_span()
    share = {g: 100.0 * sum(v for k, v in idle.items() if match(k))
             / r.window_s for g, match in GROUPS.items()} \
        if r.window_s else {}
    return {"window_s": r.window_s, "busy_s": r.busy_s,
            "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "idle_groups": share, "top_ops": top_ops(data)}


def main() -> int:
    path = Path(sys.argv[1])
    if path.is_dir():
        path = sorted(path.glob("**/*.xplane.pb"))[-1]
    print(json.dumps(phases(extract(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
