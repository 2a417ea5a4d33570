"""Per-layer numbers from inside the program: the device ops' named scopes
and the program's `fj.` host spans, read from the traced run's trace.

`trace.load_xplane` keeps what `jax.profiler.ProfileData` shows: each
event's name, times and own stats. A device op's named scope is not among
them: it lies in the op's event *metadata* (the stat `tf_op`, such as
`jit(run)/node1/expand/gather:gather`). So this reads the `.xplane.pb`
itself, with `protobuf` alone, from the field numbers of the profiler's
XPlane messages (tsl/profiler/protobuf/xplane.proto).

Two stages, as in `trace.py`, so that the second can be checked on a small
recorded trace:

1. `load` turns the newest trace file under `harness.TRACE_DIR` into plain
   lists: the slice mark, every `fj.` span with its stats and thread, and
   each TPU's modules and ops, the op's `tf_op` beside it. It parses a file
   once.
2. `stage_ns`, `span_self_ns`, `counter_sum` and `idle_gaps` reduce those
   lists over the traced slice.

Readers divide by the slice's batches (`ctx.slice["batches"]`) and return
None where the trace holds nothing to read: a program without the scopes,
spans or counters they look for.
"""
from __future__ import annotations

import bisect
import os
import re
from pathlib import Path

from chipbench import harness
from chipbench import trace as tr

STAGES = ("expand", "probe", "compact", "count")  # the executor's scopes
EXECUTOR = "jit_run"  # the compiled executor's program
BLOCKING = ("fj.executor.sync", "fj.result.read")  # spans that wait on the device
_NODE = re.compile(r"^node(\d+)$")

_XPLANE = """
syntax = "proto3";
package chipbench_xplane;
message XSpace { repeated XPlane planes = 1; }
message XPlane {
  int64 id = 1; string name = 2; repeated XLine lines = 3;
  repeated EventMetadataEntry event_metadata = 4;
  repeated StatMetadataEntry stat_metadata = 5;
}
message EventMetadataEntry { int64 key = 1; XEventMetadata value = 2; }
message StatMetadataEntry { int64 key = 1; XStatMetadata value = 2; }
message XLine {
  int64 id = 1; string name = 2; int64 timestamp_ns = 3;
  repeated XEvent events = 4; int64 display_id = 10;
}
message XEvent {
  int64 metadata_id = 1; int64 offset_ps = 2; int64 duration_ps = 3;
  repeated XStat stats = 4;
}
message XStat {
  int64 metadata_id = 1; double double_value = 2; uint64 uint64_value = 3;
  int64 int64_value = 4; string str_value = 5; bytes bytes_value = 6;
  uint64 ref_value = 7;
}
message XEventMetadata {
  int64 id = 1; string name = 2; string display_name = 4;
  repeated XStat stats = 5;
}
message XStatMetadata { int64 id = 1; string name = 2; }
"""

_classes = None
_loaded: dict = {}  # (path, mtime, size) -> parsed trace


def _messages():
    """The message classes, built from `_XPLANE`'s field numbers. Each
    map of the real schema is its wire form here: a repeated key/value
    entry. Fields not listed are skipped by the parser."""
    global _classes
    if _classes is None:
        from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

        fdp = descriptor_pb2.FileDescriptorProto(
            name="chipbench_xplane.proto", package="chipbench_xplane", syntax="proto3"
        )
        types = descriptor_pb2.FieldDescriptorProto
        kinds = {
            "int64": types.TYPE_INT64,
            "uint64": types.TYPE_UINT64,
            "double": types.TYPE_DOUBLE,
            "string": types.TYPE_STRING,
            "bytes": types.TYPE_BYTES,
        }
        for m in re.finditer(r"message (\w+) \{([^}]*)\}", _XPLANE):
            msg = fdp.message_type.add(name=m.group(1))
            for f in re.finditer(r"(repeated )?(\w+) (\w+) = (\d+);", m.group(2)):
                rep, kind, name, num = f.groups()
                field = msg.field.add(
                    name=name,
                    number=int(num),
                    label=types.LABEL_REPEATED if rep else types.LABEL_OPTIONAL,
                )
                if kind in kinds:
                    field.type = kinds[kind]
                else:
                    field.type = types.TYPE_MESSAGE
                    field.type_name = f".chipbench_xplane.{kind}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        _classes = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("chipbench_xplane.XSpace")
        )
    return _classes


def _stat_value(stat, names: dict):
    """A stat's value: the one of its value fields that is set (a string
    stat may be a reference to a stat metadata's name)."""
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return names.get(stat.ref_value, "")
    if stat.int64_value:
        return stat.int64_value
    if stat.uint64_value:
        return stat.uint64_value
    if stat.double_value:
        return stat.double_value
    return 0


def parse(path: str) -> dict:
    """One trace file as plain lists, times in ns on the profiler's clock:
    {"spans": [[name, start, dur, thread, {stat: value}]] (the slice mark
    and every `fj.` span), "devices": {ordinal: {"modules": [[name, start,
    dur]], "ops": [[name, start, dur, tf_op]]}}}."""
    space = _messages()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    spans, devices = [], {}
    for plane in space.planes:
        dev = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not dev and plane.name != "/host:CPU":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if dev:
            out = devices.setdefault(dev.group(1), {"modules": [], "ops": []})
            tf_op = {}
            for key, md in meta.items():
                for s in md.stats:
                    if stat_names.get(s.metadata_id) == "tf_op":
                        tf_op[key] = _stat_value(s, stat_names)
            for line in plane.lines:
                kind = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    start = line.timestamp_ns + e.offset_ps / 1e3
                    row = [meta[e.metadata_id].name, start, e.duration_ps / 1e3]
                    if kind == "ops":
                        row.append(tf_op.get(e.metadata_id, ""))
                    out[kind].append(row)
            continue
        for line in plane.lines:
            for e in line.events:
                name = meta[e.metadata_id].name
                if name != tr.SLICE_SPAN and not name.startswith("fj."):
                    continue
                stats = {stat_names.get(s.metadata_id, "?"): _stat_value(s, stat_names)
                         for s in e.stats}
                start = line.timestamp_ns + e.offset_ps / 1e3
                spans.append([name, start, e.duration_ps / 1e3, line.id, stats])
    return {"spans": spans, "devices": devices}


def newest(directory: Path | None = None) -> str | None:
    """The newest `*.xplane.pb` under the benchmark's trace directory."""
    found = sorted(Path(directory or harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(found[-1]) if found else None


def load(path: str | None = None) -> dict | None:
    """The parsed trace of `path` (default: the newest), parsed once."""
    path = path or newest()
    if path is None:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = parse(path)
    return _loaded[key]


def bounds(trace: dict):
    """The traced slice, from its mark, as `trace.slice_bounds` finds it."""
    return tr.slice_bounds({"spans": [s[:3] for s in trace["spans"] if s[0] == tr.SLICE_SPAN]})


def _in(trace, lo, hi):
    return [s for s in trace["spans"] if s[0] != tr.SLICE_SPAN and lo <= s[1] < hi]


def scope_of(tf_op: str) -> tuple[str | None, str | None]:
    """(stage, node) of an op: the innermost of STAGES and of `node{i}`
    among the scopes of its `tf_op` (`jit(run)/node1/probe/gather:gather`)."""
    parts = tf_op.rsplit(":", 1)[0].split("/")[:-1]
    stage = next((p for p in reversed(parts) if p in STAGES), None)
    node = next((p for p in reversed(parts) if _NODE.match(p)), None)
    return stage, node


def stage_ns(trace: dict, lo: float, hi: float) -> tuple[dict, dict]:
    """Self time of the executor's ops that start in [lo, hi), by stage and
    by node: ({stage or None: ns}, {node or None: ns}), summed over chips."""
    by_stage: dict = {}
    by_node: dict = {}
    for dev in trace["devices"].values():
        runs = sorted((s, s + d) for n, s, d in dev["modules"] if tr.program_of(n) == EXECUTOR)
        starts = [r[0] for r in runs]
        ops = [o for o in dev["ops"] if lo <= o[1] < hi]
        scopes = {id(o): o[3] for o in ops}
        ordered, self_ns = tr._self_times([[id(o), o[1], o[2]] for o in ops])
        for (oid, s, _d), ns in zip(ordered, self_ns):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue
            stage, node = scope_of(scopes[oid])
            by_stage[stage] = by_stage.get(stage, 0.0) + ns
            by_node[node] = by_node.get(node, 0.0) + ns
    return by_stage, by_node


def span_self_ns(trace: dict, lo: float, hi: float) -> dict:
    """Host self time of each `fj.` span name, over the spans that start in
    [lo, hi): a span's time less that of the `fj.` spans inside it on its
    thread."""
    out: dict = {}
    threads: dict = {}
    for s in _in(trace, lo, hi):
        threads.setdefault(s[3], []).append(s[:3])
    for spans in threads.values():
        ordered, self_ns = tr._self_times(spans)
        for (name, _s, _d), ns in zip(ordered, self_ns):
            out[name] = out.get(name, 0.0) + ns
    return out


def counter_sum(trace: dict, lo: float, hi: float, name: str) -> int | None:
    """A program counter summed over the spans that start in [lo, hi) (the
    outermost span of each thread carries the counts made inside it); None
    when no span carries it."""
    values = [s[4][name] for s in _in(trace, lo, hi) if name in s[4]]
    return int(sum(values)) if values else None


def idle_gaps(trace: dict, lo: float, hi: float, top: int = 10) -> list:
    """The longest device idle gaps of [lo, hi), each labelled by the
    innermost `fj.` span open at its middle: [[label, seconds]]."""
    flat = {
        "devices": {
            k: {"modules": d["modules"], "ops": [o[:3] for o in d["ops"]]}
            for k, d in trace["devices"].items()
        },
        "spans": [s[:3] for s in _in(trace, lo, hi)],
    }
    return tr.reduce(flat, (lo, hi), top=top).idle_gaps


# ---------------------------------------------------------------------------
# what the readers call


def traced() -> dict | None:
    """The traced run's parsed trace: the newest under the trace directory."""
    return load()


def _per_batch(ctx, value):
    n = ctx.slice.get("batches", 0)
    if not n or value is None:
        return None
    return value / n


def _slice():
    trace = traced()
    if trace is None:
        return None, None
    b = bounds(trace)
    return (trace, b) if b is not None else (None, None)


def stage_ms(ctx, stage: str):
    """Device ms per batch of the executor's ops under `stage`: 0 for a
    stage the executor does not run (a plan without compaction), None when
    no executor op of the slice carries a stage scope at all."""
    trace, b = _slice()
    if trace is None:
        return None
    by_stage = stage_ns(trace, *b)[0]
    if not any(by_stage.get(s, 0.0) > 0 for s in STAGES):
        return None
    return _per_batch(ctx, by_stage.get(stage, 0.0) / 1e6)


def host_ms(ctx):
    """Host ms per batch inside `fj.` spans, less the spans that wait on
    the device (BLOCKING)."""
    trace, b = _slice()
    if trace is None:
        return None
    self_ns = span_self_ns(trace, *b)
    if not self_ns:
        return None
    ns = sum(v for k, v in self_ns.items() if k not in BLOCKING)
    return _per_batch(ctx, ns / 1e6)


def counter(ctx, name: str, scale: float = 1.0):
    """A program counter per batch, times `scale`."""
    trace, b = _slice()
    if trace is None:
        return None
    n = counter_sum(trace, *b, name)
    return _per_batch(ctx, None if n is None else n * scale)
