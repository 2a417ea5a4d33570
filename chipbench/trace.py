"""Device trace capture and its reduction to per-layer numbers.

Two stages, kept apart so that the second can be checked on a small
recorded trace:

1. `load_xplane` reads the profiler's `.xplane.pb` into a plain dict of
   events: device ops and program (module) executions of each TPU, and the
   benchmark's own host spans, all on the profiler's one clock.
2. `reduce` takes that dict and the traced slice and gives busy time (the
   union of the intervals in which an op ran), device time per program,
   the ops with the most self time, and the idle gaps, each labelled by the
   innermost benchmark span open at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SLICE_SPAN = "slice"


def program_of(module_event_name: str) -> str:
    """`jit_run(1234)` -> `jit_run`: the jitted function's program name."""
    return module_event_name.split("(", 1)[0]


def op_of(op_event_name: str) -> str:
    """`%fusion.67 = s32[...] fusion(...)` -> `fusion.67`."""
    return op_event_name.split(" = ", 1)[0].strip().lstrip("%")


class Tracer:
    """Runs jax.profiler over one slice of the window. `start()` and
    `stop()` are called by the driver at unit boundaries it chooses; the
    slice itself is marked by a host span, so the reduction measures
    exactly from the first traced unit to the end of the last."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self.active = False
        self.done = False
        self._slice = None

    def start(self) -> None:
        if not self.enabled or self.active or self.done:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no Python call events: spans only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._slice = jax.profiler.TraceAnnotation(SLICE_SPAN)
        self._slice.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def path(self) -> str | None:
        found = glob.glob(os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if found else None


def load_xplane(path: str, span_names) -> dict:
    """The device and span events of one trace file, as plain lists:
    {"devices": {ordinal: {"modules": [[name, start, dur]], "ops": [...]}},
     "spans": [[name, start, dur]]}, times in ns on the profiler's clock.
    Only host events whose name passes `span_names` (a predicate) are kept."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    spans = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                dev[key].extend([e.name, e.start_ns, e.duration_ns] for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events if span_names(e.name)
                )
    return {"devices": devices, "spans": spans}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _self_times(ops):
    """Self time per op event: its duration less that of the ops nested in
    it on the same line (a `while` holds its body's ops)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    self_ns = [float(o[2]) for o in ops]
    stack: list[int] = []
    for i, (_n, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(d, ops[stack[-1]][1] + ops[stack[-1]][2] - s)
        stack.append(i)
    return ops, self_ns


@dataclass
class Reduction:
    window_ns: float
    busy_ns: float
    devices: int = 0  # device planes in the trace
    program_ns: dict = field(default_factory=dict)  # program name -> device ns
    top_ops: list = field(default_factory=list)  # [[program:op, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[span label, seconds]]

    def device_ns(self, programs) -> float:
        """Device time of the programs named (by `program_of` name)."""
        return float(sum(ns for p, ns in self.program_ns.items() if p in programs))


def slice_bounds(trace: dict) -> tuple[float, float] | None:
    marks = [s for s in trace["spans"] if s[0] == SLICE_SPAN]
    if not marks:
        return None
    _n, start, dur = marks[0]
    return float(start), float(start + dur)


def reduce(trace: dict, bounds: tuple[float, float], top: int = 10) -> Reduction:
    """Busy, per-program device time, top ops and idle gaps over
    `bounds`, averaged over the devices present (one chip: that chip)."""
    lo, hi = bounds
    window = hi - lo
    devs = trace["devices"]
    busy = 0.0
    programs: dict[str, float] = {}
    op_self: dict[str, float] = {}
    gaps = []
    spans = [s for s in trace["spans"] if s[0] != SLICE_SPAN]
    for dev in devs.values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        for name, s, d in mods:
            clipped = _clip([(s, s + d)], lo, hi)
            if clipped:
                p = program_of(name)
                programs[p] = programs.get(p, 0.0) + (clipped[0][1] - clipped[0][0])
        ops, self_ns = _self_times([o for o in dev["ops"] if o[1] < hi and o[1] + o[2] > lo])
        starts = [m[1] for m in mods]
        for (name, s, _d), sn in zip(ops, self_ns):
            k = bisect.bisect_right(starts, s) - 1
            owner = program_of(mods[k][0]) if k >= 0 and s < mods[k][1] + mods[k][2] else "?"
            key = f"{owner}:{op_of(name)}"
            op_self[key] = op_self.get(key, 0.0) + sn
        merged = _union(_clip([(o[1], o[1] + o[2]) for o in ops], lo, hi))
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label(spans, (g0 + g1) / 2)))
    n = max(len(devs), 1)
    gaps.sort(key=lambda g: -g[0])
    return Reduction(
        window_ns=window,
        busy_ns=busy / n,
        devices=len(devs),
        program_ns={p: ns / n for p, ns in programs.items()},
        top_ops=[[k, v / 1e9] for k, v in sorted(op_self.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label, g / 1e9] for g, label in gaps[:top]],
    )


def _label(spans, t: float) -> str:
    """The innermost (shortest) benchmark span open at time t."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"
