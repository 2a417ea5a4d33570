"""Host spans and counters of the program, on the profiler's clock.

    with obs.span("fj.trie.get") as sp:
        ...
        sp.stats(outcome="merge")  # a stat known only at the end

A span enters `jax.profiler.TraceAnnotation`, so while a profiler runs it
lands in the trace on the same clock as the device's ops; with none running
it costs a few microseconds. Every span also keeps in-process totals per
name: how many closed, their host seconds, and their self seconds (the
time less that of the spans opened inside them on the same thread).

Counters are plain ints in one registry (`count`). The outermost span of a
thread writes the counts that thread made since the last outermost span
closed into the trace as its stats when it closes, so a reader sums each
counter over the spans of a traced slice; only the outermost span carries
them, so nothing is summed twice, and a count made outside every span rides
with the next one. Counters today:

* `executor.dispatches`: dispatches of a compiled executor, one per call
  plus its overflow and tightening re-runs;
* `executor.lanes`: the frontier lanes the expansions of the answering
  dispatch produced (the sum of its need vector);
* `sync_reads`: blocking device-to-host reads, all made by `read`;
* `executor.refused_lanes`: lanes (or output rows) whose multiplicity
  reached 2**31 - 1 and reached a result, which was then refused
  (`compiled.CountOverflowError`);
* `standing.stage_rows`: the valid output rows of each recomputed
  non-root stage of a standing query, read with the root's result.

`snapshot()` returns the totals and counters. There is nothing to turn on
or off: the trace holds the spans while a profiler runs, and only then.
"""
from __future__ import annotations

import itertools
import threading
import time

import jax
from jax.profiler import TraceAnnotation

_counters: dict[str, int] = {}
_totals: dict[str, list] = {}  # name -> [count, seconds, self seconds]
_lock = threading.Lock()  # guards the two registries above
_local = threading.local()
seq = itertools.count(1).__next__  # sequence ids: refreshes, executor calls


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
    moved = _thread().moved
    moved[name] = moved.get(name, 0) + n


def _thread():
    """This thread's open spans (`stack`) and the counts it made since its
    last outermost span closed (`moved`)."""
    if not hasattr(_local, "stack"):
        _local.stack, _local.moved = [], {}
    return _local


class span:
    """One host span; `stats` are written into the trace beside its name."""

    __slots__ = ("name", "ann", "t0", "child")

    def __init__(self, name: str, **stats):
        self.name = name
        self.ann = TraceAnnotation(name, **stats)

    def stats(self, **stats) -> None:
        self.ann.set_metadata(**stats)

    def __enter__(self) -> "span":
        t = _thread()
        t.stack.append(self)
        self.child = 0.0
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self.t0
        t = _thread()
        t.stack.pop()
        if t.stack:
            t.stack[-1].child += dt
        elif t.moved:
            self.ann.set_metadata(**t.moved)
            t.moved = {}
        self.ann.__exit__(*exc)
        with _lock:
            tot = _totals.get(self.name)
            if tot is None:
                tot = _totals[self.name] = [0, 0.0, 0.0]
            tot[0] += 1
            tot[1] += dt
            tot[2] += dt - self.child
        return False


def read(tree, name: str, **stats):
    """Bring `tree` to the host, blocking until the device has it: the
    program's deliberate device-to-host reads all go through here, each in
    a span of its own and counted as `sync_reads`. This read is allowed
    whatever transfer guard the caller set, so a path run under
    `jax.transfer_guard_device_to_host("disallow_explicit")` raises on any
    other read alone."""
    with span(name, **stats):
        with jax.transfer_guard_device_to_host("allow"):
            out = jax.device_get(tree)
        count("sync_reads")
    return out


def snapshot() -> dict:
    """The totals of every span name and the counters, since the process
    started: {"spans": {name: {"count", "seconds", "self_seconds"}},
    "counters": {name: n}}."""
    with _lock:
        return {
            "spans": {
                name: {"count": c, "seconds": s, "self_seconds": ss}
                for name, (c, s, ss) in _totals.items()
            },
            "counters": dict(_counters),
        }
