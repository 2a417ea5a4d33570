"""Device time of a standing query's stages, told apart by the program's
host spans rather than by named scopes.

Named scopes cannot tell the stages apart: two triangle stages that differ
only in their variables' names compile to one program, and a compile cache
keeps one copy of its op metadata. The spans can:

* each recomputed stage runs in the span `fj.standing.stage` (stats
  `stage`, its index, and `root`, 1 for the root), and its executor call
  blocks on the need vectors before the span closes, so every executor
  program (`jit_run`) of a stage starts inside that stage's span;
* each weighted stage-output trie is built in a span
  `fj.standing.stage_trie`, dispatched without a wait, so its programs run
  after the span has closed and before the next executor program. A trie
  program (sort, build, table) counts as a stage trie's from the start of
  such a span to the start of the next `jit_run`.

The functions take the parsed trace of `scopes.load` and the traced slice,
and return device ns summed over chips, or None where the trace holds no
such span (a program without them) or no chip.
"""
from __future__ import annotations

from chipbench import scopes
from chipbench import trace as tr

STAGE_SPAN = "fj.standing.stage"
TRIE_SPAN = "fj.standing.stage_trie"
# the programs of a weighted trie build: ops.lex_order's sort passes,
# compiled._build_trie_jit's group structure, ops.build_table's tables
TRIE_PROGRAMS = (
    "jit__lsd_pass",
    "jit__build_trie_jit",
    "jit__home_slots",
    "jit__assign_slots",
    "jit__build",
)


def _spans(trace, lo, hi, name):
    return [s for s in trace["spans"] if s[0] == name and lo <= s[1] < hi]


def _modules(trace, programs):
    """[(start, duration)] of the named programs' runs, over every chip."""
    return [
        (s, d)
        for dev in trace["devices"].values()
        for n, s, d in dev["modules"]
        if tr.program_of(n) in programs
    ]


def executor_ns(trace, lo: float, hi: float, root: bool) -> float | None:
    """Device ns of the executor programs that start inside a stage span of
    the slice: the root's (`root`) or the non-root stages'."""
    spans = [
        (s[1], s[1] + s[2])
        for s in _spans(trace, lo, hi, STAGE_SPAN)
        if bool(s[4].get("root", 0)) == root
    ]
    if not spans or not trace["devices"]:
        return None
    return float(
        sum(d for s, d in _modules(trace, (scopes.EXECUTOR,)) if any(a <= s < b for a, b in spans))
    )


def stage_trie_ns(trace, lo: float, hi: float) -> float | None:
    """Device ns of the trie programs that start between a stage-trie span's
    start and the next executor program's start."""
    starts = sorted(s[1] for s in _spans(trace, lo, hi, TRIE_SPAN))
    if not starts or not trace["devices"]:
        return None
    runs = sorted(s for s, _d in _modules(trace, (scopes.EXECUTOR,)))
    windows = []
    for s in starts:
        end = next((r for r in runs if r >= s), float("inf"))
        windows.append((s, end))
    return float(
        sum(d for s, d in _modules(trace, TRIE_PROGRAMS) if any(a <= s < b for a, b in windows))
    )


def per_batch_ms(ctx, ns):
    """Device ms per batch of the slice; None where nothing was read."""
    if ns is None:
        return None
    return scopes._per_batch(ctx, ns / 1e6)


def read(ctx, fn, *args):
    """`fn(trace, lo, hi, *args)` over the traced slice, per batch, in ms."""
    trace, b = scopes._slice()
    if trace is None:
        return None
    return per_batch_ms(ctx, fn(trace, *b, *args))
