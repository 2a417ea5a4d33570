"""Arithmetic the per-layer metric readers share. Each reader keeps its
own list of program names; these take the list as an argument."""
from __future__ import annotations


def idle_share(ctx):
    """Percent of the traced slice in which no op ran on the device."""
    r = ctx.reduction
    if r.devices == 0 or r.window_ns <= 0:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)


def device_ms_per(ctx, programs, unit: str):
    """Device milliseconds of the named programs per unit of work
    (`unit` is a count in the driver's slice report); None when the slice
    holds no such unit or the programs never ran."""
    n = ctx.slice.get(unit, 0)
    ns = ctx.reduction.device_ns(programs)
    if not n or ns <= 0:
        return None
    return ns / 1e6 / n
