"""Executor device time per append batch: the refresh's recount, the
compiled executor programs (jitted as `run`)."""
from chipbench.layers import device_ms_per

PROGRAMS = ("jit_run",)


def read(ctx):
    return device_ms_per(ctx, PROGRAMS, "batches")
