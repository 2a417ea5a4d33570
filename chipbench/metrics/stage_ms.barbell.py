"""Device ms per append batch of the non-root stages' executors: the
executor programs (`jit_run`) that start inside a `fj.standing.stage` span
with `root` 0, the two triangle stages that materialise their output on
the device (see chipbench/stage_spans.py)."""
from chipbench import stage_spans


def read(ctx):
    return stage_spans.read(ctx, stage_spans.executor_ns, False)
