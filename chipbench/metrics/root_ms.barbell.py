"""Device ms per append batch of the root stage's executor: the executor
programs (`jit_run`) that start inside the `fj.standing.stage` span with
`root` 1, the bridge weighed by the two stage tries (see
chipbench/stage_spans.py)."""
from chipbench import stage_spans


def read(ctx):
    return stage_spans.read(ctx, stage_spans.executor_ns, True)
