"""Executor device time per append batch under the named scope `count`: the
count: the factorized count's row totals and the terminal reduction. Each op
of the compiled executor (`jit_run`) counts its self time under the
innermost executor scope of its `tf_op`."""
from chipbench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "count")
