"""Executor device time per append batch under the named scope `compact`,
over every stage's executor: the live lanes squeezed into a narrower
frontier before a stage's output is materialised."""
from chipbench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "compact")
