"""Executor device time per append batch under the named scope `expand`:
frontier expansion: the expansion's counts and offsets, the frontier
regathered to the new lanes, the cover's vars bound. Each op of the compiled
executor (`jit_run`) counts its self time under the innermost executor scope
of its `tf_op`."""
from chipbench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "expand")
