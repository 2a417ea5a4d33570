"""Executor device time per append batch under the named scope `probe`: hash
probes: each probe of a trie level and its fold into the lanes' validity and
multiplicity. Each op of the compiled executor (`jit_run`) counts its self
time under the innermost executor scope of its `tf_op`."""
from chipbench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "probe")
