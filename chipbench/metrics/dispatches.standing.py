"""Compiled-executor dispatches per append batch, from the program counter
`executor.dispatches`: one per call plus each overflow or tightening
re-run, so 1 means the refresh ran its recount once."""
from chipbench import scopes


def read(ctx):
    return scopes.counter(ctx, "executor.dispatches")
