"""Frontier lanes per append batch, in millions, from the program counter
`executor.lanes`: the lanes the answering dispatch's expansions produced
(the sum of its need vector over the executor's nodes)."""
from chipbench import scopes


def read(ctx):
    return scopes.counter(ctx, "executor.lanes", scale=1e-6)
