"""Valid output rows of the recomputed non-root stages per append batch,
in millions, from the program counter `standing.stage_rows`: the rows the
two triangle stages materialise and the root's stage tries are built
from."""
from chipbench import scopes


def read(ctx):
    return scopes.counter(ctx, "standing.stage_rows", scale=1e-6)
