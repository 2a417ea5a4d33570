"""Blocking device-to-host reads per append batch, from the program
counter `sync_reads`: the executor's need vectors after each dispatch and
the count the refresh returns."""
from chipbench import scopes


def read(ctx):
    return scopes.counter(ctx, "sync_reads")
