"""Trie build device time per append batch: the delta merge
(`compiled._merge_append_jit`), the sort passes of `ops.lex_order`
(`_lsd_pass`), hash-table slots (`ops._home_slots`, `ops._assign_slots`,
`ops._build`), full builds (`compiled._build_trie_jit`) and tombstone
refreshes (`compiled._retire_rows_jit`)."""
from chipbench.layers import device_ms_per

PROGRAMS = (
    "jit__merge_append_jit",
    "jit__lsd_pass",
    "jit__home_slots",
    "jit__assign_slots",
    "jit__build",
    "jit__build_trie_jit",
    "jit__retire_rows_jit",
)


def read(ctx):
    return device_ms_per(ctx, PROGRAMS, "batches")
