"""Device ms per append batch of the weighted stage-output trie builds:
their sort, build and table programs, from the start of each
`fj.standing.stage_trie` span to the next executor program (see
chipbench/stage_spans.py)."""
from chipbench import stage_spans


def read(ctx):
    return stage_spans.read(ctx, stage_spans.stage_trie_ns)
