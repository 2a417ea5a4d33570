"""Host milliseconds per append batch inside the program's `fj.` spans
(relcache appends, the refresh and its stages, stage inputs and stage-trie
builds, trie serving and sorts, executor dispatch), less the time blocked
on the device in `fj.executor.sync` and `fj.result.read`."""
from chipbench import scopes


def read(ctx):
    return scopes.host_ms(ctx)
