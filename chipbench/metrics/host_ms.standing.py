"""Host milliseconds per append batch inside the program's `fj.` spans
(relcache append, refresh, stage inputs, trie serving and sorts, planning,
executor dispatch), less the time blocked on the device in
`fj.executor.sync` and `fj.result.read`."""
from chipbench import scopes


def read(ctx):
    return scopes.host_ms(ctx)
