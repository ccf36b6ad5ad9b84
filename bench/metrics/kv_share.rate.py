"""Device time under the program's ``kv`` scope (the page write, the
pool's hand-off to the attention kernel) over device busy time, in
percent."""
from bench.core.scopes import scope_share


def read(ctx):
    return scope_share(ctx, ("kv",), "kv_share")
