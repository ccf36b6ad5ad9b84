"""Device time under the program's ``lm_head`` and ``sample`` scopes
(final norm, vocabulary projection, argmax) over device busy time, in
percent."""
from bench.core.scopes import scope_share


def read(ctx):
    return scope_share(ctx, ("lm_head", "sample"), "head_share")
