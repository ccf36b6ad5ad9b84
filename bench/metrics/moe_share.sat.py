"""Device time under the program's ``moe`` scope (router, experts,
combine and shared expert, its norm included) over device busy time, in
percent."""
from bench.core.scopes import scope_share


def read(ctx):
    return scope_share(ctx, ("moe",), "moe_share")
