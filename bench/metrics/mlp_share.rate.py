"""Device time under the program's ``mlp`` scope (the dense feed-forward
block, its norm included) over device busy time, in percent."""
from bench.core.scopes import scope_share


def read(ctx):
    return scope_share(ctx, ("mlp",), "mlp_share")
