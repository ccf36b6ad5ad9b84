"""Device time under the program's ``mla`` scope (latent attention's
down-projection and latent norm, the rotary embedding, the absorption of
each head's query through ``W_UK`` and its output through ``W_UV``) over
device busy time, in percent.

``bench/core/scopes.py`` knows the scopes the benchmark had before
``mla``, so this reader gives each moment of device time to the innermost
of those scopes and ``mla`` itself; a trace without the ``mla`` scope
reads as nothing."""
from bench.core.readers import share
from bench.core.scopes import SCOPES, UNSCOPED, _innermost, for_run

NAMES = SCOPES + ("mla",)


def _scope(stack: str) -> str:
    for part in reversed(stack.split("/")):
        if part in NAMES:
            return part
    return UNSCOPED


def read(ctx):
    m = for_run(ctx)
    dev, win = ctx["dev"], ctx["window"]
    if m is None or ctx["busy"] <= 0:
        return None
    t = _innermost([(_scope(o[3]), o[1], o[2]) for o in m.ops.get(dev, [])],
                   win)
    if t.get("mla", 0.0) <= 0:
        return None
    ctx["log"]("[bench] device time by scope with mla (of busy): " + ", ".join(
        f"{k} {100 * v / ctx['busy']:.2f}%"
        for k, v in sorted(t.items(), key=lambda kv: -kv[1])))
    return share(t["mla"], ctx["busy"], "mla_share")
