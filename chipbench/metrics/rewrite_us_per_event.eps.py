"""The dictionary layer's host time per event committed in the window,
microseconds: the program's `rewrite.*` spans (mining, lookup and the
residual's and references' masks) and `dict.admit`, summed over the
window.  None where the program writes no such span."""


def _dictionary(name):
    return name.startswith("rewrite.") or name == "dict.admit"


def read(ctx):
    n = ctx.events_committed()
    names = {s for s, _a, _b in ctx.spans if _dictionary(s)}
    if not n or not names:
        return None
    return sum(ctx.span_total(s) for s in names) / n * 1e6
