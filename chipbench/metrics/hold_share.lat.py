"""Share of the controller's decisions in the window that held or
throttled instead of pushing, in percent."""


def read(ctx):
    total = sum(ctx.decisions.values())
    if not total:
        return None
    held = ctx.decisions.get("hold", 0) + ctx.decisions.get("throttle", 0)
    return 100.0 * held / total
