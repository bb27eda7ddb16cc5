"""grid_call_s: the window's seconds over the calls completed in it, each a
whole grid (host clock)."""


def read(ctx):
    if not ctx["calls"]:
        return None
    return ctx["window_s"] / ctx["calls"]
