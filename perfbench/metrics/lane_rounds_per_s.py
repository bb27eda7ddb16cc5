"""lane_rounds_per_s: the scenario-lane rounds of every call completed in the
window, over the window's seconds (host clock).  The window runs whole
calls: it ends with the first call that finishes past --seconds."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["lane_rounds"] / ctx["window_s"]
