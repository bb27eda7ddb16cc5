"""Shared arithmetic of the `<kernel>_roofline` readers: the kernel's
launches are the trace's events of its name; each launch's shapes are read
from its HLO text and priced by `kernels/<kernel>.py`."""


def read_kernel(ctx, kernel, lanes_u_d):
    """lanes_u_d(shapes) -> (lanes, U, D) of one launch from its HLO shapes."""
    s = ctx["summary"]
    events = [] if s is None else s.op_events.get(kernel, [])
    if not events:
        return None
    import trace_reduce
    counts = ctx["load_kernel"](kernel)
    nbytes = sum(counts.cost(*lanes_u_d(trace_reduce.shapes(ev.text)))["bytes"]
                 for ev in events) / s.chips
    seconds = sum(ev.end_ns - ev.start_ns for ev in events) / 1e9 / s.chips
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
