"""Shared reading of the metrics of the program's own tracing (layer scopes,
sweep.* host spans, the h2d_bytes counter): `program_trace.probe` traces one
call of the cell's system after the window, once per traced run, and every
such reader takes its number from that one call.  The window's own trace is
gone by the time the readers run, and its events carry no scopes."""
import sys
import traceback


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def reading(ctx):
    """The probe of this run, made on the first call and kept in ctx; None
    in an untraced run, or where the traced call fails."""
    if "program_probe" not in ctx:
        ctx["program_probe"] = _probe(ctx)
    return ctx["program_probe"]


def _probe(ctx):
    if ctx.get("summary") is None or ctx.get("system") is None:
        return None
    import program_trace
    try:
        got = program_trace.probe(ctx["system"], ctx["chips"])
    except Exception:  # the run goes on; these metrics report nothing
        log("program trace: the traced call failed:\n"
            + traceback.format_exc())
        return None
    s = got.summary()
    log(f"program trace, one call after the window ({s['lane_rounds']} "
        f"lane-rounds): device us per lane-round by layer "
        f"{s['device_us_per_lane_round']}; host ms per call by span "
        f"{s['span_ms_per_call']}; h2d bytes {s['h2d_bytes']}")
    log(f"program trace: device-idle ms inside the call by sweep span "
        f"{s['idle_ms_by_sweep_span']}; share covered by a sweep span "
        f"{s['idle_covered_share']}; kernel seconds by layer "
        f"{s['kernel_layers']}")
    return got
