"""trace_lower_ms_per_call: host milliseconds per grid call that JAX spends
tracing to a jaxpr, lowering to MLIR, compiling and loading compiled
programs from the compile cache (the jax.monitoring durations in the
harness's COMPILE_EVENTS, summed over the window), divided by the calls.
Near 0 where the engine is reused; a new run_sweep pays it every call."""


def read(ctx):
    if not ctx["calls"]:
        return None
    total = sum(ctx["durations"].get(k, 0.0) for k in ctx["compile_events"])
    return 1000.0 * total / ctx["calls"]
