"""Device microseconds of the fused fleet kernel per simulated step: the
summed device time of its trace events (the Mosaic custom call that
``repro.kernels.fleet_step.fleet_fused_steps`` emits, named after it in
the HLO) over the calls' steps."""

KERNEL = "fleet_fused_steps"


def read(ctx):
    if ctx.trace is None:
        return None
    # In a traced run the events have to be there: a rename in the
    # program must not drop the metric unnoticed.
    t = sum(v for k, v in ctx.trace.op_s.items() if KERNEL in k)
    steps = ctx.calls * ctx.extra["steps_per_call"]
    if t <= 0:
        raise LookupError(f"no device event of {KERNEL!r} in the trace: "
                          "was it renamed?")
    return 1e6 * t / steps
