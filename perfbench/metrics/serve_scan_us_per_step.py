"""Device microseconds of the live-serving scan per simulated step: the
summed device time of the operations of the serve scan's executable over
the calls' steps.

The executable is ``FleetServeEngine``'s jitted ``_scan_steps``.  The
engine jits it as a ``functools.partial``, which JAX names
``jit__unknown``; a name of its own (``jit__scan_steps``) is matched as
well, so that naming the executable leaves the metric in place.  No other
executable of the serve window is unnamed."""

EXECUTABLES = ("jit__scan_steps", "jit__unknown")


def module_base(name: str) -> str:
    """An executable's name without the fingerprint the trace appends:
    ``jit__unknown(1663...)`` -> ``jit__unknown``."""
    return name.split("(", 1)[0]


def read(ctx):
    if ctx.trace is None:
        return None
    # In a traced run the events have to be there: a rename in the
    # program must not drop the metric unnoticed.
    t = sum(v for k, v in ctx.trace.module_s.items()
            if module_base(k) in EXECUTABLES)
    steps = ctx.calls * ctx.extra["steps_per_call"]
    if t <= 0:
        raise LookupError(f"no device event of {EXECUTABLES!r} in the "
                          "trace: was it renamed?")
    return 1e6 * t / steps
