"""Jobs served per second of the window, over whole calls: every job
released to the fleet, scheduled or missed, over the host-clock length of
the window (featurize, build and scan included)."""


def read(ctx):
    jobs = ctx.counters.get("jobs")
    return jobs / ctx.window_s if jobs else None
