"""Mean host-clock milliseconds of the harness's ``build`` span per call:
``repro.fleet.build`` of the stacked fleet configuration."""


def read(ctx):
    rows = [t1 - t0 for name, t0, t1 in ctx.spans if name == "build"]
    return 1e3 * sum(rows) / len(rows) if rows else None
