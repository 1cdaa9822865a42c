"""Simulated device-timesteps per second of the window, over whole calls:
every device of every call times the call's steps, over the host-clock
length of the window (build included)."""


def read(ctx):
    steps = ctx.counters.get("device_steps")
    return steps / ctx.window_s if steps else None
