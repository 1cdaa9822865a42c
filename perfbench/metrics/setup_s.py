"""Seconds from the start of the process to the start of the window:
imports, inputs and weights, and the warm-up (compilation or the
compilation cache's load) of every shape the cell uses."""


def read(ctx):
    return ctx.setup_s
