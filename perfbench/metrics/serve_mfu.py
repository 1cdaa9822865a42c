"""Percent of the chip's bf16 peak that the served model's work fills:
operations of every unit the fleet executed in the window (conv and FC
multiply-accumulates x 2 at the published shapes, plus each executed
unit's L1 classification; counted by ``lib.flops`` from the
configuration's shapes and the executed-unit log), over the window's
host-clock length, over the peak."""


def read(ctx):
    done = ctx.counters.get("model_flops")
    if not done or ctx.peaks is None:
        return None
    return 100.0 * done / ctx.window_s / ctx.peaks["bf16_flops"]
