"""Whole-step model FLOP utilisation: analytic FLOPs of every token the
traced dispatches processed (the family's ``step_flops``), over the
device's busy seconds in the trace times the bf16 peak, in percent."""
import layer


def read(ctx):
    return layer.mfu(ctx)
