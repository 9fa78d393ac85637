"""Mean decode lanes per dispatch that carried decode work, over the
measured window (the program's step timeline, ``StepRecord.decode_batch``)."""


def read(ctx):
    lanes = [s.decode_batch for s in ctx.steps if s.decode_batch > 0]
    return sum(lanes) / len(lanes) if lanes else None
