"""Peak share of the paged KV pool's blocks in use over the measured
window (``StepRecord.pool_util``), in percent."""


def read(ctx):
    util = [s.pool_util for s in ctx.steps if s.pool_util is not None]
    return 100.0 * max(util) if util else None
