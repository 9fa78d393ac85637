"""What the per-layer metric readers share: the run's context and the
arithmetic each reader applies to it.  A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
import types
from typing import Any

import numpy as np

import cost
import trace as tr

# kernel names as they appear on the device's op line: the custom calls
# the Pallas wrappers lower to (``paged_decode_attention.6``)
PAGED_KERNEL = "paged_decode_attention"
FLASH_KERNEL = "flash_attention"


class Step(types.SimpleNamespace):
    """One engine dispatch, from the program's step timeline: every field
    of its ``StepRecord`` (``as_dict()``: ``step``, ``wall``,
    ``decode_batch``, ``kv_tokens``, ``pool_util`` and whatever else the
    program records), and ``chunks``, the dispatch's prefill chunks as
    ``(pos, n_valid, last)``."""


@dataclasses.dataclass
class Context:
    shape: Any                      # the family's shape(config)
    peak: dict                      # peaks.json entry of this device
    steps: list[Step]               # dispatches in the measured window
    trace: tr.Trace | None = None
    trace_window: tuple[float, float] | None = None   # host clock
    family: Any = None              # the cell's family module (spec.py)

    def traced_steps(self) -> list[Step]:
        lo, hi = self.trace_window
        return [s for s in self.steps if lo <= s.wall <= hi]


def percentile(values, p: float) -> float:
    return float(np.percentile(np.asarray(values, float), p, method="higher"))


def mfu(ctx: Context) -> float | None:
    """Analytic FLOPs of the traced dispatches over busy device time at
    the bf16 peak, in percent."""
    if ctx.trace is None:
        return None
    steps = ctx.traced_steps()
    busy = tr.busy_s(ctx.trace)
    if not steps or busy <= 0:
        return None
    flops = sum(ctx.family.step_flops(ctx.shape, s.decode_batch, s.kv_tokens, s.chunks)
                for s in steps)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops"])


def idle_share(ctx: Context) -> float | None:
    if ctx.trace is None:
        return None
    w = tr.window_s(ctx.trace)
    return 100.0 * (1.0 - tr.busy_s(ctx.trace) / w) if w > 0 else None


def roofline(ctx: Context, kernel: str, work) -> tuple[float, str] | None:
    """Least time for ``work(step) -> [(flops, bytes), ...]`` over the
    traced dispatches, as a share of the kernel's device time."""
    if ctx.trace is None:
        return None
    k_s = tr.kernel_s(ctx.trace, kernel)
    if k_s <= 0:
        return None
    least, bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for s in ctx.traced_steps():
        for flops, bytes_ in work(s):
            t, which = cost.least_s(flops, bytes_, ctx.peak)
            least += t
            bound[which] += t
    if least <= 0:
        return None
    return 100.0 * least / k_s, max(bound, key=bound.get)
