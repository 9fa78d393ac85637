"""The engine's own host work per step: over the program's
``Engine.step`` spans inside the traced window, the median of each
span's duration less the time it spends blocked, in ms.  Blocked is the
union of its ``Engine.readback`` spans (the device-to-host fetches) and
the runtime's own waits inside it: ``AllocateBufferAwait`` (the enqueue
waiting for an output buffer that a running program still holds) and
the buffer-hold waits.  A program without these spans gives nothing to
read."""
import layer

BLOCKED = ("Engine.readback", "AllocateBufferAwait", "Wait for usage holds",
           "Wait for donation holds")


def _union(ivs):
    total, end = 0, None
    for s, e in sorted(ivs):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    steps = [(s, s + d) for n, s, d in ctx.trace.spans
             if n == "Engine.step" and lo <= s and s + d <= hi]
    blocked = [(s, s + d) for n, s, d in ctx.trace.spans if n in BLOCKED]
    own = [(e - s) - _union([(b0, b1) for b0, b1 in blocked
                             if s <= b0 and b1 <= e])
           for s, e in steps]
    return layer.percentile(own, 50) / 1e6 if own else None
