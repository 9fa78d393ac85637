"""Chunked-prefill attention's share of its roofline: the least time for
the causal-chunk FLOPs and bytes of every traced chunk (``lib/cost.py``)
at the chip's peaks, over the summed device time of the flash kernel."""
import cost
import layer


def read(ctx):
    got = layer.roofline(
        ctx, layer.FLASH_KERNEL,
        lambda s: [cost.flash_prefill(ctx.shape, p, n) for p, n, _ in s.chunks])
    return None if got is None else got[0]
