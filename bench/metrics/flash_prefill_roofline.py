"""Chunked-prefill attention's share of its roofline: the least time for
the causal-chunk FLOPs and bytes of every traced chunk (the family's
``flash_prefill``) at the chip's peaks, over the summed device time of
the flash kernel."""
import layer


def read(ctx):
    got = layer.roofline(
        ctx, layer.FLASH_KERNEL,
        lambda s: [ctx.family.flash_prefill(ctx.shape, p, n) for p, n, _ in s.chunks])
    return None if got is None else got[0]
