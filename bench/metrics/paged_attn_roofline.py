"""Paged decode attention's share of its roofline: the least time for
the live KV bytes and FLOPs of every traced decode dispatch (each live
position's K and V once, plus queries and outputs: the family's
``paged_attn``) at the chip's peaks, over the summed device time of the
paged kernel."""
import layer


def read(ctx):
    got = layer.roofline(
        ctx, layer.PAGED_KERNEL,
        lambda s: [ctx.family.paged_attn(ctx.shape, s.decode_batch, s.kv_tokens)]
        if s.decode_batch else [])
    return None if got is None else got[0]
