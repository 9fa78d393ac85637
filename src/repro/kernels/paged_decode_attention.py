"""Pallas TPU paged flash-decode kernel: one grid step per sequence, the
sequence's live pages streamed by manual DMA.

Same online-softmax structure as ``decode_attention.py`` (the HPU's
GQA-group-packed design point), but the KV cache is a pool of fixed-size
physical blocks shared across sequences.  The per-sequence
``block_tables`` (B, max_blocks) int32, ``lengths`` and ``starts`` arrive
as *scalar-prefetch* operands in SMEM; the K/V pools stay in HBM
(``memory_space=pl.ANY``) and the kernel gathers the pages itself.  This
is the TPU analogue of the HPU prototype's descriptor-driven HBM access:
the bandwidth-bound KV stream is gathered at full rate with no
materialized per-sequence copy.

Grid: ``(B,)``, one step per sequence, all ``Hkv`` heads in it.  The pool
is block-major, so one physical block across all heads,
``k_pool[tables[b, s]]`` of shape ``(Hkv, block_size, D)``, is one
contiguous DMA.  The sequence's table is walked in *page groups* of
``pages`` entries (:func:`pages_per_group`, sized from the shapes to a
fixed VMEM budget): a ``fori_loop`` runs over the groups that overlap the
live window ``[starts[b], lengths[b])`` only, double-buffered, the next
group's DMAs started before the current group is computed.  Within a
group, a page that holds no live position, or whose entry is negative
(a block this call does not hold: another lane's, when the pool is split
across chips), issues no DMA and is masked; its V rows are zeroed in
VMEM so that stale or uninitialized memory never reaches the
probability-weighted sum.  A sequence with an empty window runs no group
and returns ``lse <= NEG_INF``.

On the TPU a pool row of ``D`` < 128 lanes is padded to 128 by the
operand's tiled layout, and Mosaic slices HBM only in whole tiles, so the
DMA moves the padded row (``_lanes``) and the compute reads its first
``D`` lanes.

Tiered-KV extensions (all optional, zero-cost when unused):

* **quantized pools** — when ``k_scale``/``v_scale`` pools are passed
  (``(N_blocks, Hkv, 1, block_size)`` f32, one absmax scale per stored
  vector, positions along lanes), the K/V pools hold int8 or fp8
  payloads, DMA'd at 1 byte/elem with their scale pages and widened only
  in VMEM.  A position's scale multiplies its score column (K) and its
  probability column (V), which equals scaling the stored vectors.
* **``starts``** — per-sequence first *hot* position: positions below it
  are masked exactly like positions past ``lengths``, and groups wholly
  below it are not visited.  This is the hot half of the HGCA-style
  hybrid: cold (host-offloaded) prefix blocks are attended elsewhere and
  merged by log-sum-exp.
* **log-sum-exp output** — the kernel always returns ``(out, lse)`` with
  ``lse = m + log(l)`` per (batch, kv head, group) row, the exact
  quantity LSE merging needs.  A window with no valid positions yields
  ``lse <= NEG_INF`` so its merge weight underflows to 0 (never NaN).

A group's heads are computed together, in as few blocks as
:func:`heads_per_block` lets fit, each block one batched product for the
scores and one for the weighted sum of V: one product per head, of only
``G`` rows, would pay a product's fixed cost ``Hkv`` times a group, which
makes the kernel compute-bound at small ``G``.  Scores multiply the
stored operands in their own precision (bf16 x bf16, or
int8/fp8 widened exactly to q's dtype) with float32 accumulation; the
softmax, the probabilities and the probability-weighted sum of V stay
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM the double-buffered K, V (and scale) pages may take: half the
# 16 MiB scoped-VMEM default of a v5e, leaving room for the q/out blocks,
# the accumulators and a block of heads' widened operands.
VMEM_BUDGET = 8 * 1024 * 1024
# Positions a group spans at most: the compute spent on a group's dead
# tail grows with it, as does a quantized pool's unrolled scale-row
# assembly.
MAX_GROUP_POSITIONS = 512
# Bytes the widened float32 ``(heads, span, D)`` operand of the heads
# computed together may take (lanes padded to 128): a group's heads are
# computed in as few blocks as fit, each block one batched product.
HEAD_BLOCK_BYTES = 4 * 1024 * 1024
_LANE = 128
_SUBLANE = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _lanes(n: int, interpret: bool) -> int:
    """Lanes a DMA moves for a pool row of ``n`` elements: the TPU layout
    pads rows to whole 128-lane tiles; interpret mode has no padding."""
    return n if interpret else _round_up(n, _LANE)


def _widen(x: jax.Array, dtype) -> jax.Array:
    """Exact conversion to ``dtype``; Mosaic converts integer and fp8
    payloads only to float32, which then narrows exactly to bf16."""
    if x.dtype == dtype:
        return x
    return x.astype(jnp.float32).astype(dtype)


def page_bytes(n_kv_heads: int, block_size: int, head_dim: int,
               itemsize: int, quantized: bool) -> int:
    """VMEM one page takes in one buffer slot: K and V rows of
    ``head_dim`` padded to 128 lanes, plus, when quantized, their
    ``(1, block_size)`` f32 scale rows padded likewise."""
    rows = _round_up(block_size, _SUBLANE)
    nbytes = 2 * n_kv_heads * rows * _round_up(head_dim, _LANE) * itemsize
    if quantized:
        nbytes += 2 * n_kv_heads * _round_up(block_size, _LANE) * 4
    return nbytes


def heads_per_block(n_kv_heads: int, span: int, head_dim: int) -> int:
    """Heads computed together: the most that divide ``n_kv_heads`` and
    fit :data:`HEAD_BLOCK_BYTES`."""
    one = _round_up(span, _SUBLANE) * _round_up(head_dim, _LANE) * 4
    fit = max(1, HEAD_BLOCK_BYTES // one)
    return max(h for h in range(1, min(fit, n_kv_heads) + 1)
               if n_kv_heads % h == 0)


def pages_per_group(n_kv_heads: int, block_size: int, head_dim: int,
                    itemsize: int, quantized: bool, max_blocks: int) -> int:
    """Pages one group gathers: as many as two buffer slots fit in
    :data:`VMEM_BUDGET`, spanning at most :data:`MAX_GROUP_POSITIONS`
    and at most the table."""
    one = page_bytes(n_kv_heads, block_size, head_dim, itemsize, quantized)
    return max(1, min(max_blocks, VMEM_BUDGET // (2 * one),
                      MAX_GROUP_POSITIONS // block_size))


def _paged_decode_kernel(
    tables_ref,   # SMEM (B, MB) int32
    lengths_ref,  # SMEM (B,)
    starts_ref,   # SMEM (B,) — first hot position (0 = whole sequence)
    q_ref,        # VMEM (1, Hkv, G, D)
    k_hbm,        # HBM (N_blocks, Hkv, block_size, D)
    v_hbm,
    *rest,        # [ks_hbm, vs_hbm,] o_ref, lse_ref, buffers, sems, m/l/acc
    scale: float,
    block_size: int,
    pages: int,
    hb: int,
    quantized: bool,
    lanes: int,
    scale_lanes: int,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, lse_ref, kbuf, vbuf, ksbuf, vsbuf, sems,
         slot_ref, m_ref, l_ref, acc_ref) = rest
    else:
        (o_ref, lse_ref, kbuf, vbuf, sems, slot_ref, m_ref, l_ref,
         acc_ref) = rest
    b = pl.program_id(0)
    n_seqs = pl.num_programs(0)
    n_heads, G, D = q_ref.shape[1:]
    MB = tables_ref.shape[1]
    bs = block_size
    span = pages * bs
    # scores multiply the stored operands; int8/fp8 widen exactly to q's
    # dtype, and a float pool meets q at the wider of the two
    qk_dtype = (q_ref.dtype if quantized
                else jnp.promote_types(q_ref.dtype, kbuf.dtype))

    def window(seq):
        """(first group, group count) of the groups that overlap
        ``[starts[seq], lengths[seq])``."""
        first = starts_ref[seq] // span
        return first, jnp.maximum(pl.cdiv(lengths_ref[seq], span) - first, 0)

    def page(seq, g, p):
        """(physical block, live) of page ``p`` of group ``g``."""
        e = g * pages + p
        blk = tables_ref[seq, jnp.minimum(e, MB - 1)]
        live = ((e < MB) & (blk >= 0) & (e * bs < lengths_ref[seq])
                & (e * bs + bs > starts_ref[seq]))
        return blk, live

    def copies(seq, g, slot, p):
        blk, live = page(seq, g, p)
        sem = sems.at[slot]
        cps = [
            pltpu.make_async_copy(k_hbm.at[blk, :, :, pl.ds(0, lanes)],
                                  kbuf.at[slot, :, p], sem.at[0]),
            pltpu.make_async_copy(v_hbm.at[blk, :, :, pl.ds(0, lanes)],
                                  vbuf.at[slot, :, p], sem.at[1]),
        ]
        if quantized:
            cps += [
                pltpu.make_async_copy(
                    ks_hbm.at[blk, :, :, pl.ds(0, scale_lanes)],
                    ksbuf.at[slot, :, p], sem.at[0]),
                pltpu.make_async_copy(
                    vs_hbm.at[blk, :, :, pl.ds(0, scale_lanes)],
                    vsbuf.at[slot, :, p], sem.at[1]),
            ]
        return live, cps

    def fetch(seq, g, slot):
        def one(p, carry):
            live, cps = copies(seq, g, slot, p)

            @pl.when(live)
            def _start():
                for cp in cps:
                    cp.start()

            @pl.when(jnp.logical_not(live))
            def _clear():
                # a masked position's probability is 0, and 0 * NaN is NaN:
                # what the weighted sum reads must be finite
                vbuf[slot, :, p] = jnp.zeros(vbuf.shape[1:2] + vbuf.shape[3:],
                                             vbuf.dtype)
                if quantized:
                    vsbuf[slot, :, p] = jnp.zeros(
                        vsbuf.shape[1:2] + vsbuf.shape[3:], vsbuf.dtype)
            return carry

        jax.lax.fori_loop(0, pages, one, 0)

    def wait(seq, g, slot):
        def one(p, carry):
            live, cps = copies(seq, g, slot, p)

            @pl.when(live)
            def _wait():
                for cp in cps:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, pages, one, 0)

    def scale_rows(buf, slot, heads):
        # (hb, pages, 1, lanes) page rows -> (hb, 1, span) positions
        return jnp.concatenate(
            [buf[slot, heads, p, :, :bs] for p in range(pages)], axis=-1)

    def compute(g, slot):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, span), 2)
        pos = g * span + lane
        valid = (pos >= starts_ref[b]) & (pos < lengths_ref[b])

        def held(p, acc):                  # lanes of a page that holds data
            live = page(b, g, p)[1].astype(jnp.int32)
            return jnp.where(lane // bs == p, live, acc)

        valid &= jax.lax.fori_loop(
            0, pages, held, jnp.zeros((1, 1, span), jnp.int32)) > 0

        def block(i, carry):               # heads [i * hb, (i + 1) * hb)
            heads = pl.ds(pl.multiple_of(i * hb, hb), hb)
            q = q_ref[0, heads].astype(qk_dtype)                  # (hb, G, D)
            k = _widen(kbuf[slot, heads][..., :D], qk_dtype)
            s = jnp.einsum("hgd,hsd->hgs", q, k.reshape(hb, span, D),
                           preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * scale_rows(ksbuf, slot, heads)
            s = jnp.where(valid, s, NEG_INF)                      # (hb, G, span)
            m_prev = m_ref[heads]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[heads] = l_ref[heads] * corr + jnp.sum(p, axis=2, keepdims=True)
            if quantized:
                p = p * scale_rows(vsbuf, slot, heads)
            v = _widen(vbuf[slot, heads][..., :D], jnp.float32)
            acc_ref[heads] = acc_ref[heads] * corr + jnp.einsum(
                "hgs,hsd->hgd", p, v.reshape(hb, span, D),
                preferred_element_type=jnp.float32)
            m_ref[heads] = m_new
            return carry

        jax.lax.fori_loop(0, n_heads // hb, block, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # The grid runs the sequences in order and each step starts the DMAs
    # of the next sequence's first group before its own last group is
    # computed, so only the first sequence waits for its first pages.
    # ``slot_ref`` carries the buffer slot that group lands in.
    first, n_groups = window(b)
    base = jnp.where(b == 0, 0, slot_ref[0])
    nxt = jnp.minimum(b + 1, n_seqs - 1)
    nxt_first, nxt_groups = window(nxt)
    has_next = (b + 1 < n_seqs) & (nxt_groups > 0)

    @pl.when((b == 0) & (n_groups > 0))
    def _prime():
        fetch(b, first, 0)

    @pl.when((n_groups == 0) & has_next)
    def _pass_on():
        fetch(nxt, nxt_first, base)

    def group(i, carry):
        g = first + i
        slot = (base + i) % 2

        @pl.when(i + 1 < n_groups)
        def _prefetch():
            fetch(b, g + 1, 1 - slot)

        @pl.when((i + 1 == n_groups) & has_next)
        def _prefetch_next_seq():
            fetch(nxt, nxt_first, 1 - slot)

        wait(b, g, slot)
        compute(g, slot)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    slot_ref[0] = (base + n_groups) % 2

    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,             # (B, Hkv, G, D) — GQA group packed into sublanes
    k_pool: jax.Array,        # (N_blocks, Hkv, block_size, D)
    v_pool: jax.Array,        # (N_blocks, Hkv, block_size, D)
    block_tables: jax.Array,  # (B, max_blocks) int32, physical block ids
                              # (-1: held elsewhere, masked)
    lengths: jax.Array,       # (B,) int32
    *,
    scale: float,
    starts: jax.Array | None = None,    # (B,) int32 first hot position
    k_scale: jax.Array | None = None,   # (N_blocks, Hkv, 1, block_size) f32
    v_scale: jax.Array | None = None,
    out_dtype=None,                     # default: q's dtype
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(out (B,Hkv,G,D), lse (B,Hkv,G,1) f32)``."""
    B, Hkv, G, D = q.shape
    _, _, block_size, _ = k_pool.shape
    MB = block_tables.shape[1]
    quantized = k_scale is not None
    if starts is None:
        starts = jnp.zeros((B,), jnp.int32)
    itemsize = np.dtype(k_pool.dtype).itemsize
    pages = pages_per_group(Hkv, block_size, D, itemsize, quantized, MB)
    lanes = _lanes(D, interpret)
    scale_lanes = _lanes(block_size, interpret)

    def _seq(b, tables, lens, st):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, Hkv, G, D), _seq), hbm, hbm]
    operands = [q, k_pool, v_pool]
    # head-major buffers: one head's pages of a group are contiguous
    kv_buf = (2, Hkv, pages, block_size, lanes)
    scratch = [pltpu.VMEM(kv_buf, k_pool.dtype), pltpu.VMEM(kv_buf, v_pool.dtype)]
    if quantized:
        in_specs += [hbm, hbm]
        operands += [k_scale, v_scale]
        s_buf = (2, Hkv, pages, 1, scale_lanes)
        scratch += [pltpu.VMEM(s_buf, jnp.float32), pltpu.VMEM(s_buf, jnp.float32)]
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),          # (slot, K side / V side)
        pltpu.SMEM((1,), jnp.int32),              # next sequence's first slot
        pltpu.VMEM((Hkv, G, 1), jnp.float32),     # running max
        pltpu.VMEM((Hkv, G, 1), jnp.float32),     # running denominator
        pltpu.VMEM((Hkv, G, D), jnp.float32),     # running numerator
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), _seq),
            pl.BlockSpec((1, Hkv, G, 1), _seq),
        ],
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=block_size,
        pages=pages, hb=heads_per_block(Hkv, pages * block_size, D),
        quantized=quantized, lanes=lanes,
        scale_lanes=scale_lanes,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_decode_attention",
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # in order: a step prefetches for the next
            dimension_semantics=("arbitrary",),
        ),
    )(block_tables, lengths, starts.astype(jnp.int32), *operands)
    return out, lse
