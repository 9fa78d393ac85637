"""Pallas TPU paged flash-decode kernel: block-table gather via scalar
prefetch.

Same narrow-GEMM/online-softmax structure as ``decode_attention.py`` (the
HPU's GQA-group-packed design point), but the KV cache is a pool of
fixed-size physical blocks shared across sequences.  The per-sequence
``block_tables`` (B, max_blocks) int32 arrive as a *scalar-prefetch*
operand, so the BlockSpec index map — which runs ahead of the kernel body
to program the HBM->VMEM DMAs — can translate logical block ``s`` of
sequence ``b`` into physical pool block ``tables[b, s]``.  This is the
TPU analogue of the HPU prototype's descriptor-driven HBM access: the
bandwidth-bound KV stream is gathered at full rate with no materialized
per-sequence copy.

Grid: ``(B, Hkv, max_blocks)``; the block axis iterates innermost so the
VMEM scratch accumulators carry running max/denominator per (batch, kv
head).  Unused table entries point at physical block 0 (the engine's
null block) — their scores are masked by ``lengths`` so the garbage they
gather never contributes.  A negative entry marks a block this call does
not hold (another lane's, when the pool is split across chips): it is
fetched as block 0 and masked whole.

Tiered-KV extensions (all optional, zero-cost when unused):

* **quantized pools** — when ``k_scale``/``v_scale`` pools are passed
  (``(N_blocks, Hkv, 1, block_size)`` f32, one absmax scale per stored
  vector, positions along lanes so a block's scales are one
  ``(1, block_size)`` tile the TPU BlockSpec rules accept), the K/V pools
  hold int8 or fp8 payloads and the kernel dequantizes *inside* the block
  loop, right after the HBM->VMEM DMA: the bandwidth-bound stream moves
  at 1 byte/elem and widens to f32 only in VMEM.  A position's scale
  multiplies its score column (K) and its probability column (V), which
  equals scaling the stored vectors.
* **``starts``** — per-sequence first *hot* position: positions below it
  are masked exactly like positions past ``lengths``.  This is the hot
  half of the HGCA-style hybrid: cold (host-offloaded) prefix blocks are
  attended elsewhere and merged by log-sum-exp.
* **log-sum-exp output** — the kernel always returns ``(out, lse)`` with
  ``lse = m + log(l)`` per (batch, kv head, group) row, the exact
  quantity LSE merging needs.  A window with no valid positions yields
  ``lse <= NEG_INF`` so its merge weight underflows to 0 (never NaN).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(
    tables_ref,   # SMEM (B, MB) int32 — consumed by the index maps
    lengths_ref,  # SMEM (B,)
    starts_ref,   # SMEM (B,) — first hot position (0 = whole sequence)
    q_ref,        # (1, 1, G, D)
    k_ref,        # (1, 1, block_size, D) — physical block tables[b, s]
    v_ref,        # (1, 1, block_size, D)
    *rest,        # [ks_ref, vs_ref,] o_ref, lse_ref, m/l/acc scratch
    scale: float,
    block_size: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_size, D)
    v = v_ref[0, 0].astype(jnp.float32)

    length = lengths_ref[b]
    start = starts_ref[b]
    k_pos = s * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    valid = (k_pos >= start) & (k_pos < length)   # (1, block_size)
    valid &= tables_ref[b, s] >= 0                # block held elsewhere

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                     # (G, block_size)
    if quantized:
        # per-vector absmax scales, one per key column (1, block_size)
        scores = scores * ks_ref[0, 0]
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)

    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = p * vs_ref[0, 0] if quantized else p    # V scales per value row
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))).astype(
            lse_ref.dtype
        )


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

    def _q_idx(b, h, s, tables, lens, st):
        return (b, h, 0, 0)

    def _kv_idx(b, h, s, tables, lens, st):
        return (jnp.maximum(tables[b, s], 0), h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), _q_idx),
        pl.BlockSpec((1, 1, block_size, D), _kv_idx),
        pl.BlockSpec((1, 1, block_size, D), _kv_idx),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, 1, block_size), _kv_idx),
            pl.BlockSpec((1, 1, 1, block_size), _kv_idx),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, MB),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, G, D), _q_idx),
            pl.BlockSpec((1, 1, G, 1), _q_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=block_size,
        quantized=quantized,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(block_tables, lengths, starts.astype(jnp.int32), *operands)
    return out, lse
