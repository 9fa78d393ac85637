"""Pallas TPU flash-decode kernel — the HPU attention accelerator analogue.

The paper's HPU executes decode attention with a *narrow GEMM engine
optimized for GQA* (up to 8 query heads per KV group, matching its
perf/BW ratio of 8 Ops/Byte).  On TPU we realize the same design point by
packing the GQA group into the MXU sublane dimension:

    scores(G, BLOCK_S) = q(G, D) @ k(BLOCK_S, D)^T       # narrow GEMM
    out   (G, D)       = p(G, BLOCK_S) @ v(BLOCK_S, D)

with an online softmax accumulated in VMEM scratch across sequence
blocks.  KV streams HBM->VMEM in (BLOCK_S, D) tiles (the analogue of the
prototype's 64B-interleaved multi-port HBM access); operational intensity
is ~2*G Ops/Byte — G=8 reproduces the HPU's OI=8, G=1 (MHA) the
prototype's OI~1.

Grid: (B, Hkv, S/BLOCK_S); the sequence axis iterates innermost so the
scratch accumulators carry the running max/denominator per (batch, kv
head).  ``lengths`` masks the tail of partially-filled caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(
    lengths_ref,  # SMEM (B,)
    q_ref,        # (1, 1, G, D)
    k_ref,        # (1, 1, BLOCK_S, D)
    v_ref,        # (1, 1, BLOCK_S, D)
    o_ref,        # (1, 1, G, D)
    m_ref,        # VMEM scratch (G, 1) f32
    l_ref,        # VMEM scratch (G, 1) f32
    acc_ref,      # VMEM scratch (G, D) f32
    *,
    scale: float,
    block_s: int,
):
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BLOCK_S, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (BLOCK_S, D)

    length = lengths_ref[b]
    k_pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    valid = k_pos < length                        # (1, BLOCK_S)

    # narrow GEMM: (G, D) x (D, BLOCK_S)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                     # (G, BLOCK_S)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]                           # (G, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)                   # (G, BLOCK_S)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                # (G, 1)

    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,          # (B, Hkv, G, D)  — group packed into sublanes
    k: jax.Array,          # (B, Hkv, S, D)
    v: jax.Array,          # (B, Hkv, S, D)
    lengths: jax.Array,    # (B,) int32
    *,
    scale: float,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, D = q.shape
    S = k.shape[2]
    assert S % block_s == 0, (S, block_s)
    n_s = S // block_s

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s, lens: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s, lens: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="decode_attention",
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(lengths, q, k, v)
