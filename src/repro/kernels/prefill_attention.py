"""Pallas TPU causal flash attention (prefill/train path).

Standard blocked online-softmax flash attention; GQA is handled in the
BlockSpec index maps (the KV block index maps q-head -> q_head // group),
so no KV replication materializes in HBM.

Grid: (B, Hq, NQ, NK) with NK innermost; causally-skipped KV blocks
contribute nothing (masked) — the index arithmetic keeps the common
diagonal path hot.

``q_offset`` (scalar-prefetch operand, SMEM) shifts the absolute position
of q[:, 0] for chunked-prefill continuation: a (Sq, Sk) = (chunk, cache)
call attends the chunk against all earlier cache positions while staying
causal inside the chunk.  It is a traced scalar — serving one prompt at
many offsets reuses a single compiled kernel.

Optional ``k_scale``/``v_scale`` ((B, Hkv, 1, Sk) f32, one absmax scale
per stored KV vector, positions along lanes) mark the K/V operands as
int8/fp8 payloads: the kernel
dequantizes right after the HBM->VMEM load, so a quantized KV window
streams at 1 byte/elem and widens to f32 only in VMEM (the tiered-KV
counterpart of the paged decode kernel's quantized pools).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_kernel(
    off_ref,  # SMEM (1,) int32 — absolute position of q[:, 0]
    q_ref,    # (1, 1, BQ, D)
    k_ref,    # (1, 1, BK, D)
    v_ref,    # (1, 1, BK, D)
    *rest,    # [ks_ref, vs_ref (1, 1, 1, BK),] o_ref, m/l/acc scratch
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)
    off = off_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                              # (BQ, BK)
        if quantized:
            # per-vector absmax scales, one per key column (1, BK)
            scores = scores * ks_ref[0, 0]
        if causal:
            q_pos = off + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        if causal:
            p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[0, 0] if quantized else p   # V scales per value row
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip fully-masked blocks (k block entirely in the future);
        # dynamic in `off` — a traced predicate, not a grid prune
        @pl.when(ik * block_k <= off + iq * block_q + block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # (B, Hq, Sq, D)
    k: jax.Array,   # (B, Hkv, Sk, D)
    v: jax.Array,   # (B, Hkv, Sk, D)
    *,
    scale: float,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    k_scale: jax.Array | None = None,   # (B, Hkv, 1, Sk) f32
    v_scale: jax.Array | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    quantized = k_scale is not None

    def _q_idx(b, h, iq, ik, off):
        return (b, h, iq, 0)

    def _kv_idx(b, h, iq, ik, off):
        return (b, h // G, ik, 0)

    def _scale_idx(b, h, iq, ik, off):
        return (b, h // G, 0, ik)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), _q_idx),
        pl.BlockSpec((1, 1, block_k, D), _kv_idx),
        pl.BlockSpec((1, 1, block_k, D), _kv_idx),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, 1, block_k), _scale_idx),
            pl.BlockSpec((1, 1, 1, block_k), _scale_idx),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, Sq // block_q, Sk // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(off, *operands)
