"""Pure-jnp oracles for the Pallas kernels (naive, O(S^2) memory).

Tiered-KV additions: per-vector absmax KV quantization helpers
(:func:`kv_quantize` / :func:`kv_dequantize` — the single definition the
device scatter path and the kernels' oracle params share), ``starts``
windows and ``return_lse`` variants on the decode oracles, and
:func:`lse_merge` — the log-sum-exp combination of partial attention
outputs the HGCA-style hybrid (hot device kernel + cold host oracle)
is validated against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# kv_dtype name -> (storage dtype, absmax quantization range)
KV_DTYPES = {
    "fp8": (jnp.float8_e4m3fn, 448.0),
    "int8": (jnp.int8, 127.0),
}


def kv_quantize(x: jax.Array, kv_dtype: str) -> tuple[jax.Array, jax.Array]:
    """Per-vector absmax quantization over the trailing (head_dim) axis:
    ``x (..., D)`` -> ``(payload (..., D) int8|fp8, scale (...) f32)``
    with ``payload * scale ~= x``.  An all-zero vector gets scale 0."""
    dtype, qmax = KV_DTYPES[kv_dtype]
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / qmax
    q = x.astype(jnp.float32) / jnp.maximum(scale[..., None], 1e-30)
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(dtype), scale


def kv_quantize_pool(pool: jax.Array, kv_dtype: str) -> tuple[jax.Array, jax.Array]:
    """:func:`kv_quantize` of a kernel-native pool ``(..., Hkv, bs, D)``
    into the pool layout: ``(payload (..., Hkv, bs, D), scale pool
    (..., Hkv, 1, bs) f32)`` -- a block's scales lie along lanes, the one
    scale-block shape the TPU compiler accepts."""
    payload, scale = kv_quantize(pool, kv_dtype)
    return payload, scale[..., None, :]


def kv_dequantize(payload: jax.Array, scale: jax.Array,
                  out_dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`kv_quantize`: ``payload (..., D)`` * ``scale
    (...)`` -> ``(..., D)`` in ``out_dtype``."""
    return (payload.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(out_dtype)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int | None = None,
    k_scale: jax.Array | None = None,   # (B, Sk, Hkv) f32
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D) -> (B,Sq,Hq,Dv).  fp32 softmax.

    ``q_offset`` places q[:, 0] at an absolute position (chunked-prefill
    continuation); default keeps the historical right-aligned causal mask
    (offset ``Sk - Sq``).  ``k_scale``/``v_scale`` dequantize int8/fp8
    K/V payloads per stored vector."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if causal:
        off = Sk - Sq if q_offset is None else q_offset
        q_pos = off + jnp.arange(Sq, dtype=jnp.int32)[:, None]
        mask = q_pos >= jnp.arange(Sk, dtype=jnp.int32)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, Hq, vf.shape[-1]).astype(q.dtype)


def gather_paged_cache(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(N_blocks, Hkv, block_size, D) kernel-native pool + (B, max_blocks)
    tables -> contiguous dense-layout (B, max_blocks*block_size, Hkv, D)
    cache, positions in logical order.  The single definition of the
    block-table gather the non-Pallas paths rely on."""
    N, Hkv, bs, D = pool.shape
    B, MB = block_tables.shape
    return jnp.swapaxes(pool[block_tables], 2, 3).reshape(B, MB * bs, Hkv, D)


def gather_paged_scales(spool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(N_blocks, Hkv, 1, block_size) scale pool + (B, max_blocks) tables
    -> dense-layout (B, max_blocks*block_size, Hkv) scales."""
    N, Hkv, _, bs = spool.shape
    B, MB = block_tables.shape
    return jnp.swapaxes(spool[:, :, 0][block_tables], 2, 3).reshape(B, MB * bs, Hkv)


def paged_decode_attention(
    q: jax.Array,             # (B, Hq, D)
    k_pool: jax.Array,        # (N_blocks, Hkv, block_size, D) — kernel-native
    v_pool: jax.Array,        # (N_blocks, Hkv, block_size, D)
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,       # (B,)
    *,
    scale: float | None = None,
    starts: jax.Array | None = None,
    k_scale: jax.Array | None = None,   # (N_blocks, Hkv, 1, block_size) f32
    v_scale: jax.Array | None = None,
    return_lse: bool = False,
):
    """Oracle for the paged kernel: gather each sequence's blocks into a
    contiguous cache, then run the dense decode oracle.  Positions beyond
    ``lengths`` (including whatever the null block holds) — and below
    ``starts`` when given — are masked there.  Quantized pools are
    dequantized after the gather via the per-vector scale pools."""
    k = gather_paged_cache(k_pool, block_tables).astype(jnp.float32)
    v = gather_paged_cache(v_pool, block_tables).astype(jnp.float32)
    if k_scale is not None:
        k = k * gather_paged_scales(k_scale, block_tables)[..., None]
        v = v * gather_paged_scales(v_scale, block_tables)[..., None]
    return naive_decode_attention(q, k, v, lengths, scale=scale,
                                  starts=starts, return_lse=return_lse)


def naive_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
    starts: jax.Array | None = None,
    return_lse: bool = False,
):
    """q (B,Hq,D), caches (B,S,Hkv,D), lengths (B,) -> (B,Hq,D).

    ``starts`` (B,) masks positions below it (a hot/cold attention
    window); ``return_lse`` additionally returns the per-row
    log-sum-exp ``(B, Hkv, G) f32`` for :func:`lse_merge`.  A row with
    no valid positions yields output 0 and lse <= NEG_INF (never NaN)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kf) * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    mask = pos[None] < lengths[:, None]            # (B,S)
    if starts is not None:
        mask &= pos[None] >= starts[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, vf) / jnp.maximum(l, 1e-30)
    out = o.reshape(B, Hq, vf.shape[-1]).astype(q.dtype)
    if return_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]   # (B, Hkv, G)
        return out, lse
    return out


def lse_merge(parts: list) -> jax.Array:
    """Combine partial attention outputs over disjoint KV windows.

    ``parts`` is a list of ``(out (B,Hq,D), lse (B,Hkv,G))`` pairs, each
    the softmax-normalized attention over its own window; the exact
    combined attention is the lse-softmax-weighted sum.  Windows with no
    valid positions carry ``lse <= NEG_INF`` and get weight ~0; if every
    window is empty the result is 0 (never NaN)."""
    outs = jnp.stack([o.astype(jnp.float32) for o, _ in parts])  # (P,B,Hq,D)
    lses = jnp.stack([l.astype(jnp.float32) for _, l in parts])  # (P,B,Hkv,G)
    m = jnp.max(lses, axis=0)
    w = jnp.exp(lses - m[None])                                  # (P,B,Hkv,G)
    w = w / jnp.maximum(jnp.sum(w, axis=0), 1e-30)[None]
    P, B, Hkv, G = lses.shape
    wf = w.reshape(P, B, Hkv * G, 1)
    return jnp.sum(outs * wf, axis=0).astype(parts[0][0].dtype)
