"""jit'd public wrappers around the Pallas kernels.

Handles layout conversion (model layout <-> kernel layout), GQA group
packing, shape padding to hardware-aligned blocks, and the CPU interpret
fallback (``interpret=True`` executes the identical kernel body on CPU,
which is how the kernels are validated in this container).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import flash_attention_pallas


def on_tpu() -> bool:
    """The one backend test: compile the kernels for the TPU there, and
    everywhere else run them in interpret mode (tests) or not at all
    (``core/offload.py`` serves the jnp path off the TPU)."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "block_s"))
def decode_attention(
    q: jax.Array,        # (B, Hq, D) — model layout
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,)
    scale: float | None = None,
    block_s: int = 512,
) -> jax.Array:
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qk = q.reshape(B, Hkv, G, D)                    # pack GQA group
    kk = jnp.swapaxes(k_cache, 1, 2)                # (B, Hkv, S, D)
    vk = jnp.swapaxes(v_cache, 1, 2)
    block = min(block_s, S)
    kk = _pad_to(kk, 2, block)
    vk = _pad_to(vk, 2, block)

    out = decode_attention_pallas(
        qk, kk, vk, lengths.astype(jnp.int32),
        scale=scale, block_s=block, interpret=_interpret(),
    )
    return out.reshape(B, Hq, D)


@functools.partial(jax.jit, static_argnames=("scale", "return_lse", "out_dtype"))
def paged_decode_attention(
    q: jax.Array,             # (B, Hq, D) — model layout
    k_pool: jax.Array,        # (N_blocks, Hkv, block_size, D) — kernel-native
    v_pool: jax.Array,        # (N_blocks, Hkv, block_size, D)
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,       # (B,)
    scale: float | None = None,
    *,
    starts: jax.Array | None = None,    # (B,) first hot position
    k_scale: jax.Array | None = None,   # (N_blocks, Hkv, 1, block_size) f32
    v_scale: jax.Array | None = None,
    return_lse: bool = False,
    out_dtype=None,
):
    """Tiered-KV params: ``k_scale``/``v_scale`` mark the pools as
    int8/fp8 payloads dequantized inside the kernel; ``starts`` restricts
    attention to the hot window ``[start, length)``; ``return_lse``
    additionally returns the per-row log-sum-exp ``(B, Hkv, G) f32`` for
    :func:`repro.kernels.ref.lse_merge`.  ``out_dtype`` (default: q's)
    lets a partial that is merged later stay float32."""
    B, Hq, D = q.shape
    N, Hkv, bs, _ = k_pool.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # the pool is stored kernel-native (see paged_cache_defs): only the
    # tiny per-token q needs packing, the bandwidth-bound KV streams as-is
    qk = q.reshape(B, Hkv, G, D)                  # pack GQA group
    out, lse = paged_decode_attention_pallas(
        qk, k_pool, v_pool,
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
        scale=scale, starts=starts, k_scale=k_scale, v_scale=v_scale,
        out_dtype=out_dtype, interpret=_interpret(),
    )
    out = out.reshape(B, Hq, D)
    if return_lse:
        return out, lse[..., 0]                   # (B, Hkv, G)
    return out


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D) — model layout
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    scale: float | None = None,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    block_q: int = 512,
    block_k: int = 512,
    k_scale: jax.Array | None = None,   # (B, Sk, Hkv) f32
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """``q_offset`` (traced scalar) is the absolute position of q[:, 0] —
    chunked-prefill continuation attends a (Sq=chunk) query block against
    a (Sk=cache) KV window without recompiling per offset.
    ``k_scale``/``v_scale`` mark k/v as int8/fp8 payloads dequantized
    per stored vector inside the kernel."""
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qk = jnp.swapaxes(q, 1, 2)  # (B, Hq, Sq, D)
    kk = jnp.swapaxes(k, 1, 2)
    vk = jnp.swapaxes(v, 1, 2)
    # scales ride lanes in the kernel: (B, Sk, Hkv) -> (B, Hkv, 1, Sk)
    ks = None if k_scale is None else jnp.swapaxes(k_scale, 1, 2)[:, :, None]
    vs = None if v_scale is None else jnp.swapaxes(v_scale, 1, 2)[:, :, None]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    while Sq % bq:
        bq //= 2
    while Sk % bk:
        bk //= 2
    out = flash_attention_pallas(
        qk, kk, vk, scale=scale, causal=causal, q_offset=q_offset,
        k_scale=ks, v_scale=vs,
        block_q=max(bq, 1), block_k=max(bk, 1), interpret=_interpret(),
    )
    return jnp.swapaxes(out, 1, 2)
