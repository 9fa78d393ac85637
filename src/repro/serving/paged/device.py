"""Jitted device ops on the physical block pool arrays.

The pool K/V leaves are laid out kernel-native, ``(layers, n_blocks,
kv_heads, block_size, head_dim)`` (``models.*.paged_cache_defs``, heads
before positions so decode attention streams it without relayout); all
host-side
allocator decisions reduce to three device primitives: scatter a prefill
slice into a block, duplicate a block (copy-on-write), and refresh one
block-table row.  Block ids arrive as traced scalars so admission never
recompiles.

Two whole-block transfer families ride the same layout: host-tier
moves (:func:`spill_block` / :func:`rehydrate_block`, device<->host in
storage dtype) and cross-replica migration
(:func:`copy_blocks_out` gathers a block-id list into a compact
payload, :func:`copy_blocks_in` scatters it into the destination pool —
quantized pools move payload + scale pools as-is, bit-exact, no
dequant/requant round trip).

The module also hosts the async engine's tiny per-slot state vectors
(:func:`feed_token` token feedback, :func:`set_stop_id` stop flags):
same donated, recompile-free update pattern, shared by both cache kinds.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

# the pool argument is donated: these are in-place block updates and the
# engine always replaces its cache reference, so XLA may alias in->out
# instead of copying the whole (L, n_blocks, ...) pool per call.  A
# donation that fails warns ("Some donated buffers were not usable"): on
# a TPU that is a silent pool-sized copy per call, so it stays visible
# (only the test configuration silences it, for the CPU backend, which
# implements no donation).
_donate0 = functools.partial(jax.jit, donate_argnums=(0,))


@_donate0
def _copy_block(pool: jax.Array, src, dst) -> jax.Array:
    return pool.at[:, dst].set(pool[:, src])


@_donate0
def _write_block(pool: jax.Array, sub: jax.Array, phys, start, lane) -> jax.Array:
    """Copy ``sub[:, lane, start:start+block_size]`` into pool block
    ``phys``.

    The prefill sub-cache is sequence-major (L, lanes, S, Hkv, Dh); one
    block's worth is transposed to the pool's heads-major layout here —
    a (block_size, Hkv) tile per layer, negligible next to the pool.
    ``lane`` is a traced scalar: boundary packing runs two prefills in
    the same staging cache and drains either lane without recompiling.
    """
    bs = pool.shape[3]
    blk = jax.lax.dynamic_slice_in_dim(sub[:, lane], start, bs, axis=1)
    blk = jnp.swapaxes(blk, 1, 2)                 # (L, Hkv, bs, Dh)
    return jax.lax.dynamic_update_slice(
        pool, blk[:, None].astype(pool.dtype), (0, phys, 0, 0, 0)
    )


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("kv_dtype",)
)
def _write_block_q(
    pool: jax.Array, spool: jax.Array, sub: jax.Array, phys, start, lane,
    *, kv_dtype: str,
) -> tuple[jax.Array, jax.Array]:
    """Quantizing :func:`_write_block`: the bf16 staging tile quantizes
    per (head, position) vector on the way into the pool; the scale pool
    gets the matching (L, Hkv, 1, bs) tile."""
    from repro.kernels import ref

    bs = pool.shape[3]
    blk = jax.lax.dynamic_slice_in_dim(sub[:, lane], start, bs, axis=1)
    blk = jnp.swapaxes(blk, 1, 2)                 # (L, Hkv, bs, Dh)
    payload, scale = ref.kv_quantize_pool(blk, kv_dtype)
    pool = jax.lax.dynamic_update_slice(pool, payload[:, None], (0, phys, 0, 0, 0))
    spool = jax.lax.dynamic_update_slice(spool, scale[:, None], (0, phys, 0, 0, 0))
    return pool, spool


def copy_block(cache: Pytree, src: int, dst: int) -> Pytree:
    """COW: duplicate physical block ``src`` into ``dst`` (k and v, and
    their scale blocks when the pool is quantized)."""
    out = {
        **cache,
        "k": _copy_block(cache["k"], src, dst),
        "v": _copy_block(cache["v"], src, dst),
    }
    if "k_scale" in cache:
        out["k_scale"] = _copy_block(cache["k_scale"], src, dst)
        out["v_scale"] = _copy_block(cache["v_scale"], src, dst)
    return out


def write_prompt_block(
    cache: Pytree, sub_cache: Pytree, phys: int, start: int, lane: int = 0,
) -> Pytree:
    """Scatter prompt KV positions ``[start, start+block_size)`` from a
    prefill staging lane (seq padded to a block multiple) into physical
    block ``phys`` — quantizing on the way in when the pool is int8/fp8
    (the staging cache always holds full-precision KV)."""
    if "k_scale" in cache:
        kv_dtype = "int8" if cache["k"].dtype == jnp.int8 else "fp8"
        k, ks = _write_block_q(
            cache["k"], cache["k_scale"], sub_cache["k"], phys, start, lane,
            kv_dtype=kv_dtype,
        )
        v, vs = _write_block_q(
            cache["v"], cache["v_scale"], sub_cache["v"], phys, start, lane,
            kv_dtype=kv_dtype,
        )
        return {**cache, "k": k, "v": v, "k_scale": ks, "v_scale": vs}
    return {
        **cache,
        "k": _write_block(cache["k"], sub_cache["k"], phys, start, lane),
        "v": _write_block(cache["v"], sub_cache["v"], phys, start, lane),
    }


@_donate0
def _read_block(sub: jax.Array, pool: jax.Array, phys, start, lane) -> jax.Array:
    """Inverse of ``_write_block``: copy pool block ``phys`` into staging
    lane ``lane`` at positions [start, start+block_size)."""
    blk = jnp.swapaxes(pool[:, phys], 1, 2)[:, None]   # (L, 1, bs, Hkv, Dh)
    return jax.lax.dynamic_update_slice(
        sub, blk.astype(sub.dtype), (0, lane, start, 0, 0)
    )


@_donate0
def _read_block_q(
    sub: jax.Array, pool: jax.Array, spool: jax.Array, phys, start, lane,
) -> jax.Array:
    """Dequantizing :func:`_read_block` for int8/fp8 pools."""
    from repro.kernels import ref

    blk = ref.kv_dequantize(pool[:, phys], spool[:, phys, :, 0], sub.dtype)
    blk = jnp.swapaxes(blk, 1, 2)[:, None]             # (L, 1, bs, Hkv, Dh)
    return jax.lax.dynamic_update_slice(sub, blk, (0, lane, start, 0, 0))


def read_block(
    sub_cache: Pytree, cache: Pytree, phys: int, start: int, lane: int = 0,
) -> Pytree:
    """Hydrate a prefill staging lane from a prefix-cache-hit block, so
    chunked-prefill attention sees the shared prefix's K/V without
    recomputing it.  Quantized pools dequantize on the way out (staging
    stays full precision)."""
    if "k_scale" in cache:
        return {
            **sub_cache,
            "k": _read_block_q(
                sub_cache["k"], cache["k"], cache["k_scale"], phys, start, lane
            ),
            "v": _read_block_q(
                sub_cache["v"], cache["v"], cache["v_scale"], phys, start, lane
            ),
        }
    return {
        **sub_cache,
        "k": _read_block(sub_cache["k"], cache["k"], phys, start, lane),
        "v": _read_block(sub_cache["v"], cache["v"], phys, start, lane),
    }


@_donate0
def _xfer_block(dst_pool: jax.Array, src_pool: jax.Array, src, dst) -> jax.Array:
    """Copy one block between two pools with the same trailing layout
    (device<->host spill traffic; payloads move in storage dtype, so a
    quantized block spills quantized — 1 byte/elem over the slow link)."""
    return dst_pool.at[:, dst].set(src_pool[:, src].astype(dst_pool.dtype))


def spill_block(cache: Pytree, dev: int, host: int) -> Pytree:
    """Apply a ``("spill", dev, host)`` directive: copy device block
    ``dev`` into host-tier block ``host`` (k, v, and scales)."""
    out = {
        **cache,
        "host_k": _xfer_block(cache["host_k"], cache["k"], dev, host),
        "host_v": _xfer_block(cache["host_v"], cache["v"], dev, host),
    }
    if "k_scale" in cache:
        out["host_k_scale"] = _xfer_block(cache["host_k_scale"], cache["k_scale"], dev, host)
        out["host_v_scale"] = _xfer_block(cache["host_v_scale"], cache["v_scale"], dev, host)
    return out


def rehydrate_block(cache: Pytree, host: int, dev: int) -> Pytree:
    """Apply a ``("rehydrate", host, dev)`` directive: copy host-tier
    block ``host`` back into device block ``dev``."""
    out = {
        **cache,
        "k": _xfer_block(cache["k"], cache["host_k"], host, dev),
        "v": _xfer_block(cache["v"], cache["host_v"], host, dev),
    }
    if "k_scale" in cache:
        out["k_scale"] = _xfer_block(cache["k_scale"], cache["host_k_scale"], host, dev)
        out["v_scale"] = _xfer_block(cache["v_scale"], cache["host_v_scale"], host, dev)
    return out


# NOT donated: the gathered payload must outlive the source pool (the
# exporting engine keeps stepping while the destination lands the copy)
@jax.jit
def _gather_blocks(pool: jax.Array, ids: jax.Array) -> jax.Array:
    return pool[:, ids]


@_donate0
def _scatter_blocks(
    pool: jax.Array, payload: jax.Array, src_sel: jax.Array, dst_ids: jax.Array
) -> jax.Array:
    return pool.at[:, dst_ids].set(payload[:, src_sel].astype(pool.dtype))


def copy_blocks_out(cache: Pytree, ids: list[int]) -> Pytree:
    """Gather a migrating sequence's physical blocks out of this pool in
    **storage dtype**: a quantized pool exports its int8/fp8 payload bytes
    plus the matching scale-pool tiles, so migration across replicas of
    the same ``kv_dtype`` tier is bit-exact (no dequant/requant round
    trip).  Returns a ``{"k": (L, n, Hkv, bs, Dh), ...}`` payload pytree
    detached from the pool (the source keeps stepping afterwards)."""
    idx = jnp.asarray(ids, jnp.int32)
    out = {
        "k": _gather_blocks(cache["k"], idx),
        "v": _gather_blocks(cache["v"], idx),
    }
    if "k_scale" in cache:
        out["k_scale"] = _gather_blocks(cache["k_scale"], idx)
        out["v_scale"] = _gather_blocks(cache["v_scale"], idx)
    return out


def copy_blocks_in(
    cache: Pytree, payload: Pytree, src_sel: list[int], dst_ids: list[int]
) -> Pytree:
    """Scatter payload columns ``src_sel`` (positions in the exported
    block list) into this pool's blocks ``dst_ids``.  The selection lets
    the importer skip positions its own prefix cache already holds
    (``BlockPool.import_blocks`` dedup).  Storage-dtype on both sides:
    same-tier migration moves bytes, never values."""
    sel = jnp.asarray(src_sel, jnp.int32)
    idx = jnp.asarray(dst_ids, jnp.int32)
    out = {
        **cache,
        "k": _scatter_blocks(cache["k"], payload["k"], sel, idx),
        "v": _scatter_blocks(cache["v"], payload["v"], sel, idx),
    }
    if "k_scale" in cache:
        out["k_scale"] = _scatter_blocks(
            cache["k_scale"], payload["k_scale"], sel, idx
        )
        out["v_scale"] = _scatter_blocks(
            cache["v_scale"], payload["v_scale"], sel, idx
        )
    return out


@_donate0
def _set_row(tables: jax.Array, slot, row: jax.Array) -> jax.Array:
    return tables.at[slot].set(row)


# NOT donated: the async engine's pending-step records may still hold a
# reference to the array being updated (it doubles as a step output)
@jax.jit
def _set_scalar(arr: jax.Array, slot, value) -> jax.Array:
    return arr.at[slot].set(value)


def feed_token(tok_state: jax.Array, slot: int, token) -> jax.Array:
    """Async engine: push one slot's next decode input into the
    device-resident token feedback vector (``token`` may be a host int or
    a 0-d device array — a prefill's first sampled token never needs to
    round-trip through the host before the next decode step consumes
    it).  Used for both cache kinds; lives here with the engine's other
    donated per-slot device primitives."""
    return _set_scalar(tok_state, slot, jnp.asarray(token, jnp.int32))


def set_stop_id(eos_ids: jax.Array, slot: int, eos_id: int) -> jax.Array:
    """Refresh one slot's on-device stop id (-1 = never stops).  The
    fused sampled step compares each sampled token against this vector to
    produce the per-slot EOS flag the host observes one step late."""
    return _set_scalar(eos_ids, slot, jnp.int32(eos_id))


def _row_snapshot(row) -> jax.Array:
    """A host table row as it is now.  On the CPU the device may alias an
    aligned host buffer and read it only when the queued push runs, after
    the manager has rewritten the row in place (a freed slot's zeros
    replaced by its next request's blocks), so the copy is made here, on
    the host."""
    return jnp.asarray(np.array(row, np.int32))


def sync_slot(cache: Pytree, slot: int, row, length: int | None = None) -> Pytree:
    """Push one host block-table row (and optionally the slot length) to
    the device cache."""
    out = {
        **cache,
        "block_tables": _set_row(cache["block_tables"], slot, _row_snapshot(row)),
    }
    if length is not None:
        out["lengths"] = out["lengths"].at[slot].set(jnp.int32(length))
    return out


def sync_host_slot(cache: Pytree, slot: int, row, cold_len: int) -> Pytree:
    """Push one slot's host block-table row and cold-prefix length (the
    hot attention window's start) to the device cache."""
    out = {
        **cache,
        "host_tables": _set_row(cache["host_tables"], slot, _row_snapshot(row)),
    }
    out["cold_lengths"] = _set_scalar(out["cold_lengths"], slot, jnp.int32(cold_len))
    return out
