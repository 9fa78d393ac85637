"""Seeded random weights, made by the benchmark: what every model family
shares.

Each layer's leaves come from a key derived from the seed and the layer
index, the tables' from a key of their own.  A family module
(``bench/families/<family>.py``) holds the per-layer and table
generators and makes the served weights on the device in one jitted
call, in the served dtype; the reference calls the same generators one
layer at a time and upcasts, so it never reads what the program holds.

Scales keep activations and logits of order one through any depth: fan-in
scaling for the projections, norm gains ``1 + w`` with small ``w``, and
tables whose logits have a standard deviation of about four.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOGIT_STD = 4.0
NORM_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the low and high 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_key(seed: int, layer: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root_key(seed), 1), layer)


def table_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), 0)
