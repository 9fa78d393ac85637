"""Seeded random weights for a dense decoder, made by the benchmark.

One generator makes each layer's leaves from a key derived from the seed
and the layer index.  The served weights are every layer stacked, made on
the device in one jitted call (``lax.map`` over the layers, in the served
dtype); the reference calls the same per-layer generator one layer at a
time and upcasts, so it never reads what the program holds.

Scales keep activations and logits of order one through any depth: fan-in
scaling for the projections, norm gains ``1 + w`` with small ``w``, and
tables whose logits have a standard deviation of about four.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from spec import Shape

LOGIT_STD = 4.0
NORM_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the low and high 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_leaves(s: Shape, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """One layer's weights, in the program's dense block layout."""
    D, F, Hq, Hkv, Dh = s.d_model, s.d_ff, s.n_heads, s.n_kv_heads, s.head_dim
    ks = jax.random.split(key, 9)
    return {
        "ln1": _normal(ks[0], (D,), NORM_STD, dtype),
        "wq": _normal(ks[1], (D, Hq, Dh), D ** -0.5, dtype),
        "wk": _normal(ks[2], (D, Hkv, Dh), D ** -0.5, dtype),
        "wv": _normal(ks[3], (D, Hkv, Dh), D ** -0.5, dtype),
        "wo": _normal(ks[4], (Hq, Dh, D), (Hq * Dh) ** -0.5, dtype),
        "ln2": _normal(ks[5], (D,), NORM_STD, dtype),
        "w_gate": _normal(ks[6], (D, F), D ** -0.5, dtype),
        "w_up": _normal(ks[7], (D, F), D ** -0.5, dtype),
        "w_down": _normal(ks[8], (F, D), F ** -0.5, dtype),
    }


def table_leaves(s: Shape, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Embedding, output head (untied only) and final norm.  Rows past the
    true vocabulary are zero: the program masks their logits."""
    D = s.d_model
    ks = jax.random.split(key, 3)
    live = jnp.arange(s.padded_vocab)[:, None] < s.vocab

    def table(k):
        t = _normal(k, (s.padded_vocab, D), LOGIT_STD * D ** -0.5, dtype)
        return jnp.where(live, t, jnp.zeros((), dtype))

    out = {"embed": table(ks[0]),
           "final_norm": _normal(ks[2], (D,), NORM_STD, dtype)}
    if not s.tied:
        out["unembed"] = table(ks[1])
    return out


def layer_key(seed: int, layer: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root_key(seed), 1), layer)


def table_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), 0)


def served_params(s: Shape, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf of the served model on the default device, from one
    jitted call."""

    def make(lkeys, tkey):
        blocks = jax.lax.map(lambda k: layer_leaves(s, k, dtype), lkeys)
        return {"blocks": blocks, **table_leaves(s, tkey, dtype)}

    lkeys = jnp.stack([layer_key(seed, i) for i in range(s.n_layers)])
    return jax.jit(make)(lkeys, table_key(seed))
