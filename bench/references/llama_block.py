"""Plain float32 forward of a llama-style decoder block stack.

Follows the published block: RMSNorm with gain ``1 + w``, rotary
embedding on query and key (the half-split form, theta from the config),
grouped-query causal softmax attention scaled by ``1/sqrt(head_dim)``, an
output projection, a second RMSNorm and a SwiGLU MLP, both on the
residual stream; a final RMSNorm and the output head (the embedding when
tied).  No kernel, cache or batching of the program is used: weights come
from the benchmark's own seeded generator (the ``dense`` family's
``layer_leaves`` and ``table_leaves``, ``bench/families/dense.py``), one
layer at a time, so the reference fits beside nothing else on the device
once the program has been freed.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale (weights) or per-row scale
(activations), the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights

Q_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = E4M3_MAX / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(spec: str, a, b, fp8: bool):
    """einsum with the activation ``a`` first and the weight ``b``."""
    if fp8:
        a = _fp8(a, axis=-1)
        b = _fp8(b, axis=None)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """x (N, S, H, Dh), pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def _layer(h, w, *, s, fp8: bool):
    """h (N, S, D) float32 -> next residual stream."""
    N, S, D = h.shape
    G = s.n_heads // s.n_kv_heads
    pos = jnp.arange(S)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = _rmsnorm(h, w["ln1"], s.norm_eps)
    q = _rope(_mm("nsd,dhk->nshk", x, w["wq"], fp8), pos, s.rope_theta)
    k = _rope(_mm("nsd,dhk->nshk", x, w["wk"], fp8), pos, s.rope_theta)
    v = _mm("nsd,dhk->nshk", x, w["wv"], fp8)
    q = q.reshape(N, S, s.n_kv_heads, G, s.head_dim) / math.sqrt(s.head_dim)
    outs = []
    for q0 in range(0, S, Q_BLOCK):                      # query blocks
        qb = q[:, q0:q0 + Q_BLOCK]
        sc = _mm("nqhgk,nshk->nhgqs", qb, k, fp8)
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("nhgqs,nshk->nqhgk", p, v, fp8))
    o = jnp.concatenate(outs, 1).reshape(N, S, s.n_heads, s.head_dim)
    h = h + _mm("nshk,hkd->nsd", o, w["wo"], fp8)
    x = _rmsnorm(h, w["ln2"], s.norm_eps)
    g = _mm("nsd,df->nsf", x, w["w_gate"], fp8)
    u = _mm("nsd,df->nsf", x, w["w_up"], fp8)
    return h + _mm("nsf,fd->nsd", jax.nn.silu(g) * u, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(tokens, tables, *, s):
    return tables["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def _head(h, rows, tables, *, s, fp8: bool):
    """Logits at the gathered rows: h (N, S, D), rows (N, R) -> (N, R, V)."""
    x = jnp.take_along_axis(h, rows[..., None], axis=1)
    x = _rmsnorm(x, tables["final_norm"].astype(jnp.float32), s.norm_eps)
    table = tables.get("unembed", tables["embed"])[:s.vocab].astype(jnp.float32)
    return _mm("nrd,vd->nrv", x, table, fp8)


def _bucket(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def logits_at(family, s, seed: int, seqs: list[np.ndarray],
              rows: list[np.ndarray], precision: str = "f32"):
    """Logits of each sequence at the given positions, one sequence and
    one layer at a time, with the weights ``family`` makes for the sizes
    ``s`` (its ``shape``) and ``seed``.  Sequences are padded at the end to one length
    (causal attention leaves the real positions unchanged), so the few
    programs compiled here are shared across runs.  Yields one
    ``(len(rows[i]), vocab)`` device array per sequence."""
    fp8 = precision == "fp8"
    S = _bucket(max(len(t) for t in seqs), Q_BLOCK)
    R = _bucket(max(len(r) for r in rows), 128)
    tables = jax.jit(lambda k: family.table_leaves(s, k))(weights.table_key(seed))
    with jax.default_matmul_precision("highest"):
        hs = []
        for t in seqs:
            tok = np.zeros((1, S), np.int32)
            tok[0, :len(t)] = t
            hs.append(_embed(jnp.asarray(tok), tables, s=s))
        make = jax.jit(lambda k: family.layer_leaves(s, k))
        for layer in range(s.n_layers):
            w = make(weights.layer_key(seed, layer))
            hs = [_layer(h, w, s=s, fp8=fp8) for h in hs]
            del w
        for h, r in zip(hs, rows):
            rr = np.zeros((1, R), np.int32)
            rr[0, :len(r)] = r
            yield _head(h, jnp.asarray(rr), tables, s=s, fp8=fp8)[0, :len(r)]
