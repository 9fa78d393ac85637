"""The dense decoder family: the llama-style block (RMSNorm, rotary
grouped-query attention, SwiGLU MLP) that the program serves as
``family=DENSE``.  A configuration names it with ``"family": "dense"``;
its plain reference is ``bench/references/llama_block.py``.

Everything of the benchmark that depends on the block is here: the sizes
read from the configuration's HF keys, the program's ``ModelConfig``,
the seeded weights in the program's layout, the configuration at the CPU
rehearsal's size, and the work counts that ``mfu.throughput`` and the
kernel rooflines divide by (adapted from the program's
``analysis/roofline.py:dispatch_flops_bytes``, kept here so that no
change to the program moves the yardstick).

The counts are of the algorithm, not of what a kernel happens to do: a
decode lane attends its live context, a prefill chunk at offset ``pos``
of ``n`` tokens attends ``n*pos + n(n+1)/2`` positions causally, logits
are needed once per decode token and once per completed prompt, and the
paged kernel needs each live KV position once.  A kernel that skips dead
work reads as a higher share; one that is replaced is counted on the
same work.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

import cost
import weights
from weights import LOGIT_STD, NORM_STD, normal

# the CPU rehearsal's widths; the KV heads keep MHA as MHA and make GQA 2:1
REHEARSAL = dict(hidden_size=64, num_attention_heads=4, intermediate_size=128,
                 num_hidden_layers=2, vocab_size=500)


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the benchmark's own code needs (cost model, reference,
    weights), read from the HF-style configuration file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * 2

    @property
    def n_params(self) -> int:
        tables = (1 if self.tied else 2) * self.padded_vocab * self.d_model
        return self.n_layers * (layer_params(self) + 2 * self.d_model) \
            + tables + self.d_model


def shape(config: dict[str, Any], vocab_multiple: int = 256) -> Shape:
    c = config
    heads = c["num_attention_heads"]
    vocab = c["vocab_size"]
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=heads, n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"], vocab=vocab,
        padded_vocab=-(-vocab // vocab_multiple) * vocab_multiple,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]),
    )


def model_config(config: dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file (imports
    the system under test)."""
    from repro.configs.base import DENSE, ModelConfig

    s = shape(config)
    return ModelConfig(
        name=config["name"], family=DENSE, n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
        d_ff=s.d_ff, vocab=s.vocab, head_dim=s.head_dim,
        max_seq=config["max_position_embeddings"], rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tied,
        dtype=config["torch_dtype"],
    )


def rehearsal(config: dict[str, Any]) -> dict[str, Any]:
    """The configuration at a size the CPU runs in seconds (tests only)."""
    c = dict(config)
    group = c["num_attention_heads"] // c["num_key_value_heads"]
    c.update(REHEARSAL, num_key_value_heads=4 if group == 1 else 2)
    return c


def layer_leaves(s: Shape, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """One layer's weights, in the program's dense block layout."""
    D, F, Hq, Hkv, Dh = s.d_model, s.d_ff, s.n_heads, s.n_kv_heads, s.head_dim
    ks = jax.random.split(key, 9)
    return {
        "ln1": normal(ks[0], (D,), NORM_STD, dtype),
        "wq": normal(ks[1], (D, Hq, Dh), D ** -0.5, dtype),
        "wk": normal(ks[2], (D, Hkv, Dh), D ** -0.5, dtype),
        "wv": normal(ks[3], (D, Hkv, Dh), D ** -0.5, dtype),
        "wo": normal(ks[4], (Hq, Dh, D), (Hq * Dh) ** -0.5, dtype),
        "ln2": normal(ks[5], (D,), NORM_STD, dtype),
        "w_gate": normal(ks[6], (D, F), D ** -0.5, dtype),
        "w_up": normal(ks[7], (D, F), D ** -0.5, dtype),
        "w_down": normal(ks[8], (F, D), F ** -0.5, dtype),
    }


def table_leaves(s: Shape, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Embedding, output head (untied only) and final norm.  Rows past the
    true vocabulary are zero: the program masks their logits."""
    D = s.d_model
    ks = jax.random.split(key, 3)
    live = jnp.arange(s.padded_vocab)[:, None] < s.vocab

    def table(k):
        t = normal(k, (s.padded_vocab, D), LOGIT_STD * D ** -0.5, dtype)
        return jnp.where(live, t, jnp.zeros((), dtype))

    out = {"embed": table(ks[0]),
           "final_norm": normal(ks[2], (D,), NORM_STD, dtype)}
    if not s.tied:
        out["unembed"] = table(ks[1])
    return out


def served_params(s: Shape, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf of the served model on the default device, from one
    jitted call: the layers stacked (``lax.map`` over their keys), in the
    served dtype."""

    def make(lkeys, tkey):
        blocks = jax.lax.map(lambda k: layer_leaves(s, k, dtype), lkeys)
        return {"blocks": blocks, **table_leaves(s, tkey, dtype)}

    lkeys = jnp.stack([weights.layer_key(seed, i) for i in range(s.n_layers)])
    return jax.jit(make)(lkeys, weights.table_key(seed))


def layer_params(s: Shape) -> int:
    D, Dh = s.d_model, s.head_dim
    return D * (s.n_heads + 2 * s.n_kv_heads) * Dh + s.n_heads * Dh * D + 3 * D * s.d_ff


def step_flops(s: Shape, decode_batch: int, kv_tokens: int,
               chunks: list[tuple[int, int, bool]]) -> float:
    """One dispatch: ``decode_batch`` lanes over ``kv_tokens`` live
    positions, plus prefill ``chunks`` of ``(pos, n, last)``."""
    tokens = decode_batch + sum(n for _, n, _ in chunks)
    heads = sum(1 for *_, last in chunks if last) + decode_batch
    attended = kv_tokens + sum(cost.causal_ctx(p, n) for p, n, _ in chunks)
    return (2.0 * s.n_layers * layer_params(s) * tokens
            + 2.0 * s.vocab * s.d_model * heads
            + 4.0 * s.n_layers * s.n_heads * s.head_dim * attended)


def paged_attn(s: Shape, decode_batch: int, kv_tokens: int) -> tuple[float, float]:
    """(flops, bytes) of decode attention over the live pool positions:
    each position's K and V read once, each lane's query read and output
    written once, in every layer."""
    L, Hq, Hkv, Dh = s.n_layers, s.n_heads, s.n_kv_heads, s.head_dim
    flops = 4.0 * L * Hq * Dh * kv_tokens
    bytes_ = L * cost.BF16 * (2 * Hkv * Dh * kv_tokens + 2 * Hq * Dh * decode_batch)
    return flops, bytes_


def flash_prefill(s: Shape, pos: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of causal attention for one chunk: queries and
    outputs of ``n`` tokens, K and V of the ``pos + n`` positions seen."""
    L, Hq, Hkv, Dh = s.n_layers, s.n_heads, s.n_kv_heads, s.head_dim
    flops = 4.0 * L * Hq * Dh * cost.causal_ctx(pos, n)
    bytes_ = L * cost.BF16 * (2 * Hq * Dh * n + 2 * Hkv * Dh * (pos + n))
    return flops, bytes_
