"""Operations and bytes that the served work needs, from shapes and live
lengths alone (adapted from the program's
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

from spec import Shape

BF16 = 2


def causal_ctx(pos: int, n: int) -> int:
    """Positions a causal chunk of ``n`` queries at offset ``pos`` attends."""
    return n * pos + n * (n + 1) // 2


def layer_params(s: Shape) -> int:
    D, Dh = s.d_model, s.head_dim
    return D * (s.n_heads + 2 * s.n_kv_heads) * Dh + s.n_heads * Dh * D + 3 * D * s.d_ff


def step_flops(s: Shape, decode_batch: int, kv_tokens: int,
               chunks: list[tuple[int, int, bool]]) -> float:
    """One dispatch: ``decode_batch`` lanes over ``kv_tokens`` live
    positions, plus prefill ``chunks`` of ``(pos, n, last)``."""
    tokens = decode_batch + sum(n for _, n, _ in chunks)
    heads = sum(1 for *_, last in chunks if last) + decode_batch
    attended = kv_tokens + sum(causal_ctx(p, n) for p, n, _ in chunks)
    return (2.0 * s.n_layers * layer_params(s) * tokens
            + 2.0 * s.vocab * s.d_model * heads
            + 4.0 * s.n_layers * s.n_heads * s.head_dim * attended)


def paged_attn(s: Shape, decode_batch: int, kv_tokens: int) -> tuple[float, float]:
    """(flops, bytes) of decode attention over the live pool positions:
    each position's K and V read once, each lane's query read and output
    written once, in every layer."""
    L, Hq, Hkv, Dh = s.n_layers, s.n_heads, s.n_kv_heads, s.head_dim
    flops = 4.0 * L * Hq * Dh * kv_tokens
    bytes_ = L * BF16 * (2 * Hkv * Dh * kv_tokens + 2 * Hq * Dh * decode_batch)
    return flops, bytes_


def flash_prefill(s: Shape, pos: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of causal attention for one chunk: queries and
    outputs of ``n`` tokens, K and V of the ``pos + n`` positions seen."""
    L, Hq, Hkv, Dh = s.n_layers, s.n_heads, s.n_kv_heads, s.head_dim
    flops = 4.0 * L * Hq * Dh * causal_ctx(pos, n)
    bytes_ = L * BF16 * (2 * Hq * Dh * n + 2 * Hkv * Dh * (pos + n))
    return flops, bytes_


def least_s(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
