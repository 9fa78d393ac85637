"""The arithmetic every family's work counts share: causal context, the
element size, and the least time a count needs on the chip.  The counts
themselves (a dispatch's FLOPs, each kernel's FLOPs and bytes) depend on
the block and live in its family module (``bench/families/``).
"""
from __future__ import annotations

BF16 = 2


def causal_ctx(pos: int, n: int) -> int:
    """Positions a causal chunk of ``n`` queries at offset ``pos`` attends."""
    return n * pos + n * (n + 1) // 2


def least_s(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
