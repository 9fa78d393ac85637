"""Kernel micro-benchmarks (interpret mode on CPU -> correctness-scale
timings; TPU numbers come from the dry-run roofline, not wall clock).

``main`` returns the decode-attention row as a metrics dict — the
kernel/oracle wall-clock *ratio* (a ratio of two runs on the same
machine transfers across CI runners where absolute µs would not) and
the max abs err vs the oracle; ``benchmarks/ci_gate.py`` gates both
with wide variance bands, so only a multiple-x blowup (the "compile
path broke" regime) trips CI.  ``--smoke`` cuts the timing repetitions
for the CI run.
"""
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(fn, *args, n=5):
    # one warmup; block_until_ready handles arrays and pytrees uniformly
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def main(print_fn=print, smoke: bool = False) -> dict:
    reps = 2 if smoke else 5
    print_fn("# kernel micro-bench (CPU interpret mode): us_per_call vs jnp oracle")
    print_fn("name,us_per_call,oracle_us,max_abs_err")
    key = jax.random.key(0)
    B, S, Hkv, G, D = 2, 256, 2, 4, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)

    t_kern = _time(lambda: ops.decode_attention(q, kc, vc, lengths, block_s=64),
                   n=reps)
    t_ref = _time(lambda: ref.naive_decode_attention(q, kc, vc, lengths), n=reps)
    err = float(
        jnp.max(jnp.abs(ops.decode_attention(q, kc, vc, lengths, block_s=64)
                        - ref.naive_decode_attention(q, kc, vc, lengths)))
    )
    print_fn(f"decode_attention_b{B}s{S}g{G},{t_kern:.0f},{t_ref:.0f},{err:.2e}")
    metrics = {"kernel_decode_vs_oracle": t_kern / max(t_ref, 1e-9),
               "kernel_decode_err": err}

    Sq = 128
    q2 = jax.random.normal(ks[0], (B, Sq, Hkv * G, D), jnp.float32)
    k2 = jax.random.normal(ks[1], (B, Sq, Hkv, D), jnp.float32)
    v2 = jax.random.normal(ks[2], (B, Sq, Hkv, D), jnp.float32)
    t_kern = _time(lambda: ops.flash_attention(q2, k2, v2, block_q=64, block_k=64),
                   n=reps)
    t_ref = _time(lambda: ref.naive_attention(q2, k2, v2), n=reps)
    err = float(jnp.max(jnp.abs(
        ops.flash_attention(q2, k2, v2, block_q=64, block_k=64)
        - ref.naive_attention(q2, k2, v2))))
    print_fn(f"flash_attention_b{B}s{Sq}g{G},{t_kern:.0f},{t_ref:.0f},{err:.2e}")

    # paged decode: same shapes as the dense decode row, KV scattered
    # across a block pool and gathered through per-sequence block tables
    bs = 64
    MB = S // bs
    N = 1 + B * MB
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
    tables = jnp.arange(1, N, dtype=jnp.int32).reshape(B, MB)
    t_kern = _time(lambda: ops.paged_decode_attention(q, kp, vp, tables, lengths),
                   n=reps)
    t_ref = _time(lambda: ref.paged_decode_attention(q, kp, vp, tables, lengths),
                  n=reps)
    err = float(
        jnp.max(jnp.abs(ops.paged_decode_attention(q, kp, vp, tables, lengths)
                        - ref.paged_decode_attention(q, kp, vp, tables, lengths)))
    )
    print_fn(f"paged_decode_attention_b{B}s{S}g{G}bs{bs},{t_kern:.0f},{t_ref:.0f},{err:.2e}")

    # quantized paged decode: fp8 payload + per-vector scales, dequant
    # inside the kernel.  The accuracy column is vs the *full-precision*
    # oracle — the end-to-end error the fp8 KV tier actually adds — and
    # kernel correctness itself is the tiny gap vs the dequantized oracle.
    kq, k_scale = ref.kv_quantize_pool(kp, "fp8")
    vq, v_scale = ref.kv_quantize_pool(vp, "fp8")
    t_kern = _time(lambda: ops.paged_decode_attention(
        q, kq, vq, tables, lengths, k_scale=k_scale, v_scale=v_scale), n=reps)
    full = ref.paged_decode_attention(q, kp, vp, tables, lengths)
    out_q = ops.paged_decode_attention(q, kq, vq, tables, lengths,
                                       k_scale=k_scale, v_scale=v_scale)
    exp_q = ref.paged_decode_attention(q, kq, vq, tables, lengths,
                                       k_scale=k_scale, v_scale=v_scale)
    q_err = float(jnp.max(jnp.abs(out_q - full)))       # quantization error
    k_err = float(jnp.max(jnp.abs(out_q - exp_q)))      # kernel-vs-oracle
    print_fn(f"paged_decode_fp8_b{B}s{S}g{G}bs{bs},{t_kern:.0f},{t_ref:.0f},{q_err:.2e}")
    metrics["kernel_decode_fp8_quant_err"] = q_err
    metrics["kernel_decode_fp8_err"] = k_err
    return metrics


def _bench_wrap(fn):
    return fn


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
