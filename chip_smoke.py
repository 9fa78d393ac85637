"""Chip bring-up smoke: serve llama3.2-1b at its published width on a TPU.

    python chip_smoke.py             # one chip: a bf16 and an int8 KV pool
    python chip_smoke.py --chips 4   # the bf16 run on a 4-chip mesh, checked
                                     # against the same requests on one chip

The server is built by ``repro.launch.serve.build_server`` -- the code the
serving CLI runs -- with the published llama3.2-1b config (random weights
from a seed), the paged KV pool, the hybrid chunked-prefill schedule and
the async dispatch-ahead pipeline; on a TPU the Pallas kernels are on
that path.  Every phase must pass:

* the paged-decode and flash kernels match their jnp oracles
  (``repro.kernels.ref``, float32 at highest matmul precision) at the
  served shapes within ``KERNEL_TOL``;
* every request finishes with all of its tokens, and the prefill logits
  are finite;
* the compiled decode program holds the kernel (``tpu_custom_call``);
* ``--chips 4``: the KV pool is split over the four chips; the served
  paged-decode entry point on that split (each lane attends its own
  blocks, lanes merge by log-sum-exp) matches its unsplit oracle within
  ``KERNEL_TOL``; prefill logits and the logits of one teacher-forced
  decode step agree with one chip's within ``LOGIT_TOL``; the share of
  identical greedy tokens is reported.

The script exits non-zero, printing no result line, where JAX finds no
TPU; there is no CPU fallback.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed before it are host wall-clock, compilation included.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "llama3.2-1b"
N_SLOTS = 16
MAX_SEQ = 2048
BLOCK_SIZE = 16
# 2048 blocks (2047 usable) hold every slot at max_seq and split evenly
# over 1, 2 or 4 lanes
N_BLOCKS = 2048
PREFILL_CHUNK = 256
N_REQUESTS = 32
PROMPT_LEN = (128, 1024)
MAX_NEW = 32
SEED = 0
# kernel vs float32 oracle: |out - ref| <= atol + rtol * |ref|, about five
# bf16 ulps at unit scale (kernel outputs are bf16)
KERNEL_TOL = dict(atol=2e-2, rtol=2e-2)
# four-chip vs one-chip logits, prefill and teacher-forced decode
# (float32 view of bf16 logits)
LOGIT_TOL = dict(atol=5e-2, rtol=5e-2)


def serve_argv(kv_dtype: str) -> list[str]:
    return [
        "--arch", ARCH, "--cache", "paged", "--block-size", str(BLOCK_SIZE),
        "--blocks", str(N_BLOCKS), "--schedule", "hybrid", "--async", "on",
        "--slots", str(N_SLOTS), "--max-seq", str(MAX_SEQ),
        "--prefill-chunk", str(PREFILL_CHUNK), "--kv-dtype", kv_dtype,
    ]


class CompileLog:
    """Counts backend compiles, their seconds and how many of them the
    persistent cache served (JAX monitoring events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self.COMPILE:
            self.n += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> tuple[int, int, float]:
        return self.n, self.hits, self.seconds


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}")


def prompts(vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lens]


# ------------------------------------------------------------------ kernels
def paged_inputs(cfg, n_pos: np.ndarray):
    """Seeded decode inputs at the served shapes: queries, bf16 K/V pools,
    and block tables covering ``n_pos`` positions per slot, each slot's
    blocks drawn at random from the whole pool (so on a split pool one
    table crosses lanes)."""
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()
    ks = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(ks[0], (N_SLOTS, Hq, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (N_BLOCKS, Hkv, BLOCK_SIZE, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (N_BLOCKS, Hkv, BLOCK_SIZE, D), jnp.bfloat16)
    tables = np.zeros((N_SLOTS, MAX_SEQ // BLOCK_SIZE), np.int32)
    free = iter(np.random.default_rng(SEED).permutation(np.arange(1, N_BLOCKS)))
    for b, n in enumerate(n_pos):
        for j in range(-(-int(n) // BLOCK_SIZE)):
            tables[b, j] = next(free)
    return q, kp, vp, tables


def slot_lengths(high: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded per-slot lengths in ``[1, high]`` and hot-window starts."""
    rng = np.random.default_rng(SEED)
    lengths = rng.integers(1, high + 1, size=N_SLOTS).astype(np.int32)
    starts = (lengths * rng.uniform(0, 0.5, size=N_SLOTS)).astype(np.int32)
    return lengths, starts


def close(name, got, want, tol=KERNEL_TOL) -> None:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    err = np.abs(got - want)
    bound = tol["atol"] + tol["rtol"] * np.abs(want)
    check(bool(np.all(np.isfinite(got)) and np.all(err <= bound)),
          f"{name} matches (max |err| {float(err.max()):.3e})")


def paged_cases(cfg, attend):
    """``attend(q, k_pool, v_pool, tables, lengths, **kw) -> (out, lse)``
    against the paged oracle, for a bf16 and an int8 pool."""
    from repro.kernels import ref

    lengths, starts = slot_lengths(MAX_SEQ)
    q, kp, vp, tables = paged_inputs(cfg, lengths)
    for kv_dtype in ("bf16", "int8"):
        k_pool, v_pool, k_sc, v_sc = kp, vp, None, None
        if kv_dtype != "bf16":
            k_pool, k_sc = ref.kv_quantize_pool(kp, kv_dtype)
            v_pool, v_sc = ref.kv_quantize_pool(vp, kv_dtype)
        args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths))
        kw = dict(starts=jnp.asarray(starts), k_scale=k_sc, v_scale=v_sc,
                  return_lse=True)
        out, lse = attend(*args, **kw)
        with jax.default_matmul_precision("highest"):
            ref_out, ref_lse = ref.paged_decode_attention(*args, **kw)
        close(f"paged decode ({kv_dtype} pool) vs its oracle", out, ref_out)
        close(f"paged decode LSE ({kv_dtype} pool) vs its oracle", lse, ref_lse)
    return tables


def kernel_phase(cfg) -> None:
    """The served kernels against their oracles at the served shapes."""
    from repro.kernels import ops, ref

    paged_cases(cfg, ops.paged_decode_attention)
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()
    ks = jax.random.split(jax.random.key(SEED), 6)
    q2 = jax.random.normal(ks[3], (1, PREFILL_CHUNK, Hq, D), jnp.bfloat16)
    k2 = jax.random.normal(ks[4], (1, MAX_SEQ, Hkv, D), jnp.bfloat16)
    v2 = jax.random.normal(ks[5], (1, MAX_SEQ, Hkv, D), jnp.bfloat16)
    off = jnp.int32(MAX_SEQ // 2)                       # traced q_offset
    out = ops.flash_attention(q2, k2, v2, causal=True, q_offset=off)
    with jax.default_matmul_precision("highest"):
        want = ref.naive_attention(q2.astype(jnp.float32), k2, v2,
                                   causal=True, q_offset=off)
    close("flash prefill kernel (bf16, traced q_offset) vs its oracle", out, want)
    kq, ksc = ref.kv_quantize(k2, "int8")
    vq, vsc = ref.kv_quantize(v2, "int8")
    out = ops.flash_attention(q2, kq, vq, causal=True, q_offset=off,
                              k_scale=ksc, v_scale=vsc)
    with jax.default_matmul_precision("highest"):
        want = ref.naive_attention(q2.astype(jnp.float32), kq, vq,
                                   causal=True, q_offset=off,
                                   k_scale=ksc, v_scale=vsc)
    close("flash prefill kernel (int8 K/V) vs its oracle", out, want)


# ------------------------------------------------------------------ serving
def prefill_logits(srv, prompt_list) -> np.ndarray:
    """Last-position logits of each prompt, prefilled chunk by chunk with
    the served ``prefill_step`` into a staging-shaped cache."""
    model, eng = srv.model, srv.engine
    step = jax.jit(model.prefill_step)
    rows = []
    for p in prompt_list:
        cache = eng.place(model.init_cache(2, MAX_SEQ),
                          model.cache_specs(2, MAX_SEQ))
        for off in range(0, len(p), PREFILL_CHUNK):
            chunk = p[off:off + PREFILL_CHUNK]
            toks = np.zeros((1, PREFILL_CHUNK), np.int32)
            toks[0, :len(chunk)] = chunk
            logits, cache = step(eng.params, cache, jnp.asarray(toks), 0,
                                 off, len(chunk))
        rows.append(np.asarray(logits[0].astype(jnp.float32)))
    return np.stack(rows)


def serve_phase(kv_dtype: str, devices, clog: CompileLog):
    """Serve the seeded requests; return (server, requests, logits)."""
    from repro.launch import serve
    from repro.serving.engine import Request

    args = serve.build_parser().parse_args(serve_argv(kv_dtype))
    t0 = time.perf_counter()
    srv = serve.build_server(args, devices=devices)
    jax.block_until_ready(srv.engine.params)
    print(f"  built: {srv.cfg.name} d_model={srv.cfg.d_model} "
          f"layers={srv.cfg.n_layers} vocab={srv.cfg.vocab} "
          f"params={srv.model.n_params() / 1e9:.3f}B on {len(devices)} "
          f"device(s), kv policy {srv.plan.kv_policy} "
          f"({time.perf_counter() - t0:.1f}s)")
    reqs = [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts(srv.cfg.vocab))]
    n0, h0, s0 = clog.mark()
    t0 = time.perf_counter()
    for r in reqs:
        srv.front.submit(r)
    srv.front.run()
    wall = time.perf_counter() - t0
    n1, h1, s1 = clog.mark()
    st = srv.engine.stats
    print(f"  served {len(reqs)} requests, {st.generated} tokens in "
          f"{st.engine_steps} engine steps; wall {wall:.1f}s, of which "
          f"{n1 - n0} compiles ({h1 - h0} from the cache) took {s1 - s0:.1f}s")
    check(all(r.done and len(r.out_tokens) == MAX_NEW for r in reqs),
          f"every request finished with {MAX_NEW} tokens ({kv_dtype} pool)")
    logits = prefill_logits(srv, [r.prompt for r in reqs[:4]])
    check(bool(np.all(np.isfinite(logits))),
          f"prefill logits finite, shape {logits.shape} ({kv_dtype} pool)")
    eng = srv.engine
    hlo = eng._decode_sampled.lower(
        eng.params, eng.cache, eng._tok_state, jax.random.key(0),
        eng._eos_dev, sampler=eng.sampler,
    ).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"compiled decode program holds the kernel ({kv_dtype} pool)")
    return srv, reqs, logits


def decode_logits(srv) -> np.ndarray:
    """Logits of one served paged decode step (bf16 pool), teacher-forced:
    seeded K/V in every layer's pool, tables and lengths as in the kernel
    check, one seeded token per slot -- the same inputs on any mesh."""
    eng, cfg = srv.engine, srv.cfg
    lengths, _ = slot_lengths(MAX_SEQ - 1)
    _, _, _, tables = paged_inputs(cfg, lengths + 1)    # room to append
    tokens = np.random.default_rng(SEED).integers(1, cfg.vocab, N_SLOTS)
    fill = {"block_tables": tables, "lengths": lengths}
    cache = {}
    for i, (name, leaf) in enumerate(sorted(eng.cache.items())):
        if name in ("k", "v"):
            val = jax.random.normal(jax.random.key(SEED + 1 + i), leaf.shape,
                                    leaf.dtype)
        else:
            val = fill[name]
        cache[name] = jax.device_put(val, leaf.sharding)
    logits, _ = jax.jit(srv.model.paged_decode_step)(
        eng.params, cache, jnp.asarray(tokens, jnp.int32))
    return np.asarray(logits.astype(jnp.float32))


def split_kernel_phase(srv) -> None:
    """The served paged entry point (``core/offload.py``) on ``srv``'s
    mesh, the pools split by blocks as the serve splits them, against the
    unsplit oracle."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import offload
    from repro.core.placement import PAGED_KV_CACHE_AXES

    env = srv.model.env
    held = []                              # blocks on each lane

    def attend(q, k_pool, v_pool, tables, lengths, *, k_scale, v_scale,
               starts, return_lse):
        spec = env.kv_spec(PAGED_KV_CACHE_AXES, k_pool.shape)
        lead = NamedSharding(env.mesh, P(*tuple(spec)[:2]))  # blocks, heads
        k_pool, v_pool = jax.device_put((k_pool, v_pool), lead)
        if k_scale is not None:
            k_scale, v_scale = jax.device_put((k_scale, v_scale), lead)
        check(not k_pool.sharding.is_fully_replicated,
              f"pool split {spec} over {len(k_pool.sharding.device_set)} chips")
        held.append(k_pool.sharding.shard_shape(k_pool.shape)[0])
        fn = jax.jit(lambda *a: offload.paged_decode_attention(
            env, *a[:5], starts=a[5], k_scale=a[6], v_scale=a[7],
            return_lse=return_lse))
        return fn(q, k_pool, v_pool, tables, lengths, starts, k_scale, v_scale)

    tables = paged_cases(srv.cfg, attend)
    crossing = sum(len({int(t) // held[0] for t in row if t}) > 1
                   for row in tables)
    check(crossing > 0, f"{crossing}/{N_SLOTS} block tables cross lanes")


def one_chip(clog: CompileLog) -> None:
    from repro.configs import get_config

    print("phase: kernels vs oracles at the served shapes")
    kernel_phase(get_config(ARCH))
    for kv_dtype in ("bf16", "int8"):
        print(f"phase: serve, {kv_dtype} pool, one chip")
        srv, _, _ = serve_phase(kv_dtype, jax.devices()[:1], clog)
        del srv


def four_chips(clog: CompileLog) -> None:
    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees {len(devices)}")
    print("phase: serve, bf16 pool, one chip (reference)")
    srv1, reqs1, log1 = serve_phase("bf16", devices[:1], clog)
    dec1 = decode_logits(srv1)
    del srv1
    print("phase: serve, bf16 pool, four chips")
    srv4, reqs4, log4 = serve_phase("bf16", devices[:4], clog)
    pool = srv4.engine.cache["k"].sharding
    check(len(pool.device_set) == 4 and not pool.is_fully_replicated,
          f"the served KV pool is split {pool.spec} across the four chips")
    print("phase: split paged decode vs its oracle, four chips")
    split_kernel_phase(srv4)
    print("phase: four chips vs one chip")
    close("prefill logits, four chips vs one", log4, log1, LOGIT_TOL)
    dec4 = decode_logits(srv4)
    close("teacher-forced decode logits, four chips vs one", dec4, dec1,
          LOGIT_TOL)
    top = int(np.sum(dec4.argmax(-1) == dec1.argmax(-1)))
    print(f"  teacher-forced decode argmax, four chips vs one: "
          f"{top}/{N_SLOTS} identical")
    same = sum(a == b for r1, r4 in zip(reqs1, reqs4)
               for a, b in zip(r1.out_tokens, r4.out_tokens))
    total = sum(len(r.out_tokens) for r in reqs1)
    print(f"  identical greedy tokens, four chips vs one: {same}/{total} "
          f"({same / total:.3f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache "
          f"{jax.config.jax_compilation_cache_dir}")
    clog = CompileLog()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(clog)
    print(f"total: wall {time.perf_counter() - t0:.1f}s, {clog.n} compiles "
          f"({clog.hits} from the cache) took {clog.seconds:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
