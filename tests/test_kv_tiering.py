"""Tiered KV memory: quantized block pools and the host-offloaded cold
tier must not change what the engine serves.

Three layers of guarantee, mirroring how the tiers compose:

* block quantization round-trips within the format's step size, and the
  quantized paged kernel matches the dequantize-then-attend oracle;
* hybrid attention over a hot/cold split — device kernel over the hot
  window, oracle over the cold prefix, combined by log-sum-exp — is
  exactly full attention over the whole sequence;
* end-to-end, a host-tier run that spilled live blocks decodes the same
  greedy tokens as an unspilled run, with zero preemptions, and
  quantized pools stay greedy-faithful across every schedule combo.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduced import reduce_config
from repro.core.placement import Env
from repro.kernels import ops, ref
from repro.models.registry import build_model
from repro.serving.engine import Engine, Request
from repro.serving.paged import device as paged_dev
from repro.serving.paged.block_pool import BlockPool, chain_key
from repro.serving.paged.manager import PagedCacheManager


# ------------------------------------------------------------ quantization
@pytest.mark.parametrize("kv_dtype,tol", [("fp8", 0.07), ("int8", 0.005)])
def test_kv_quantize_roundtrip_bounded(kv_dtype, tol):
    """Dequantized blocks sit within the format's per-vector step size of
    the original (absmax scaling: error scales with the vector's amax)."""
    x = jax.random.normal(jax.random.key(0), (4, 8, 16, 64), jnp.float32) * 3
    payload, scale = ref.kv_quantize(x, kv_dtype)
    back = ref.kv_dequantize(payload, scale, jnp.float32)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert np.all(err <= tol * amax + 1e-7)


@pytest.mark.parametrize("kv_dtype", ["fp8", "int8"])
def test_kv_quantize_zero_vector_is_exact(kv_dtype):
    payload, scale = ref.kv_quantize(jnp.zeros((2, 4, 8)), kv_dtype)
    assert np.all(np.asarray(scale) == 0.0)
    back = ref.kv_dequantize(payload, scale, jnp.float32)
    assert np.all(np.asarray(back) == 0.0)


@pytest.mark.parametrize("kv_dtype", ["fp8", "int8"])
def test_kv_quantize_roundtrip_property(kv_dtype):
    """Property test over adversarial vectors (huge dynamic range, exact
    zeros, single-element spikes) — hypothesis-gated."""
    hyp = pytest.importorskip("hypothesis")
    hnp = pytest.importorskip("hypothesis.extra.numpy")
    st = hyp.strategies

    @hyp.given(
        hnp.arrays(
            np.float32, (3, 8),
            elements=st.floats(-1e4, 1e4, width=32, allow_nan=False),
        )
    )
    @hyp.settings(max_examples=200, deadline=None)
    def run(x):
        payload, scale = ref.kv_quantize(jnp.asarray(x), kv_dtype)
        back = np.asarray(ref.kv_dequantize(payload, scale, jnp.float32))
        amax = np.max(np.abs(x), axis=-1, keepdims=True)
        tol = 0.07 if kv_dtype == "fp8" else 0.005
        assert np.all(np.abs(back - x) <= tol * amax + 1e-7)

    run()


@pytest.mark.parametrize("kv_dtype", ["fp8", "int8"])
def test_paged_kernel_quantized_matches_oracle(kv_dtype):
    """The in-kernel dequantize path == gather + dequantize + dense
    oracle, and both sit close to the unquantized attention."""
    B, Hkv, G, D, bs, MB = 3, 2, 4, 16, 8, 4
    N = 1 + B * MB
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
    rng = np.random.default_rng(0)
    perm = iter(rng.permutation(np.arange(1, N)))
    lens = (5, 17, 32)
    tables = np.zeros((B, MB), np.int32)
    for b in range(B):
        for j in range(-(-int(lens[b]) // bs)):
            tables[b, j] = next(perm)
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(lens, jnp.int32)

    kq, k_scale = ref.kv_quantize_pool(kp, kv_dtype)
    vq, v_scale = ref.kv_quantize_pool(vp, kv_dtype)
    out = ops.paged_decode_attention(q, kq, vq, tables, lengths,
                                     k_scale=k_scale, v_scale=v_scale)
    exp = ref.paged_decode_attention(q, kq, vq, tables, lengths,
                                     k_scale=k_scale, v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-2, rtol=2e-2)
    full = ref.paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=0.15, rtol=0.15)


# --------------------------------------------------------------- LSE merge
def test_lse_merge_matches_full_attention_oracle():
    """Hot-window attention + cold-prefix attention, LSE-merged, must
    equal one full-sequence attention — including empty cold windows."""
    B, Hkv, G, D, S = 4, 2, 3, 16, 24
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    lengths = jnp.asarray([5, 24, 17, 9], jnp.int32)
    starts = jnp.asarray([0, 8, 16, 8], jnp.int32)   # 0 = nothing cold

    hot = ref.naive_decode_attention(q, k, v, lengths, starts=starts,
                                     return_lse=True)
    cold = ref.naive_decode_attention(q, k, v, starts, return_lse=True)
    merged = ref.lse_merge([hot, cold])
    full = ref.naive_decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               atol=1e-5, rtol=1e-5)


def test_lse_merge_kernel_hot_window_matches_oracle():
    """The Pallas kernel's (out, lse) over a ``starts``-restricted hot
    window merges with a cold-prefix oracle part into full attention."""
    B, Hkv, G, D, bs, MB = 2, 2, 4, 16, 8, 4
    N = 1 + B * MB
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([29, 32], jnp.int32)
    starts = jnp.asarray([8, 16], jnp.int32)         # cold: 1 resp. 2 blocks

    hot = ops.paged_decode_attention(q, kp, vp, tables, lengths,
                                     starts=starts, return_lse=True)
    cold = ref.paged_decode_attention(q, kp, vp, tables, starts,
                                      return_lse=True)
    merged = ref.lse_merge([hot, cold])
    full = ref.paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               atol=2e-4, rtol=2e-4)


# ----------------------------------------------------- pool/manager host tier
def test_pool_free_time_spill_and_host_lru():
    """A registered block's last decref spills it to the host tier; the
    host tier evicts its LRU unreferenced block when full."""
    pool = BlockPool(n_blocks=8, block_size=4, host_blocks=2)
    keys = []
    for i in range(3):
        b = pool.alloc()
        k = chain_key(keys[-1] if keys else None, (i, i, i, i))
        pool.register(k, b)
        keys.append(k)
        pool.decref(b)                       # -> free-time spill
    # 3 spills through a 2-block host tier: one LRU eviction
    assert pool.stats.spills == 3
    assert pool.stats.host_evictions == 1
    assert pool.host_in_use == 2
    assert pool.host_peek(keys[0]) is None   # the evicted one
    assert pool.host_peek(keys[2]) is not None
    directives = pool.drain_directives()
    assert [d[0] for d in directives] == ["spill"] * 3


def test_manager_rehydrates_host_prefix_on_admission():
    """Host-tier prefix hits admit as cached (no recompute) by copying
    the block back into a fresh device block."""
    pool = BlockPool(n_blocks=8, block_size=4, host_blocks=4)
    mgr = PagedCacheManager(pool, n_slots=2, max_blocks=4)
    toks = np.arange(100, 110, dtype=np.int32)      # 3 blocks (1 partial)
    ids = mgr.try_admit(0, toks)
    assert ids is not None
    mgr.free_slot(0)                                 # registered blocks spill
    assert pool.stats.spills == 3 and pool.in_use == 0
    pool.drain_directives()

    assert mgr.probe_prefix(toks) == 10              # host hits count
    ids2, n_cached = mgr.try_admit(1, toks)
    assert n_cached == 3                             # all three blocks cached
    assert pool.stats.rehydrates == 3
    rehydrates = [d for d in pool.drain_directives() if d[0] == "rehydrate"]
    assert len(rehydrates) == 3


def test_manager_live_spill_bookkeeping():
    """spill_live_prefix moves the oldest resident block of a live slot
    to the host tier, zeroes its device table entry, and refuses to
    touch the block holding the current append position."""
    pool = BlockPool(n_blocks=4, block_size=4, host_blocks=4)   # 3 usable
    mgr = PagedCacheManager(pool, n_slots=1, max_blocks=3)
    toks = np.arange(200, 210, dtype=np.int32)      # 10 toks = 3 blocks
    assert mgr.try_admit(0, toks) is not None
    assert pool.free_count == 0

    assert mgr.spill_live_prefix(0, 10)
    assert mgr.cold_len(0) == 4 and pool.free_count == 1
    assert mgr.tables[0, 0] == 0 and mgr.host_tables[0, 0] != 0
    assert mgr.spill_live_prefix(0, 10)
    assert mgr.cold_len(0) == 8
    # the last block holds position 10: never spilled out from under it
    assert not mgr.spill_live_prefix(0, 10)
    assert pool.stats.spills == 2
    mgr.free_slot(0)
    assert pool.in_use == 0 and pool.host_in_use <= 4


def test_spill_rehydrate_device_roundtrip_exact():
    """spill_block -> rehydrate_block is bit-exact (payloads move in
    storage dtype, host tier included)."""
    L, N, Hkv, bs, D, HN = 2, 4, 2, 8, 16, 3
    ks = jax.random.split(jax.random.key(5), 2)
    cache = {
        "k": jax.random.normal(ks[0], (L, N, Hkv, bs, D), jnp.bfloat16),
        "v": jax.random.normal(ks[1], (L, N, Hkv, bs, D), jnp.bfloat16),
        "host_k": jnp.zeros((L, HN, Hkv, bs, D), jnp.bfloat16),
        "host_v": jnp.zeros((L, HN, Hkv, bs, D), jnp.bfloat16),
    }
    want_k = np.asarray(cache["k"][:, 2].astype(jnp.float32))
    cache = paged_dev.spill_block(cache, dev=2, host=1)
    # clobber the device copy, then bring it back
    cache["k"] = cache["k"].at[:, 2].set(0)
    cache["v"] = cache["v"].at[:, 2].set(0)
    cache = paged_dev.rehydrate_block(cache, host=1, dev=2)
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, 2].astype(jnp.float32)), want_k
    )


# ------------------------------------------------------------- end to end
def _setup():
    cfg = reduce_config("llama3.2-1b")
    model = build_model(cfg, Env())
    params = model.init(jax.random.key(0))
    return model, params


def _serve(model, params, prompts, n_new, **kw):
    eng = Engine(model, params, n_slots=2, max_seq=32, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    return reqs, stats, eng


SCHEDULES = [("decode-only", False), ("decode-only", True),
             ("hybrid", False), ("hybrid", True)]


@pytest.mark.parametrize("kv_dtype", ["fp8", "int8"])
def test_quantized_engine_greedy_equivalence(kv_dtype):
    """Quantized pools across every schedule combo: everything finishes,
    pools drain, and greedy outputs track the bf16 run within tolerance
    (first token exact — prefill runs on the bf16 staging cache — and a
    clear majority of all tokens identical)."""
    model, params = _setup()
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(7, 10, dtype=np.int32),
               np.arange(2, 13, dtype=np.int32)]
    base, _, _ = _serve(model, params, prompts, 5,
                        cache_kind="paged", block_size=8)
    for sched, amode in SCHEDULES:
        q, _, eng = _serve(model, params, prompts, 5,
                           cache_kind="paged", block_size=8,
                           kv_dtype=kv_dtype, schedule=sched,
                           async_mode=amode)
        assert all(r.done for r in q)
        assert eng.pool.in_use == 0
        total = match = 0
        for a, b in zip(base, q):
            assert b.out_tokens[0] == a.out_tokens[0], (sched, amode, b.uid)
            total += len(a.out_tokens)
            match += sum(x == y for x, y in zip(a.out_tokens, b.out_tokens))
        assert match / total >= 0.6, (sched, amode, match, total)


def test_host_tier_spills_instead_of_preempting():
    """Under block pressure a host tier absorbs the pressure: the run
    spills live prefix blocks, never preempts, and decodes exactly the
    unspilled run's greedy tokens (hybrid attention is LSE-exact)."""
    model, params = _setup()
    prompts = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 8, dtype=np.int32)]
    ref_reqs, _, _ = _serve(model, params, prompts, 10,
                            cache_kind="paged", block_size=4)
    for sched, amode in SCHEDULES:
        sp, ss, se = _serve(model, params, prompts, 10,
                            cache_kind="paged", block_size=4, n_blocks=9,
                            host_blocks=8, schedule=sched, async_mode=amode)
        assert ss.spills >= 1, (sched, amode)
        assert ss.preemptions == 0, (sched, amode)
        for a, b in zip(ref_reqs, sp):
            assert a.out_tokens == b.out_tokens, (sched, amode, b.uid)
        assert se.pool.in_use == 0


def test_host_tier_rehydrates_freed_prefix():
    """A finished request's prefix blocks spill at free time; a later
    identical prompt admits them as cached straight from the host tier
    and reproduces the same greedy continuation."""
    model, params = _setup()
    prompt = np.arange(1, 10, dtype=np.int32)        # 2 full blocks of 4
    eng = Engine(model, params, n_slots=1, max_seq=32,
                 cache_kind="paged", block_size=4, host_blocks=8)
    a = Request(uid=0, prompt=prompt, max_new_tokens=5)
    eng.submit(a)
    eng.run()
    assert eng.pool.stats.spills >= 2                # prefix went cold->host
    b = Request(uid=1, prompt=prompt, max_new_tokens=5)
    eng.submit(b)
    eng.run()
    assert eng.stats.rehydrations >= 2               # came back from host
    assert b.out_tokens == a.out_tokens
