"""Paged KV subsystem: BlockPool invariants, prefix sharing + COW, the
paged Pallas kernel vs its jnp oracle, and paged-vs-dense engine
equivalence (greedy, mixed prompt lengths, preemption)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduced import reduce_config
from repro.core.placement import Env
from repro.kernels import ops, ref
from repro.kernels import paged_decode_attention as pdk
from repro.models.registry import build_model
from repro.serving.engine import Engine, Request
from repro.serving.paged import BlockPool, PagedCacheManager


# ---------------------------------------------------------------- BlockPool
def test_pool_alloc_free_refcount():
    pool = BlockPool(n_blocks=5, block_size=8)   # 4 usable, id 0 reserved
    assert pool.free_count == 4 and pool.in_use == 0
    a, b = pool.alloc(), pool.alloc()
    assert 0 not in (a, b) and a != b
    assert pool.refcount(a) == 1
    pool.incref(a)
    assert pool.refcount(a) == 2
    pool.decref(a)
    assert pool.refcount(a) == 1 and pool.free_count == 2
    pool.decref(a)
    assert pool.refcount(a) == 0 and pool.free_count == 3
    pool.decref(b)
    assert pool.free_count == 4 and pool.in_use == 0
    assert pool.stats.allocs == 2 and pool.stats.frees == 2


def test_pool_exhaustion_raises():
    pool = BlockPool(n_blocks=2, block_size=4)
    pool.alloc()
    with pytest.raises(RuntimeError):
        pool.alloc()


def test_pool_hash_register_lookup_invalidate():
    pool = BlockPool(n_blocks=4, block_size=4)
    b = pool.alloc()
    pool.register(("k",), b)
    assert pool.lookup(("k",)) == b
    assert pool.stats.hash_hits == 1
    pool.invalidate(b)
    assert pool.lookup(("k",)) is None
    # freeing also drops the hash entry
    pool.register(("k2",), b)
    pool.decref(b)
    assert pool.lookup(("k2",)) is None


# ---------------------------------------------------------------- manager
def test_manager_prefix_sharing_and_cow():
    pool = BlockPool(n_blocks=8, block_size=4)
    mgr = PagedCacheManager(pool, n_slots=2, max_blocks=4)
    prompt = np.arange(1, 7, dtype=np.int32)      # 6 tokens: 1 full + partial

    ids0, cached0 = mgr.try_admit(0, prompt)
    assert cached0 == 0 and len(ids0) == 2
    ids1, cached1 = mgr.try_admit(1, prompt)
    assert cached1 == 2 and ids1 == ids0          # full prefix shared
    assert pool.stats.allocs == 2                 # not 4: sharing worked
    assert pool.refcount(ids0[1]) == 2

    # first divergent append on the shared tail -> COW for the appender
    d0, payload = mgr.ensure_append(0, 6)
    assert d0 == "cow" and payload[0] == ids0[1]
    assert mgr.blocks[0][1] == payload[1] != ids0[1]
    assert pool.refcount(ids0[1]) == 1
    # the other owner now appends in place
    d1, _ = mgr.ensure_append(1, 6)
    assert d1 == "ready"


def test_manager_boundary_alloc_and_oom():
    pool = BlockPool(n_blocks=3, block_size=4)    # 2 usable
    mgr = PagedCacheManager(pool, n_slots=1, max_blocks=4)
    # exact-multiple prompt: the decode boundary block is reserved at
    # admission (returned ids cover the prompt block only)
    ids, _ = mgr.try_admit(0, np.arange(4, dtype=np.int32))
    assert len(ids) == 1 and len(mgr.blocks[0]) == 2
    assert pool.free_count == 0
    assert mgr.ensure_append(0, 4) == ("ready", None)   # reserved block
    assert mgr.ensure_append(0, 8) == ("oom", None)     # pool dry
    mgr.free_slot(0)
    assert pool.in_use == 0 and not mgr.blocks[0]


def test_manager_admit_insufficient_blocks_is_sideeffect_free():
    pool = BlockPool(n_blocks=3, block_size=4)
    mgr = PagedCacheManager(pool, n_slots=2, max_blocks=4)
    assert mgr.try_admit(0, np.arange(12, dtype=np.int32)) is None
    assert pool.free_count == 2 and pool.stats.allocs == 0


# ------------------------------------------------------------ paged kernel
PAGED_CASES = [
    # (B, Hkv, G, D, block_size, max_blocks, lengths)
    (1, 1, 1, 8, 8, 2, (5,)),
    (3, 2, 4, 16, 8, 4, (5, 17, 32)),
    (2, 2, 8, 32, 16, 3, (1, 48)),      # HPU design point G=8
    (2, 1, 3, 16, 8, 4, (9, 25)),       # non-pow2 group
    # page groups of 32 pages: three with a partial last, one position
    # into a second, exactly one
    (3, 2, 4, 16, 16, 70, (1100, 513, 512)),
    # the decode-long cell's shape (MiniCPM-2B: MHA, 36 heads of 64, 97
    # entries, 486 blocks): seven groups, an empty slot beside a full one
    (5, 36, 1, 64, 16, 97, (0, 1552, 700, 1000, 17)),
    (2, 8, 7, 128, 16, 40, (600, 37)),  # Yi-34B's group 7 at D 128
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_kernel_matches_oracle(case, dtype):
    B, Hkv, G, D, bs, MB, lens = case
    N = 1 + B * MB
    ks = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32).astype(dtype)
    # scrambled physical placement, null block 0 for unused entries
    rng = np.random.default_rng(0)
    perm = iter(rng.permutation(np.arange(1, N)))
    tables = np.zeros((B, MB), np.int32)
    for b in range(B):
        for j in range(-(-int(lens[b]) // bs)):
            tables[b, j] = next(perm)
    lengths = jnp.asarray(lens, jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, jnp.asarray(tables), lengths)
    exp = ref.paged_decode_attention(q, kp, vp, jnp.asarray(tables), lengths)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), exp.astype(jnp.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn])
@pytest.mark.parametrize("hkv,head_dim", [
    (36, 64),    # MiniCPM-2B: MHA, group 1
    (8, 64),     # llama3.2-1b: group 4
    (8, 128),    # Yi-34B: group 7
])
def test_page_group_fits_vmem_budget(hkv, head_dim, kv_dtype):
    """The pages a group gathers come from the shapes: the two buffer
    slots the kernel allocates (K and V rows padded to 128 lanes, and the
    f32 scale rows of a quantized pool) fit the VMEM budget, a group
    spans at least 128 positions of 16-token blocks, and the heads
    computed together fit theirs."""
    bs, max_blocks = 16, 97
    itemsize = np.dtype(kv_dtype).itemsize
    quantized = itemsize == 1
    pages = pdk.pages_per_group(hkv, bs, head_dim, itemsize, quantized,
                                max_blocks)
    lanes = -(-head_dim // 128) * 128
    buffers = 2 * 2 * hkv * pages * bs * lanes * itemsize
    if quantized:
        buffers += 2 * 2 * hkv * pages * 128 * 4
    assert buffers <= pdk.VMEM_BUDGET
    assert 128 <= pages * bs <= pdk.MAX_GROUP_POSITIONS
    assert pdk.pages_per_group(hkv, bs, head_dim, itemsize, quantized, 3) == 3
    # the heads computed together divide the heads, and their widened
    # float32 operand fits its own budget
    hb = pdk.heads_per_block(hkv, pages * bs, head_dim)
    assert hkv % hb == 0
    assert hb * pages * bs * lanes * 4 <= pdk.HEAD_BLOCK_BYTES


def test_paged_kernel_ignores_null_block_garbage():
    B, Hkv, G, D, bs, MB = 2, 2, 2, 16, 8, 2
    N = 1 + B * MB
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
    tables = jnp.asarray([[1, 0], [2, 0]], jnp.int32)
    lengths = jnp.asarray([6, 8], jnp.int32)
    out1 = ops.paged_decode_attention(q, kp, vp, tables, lengths)
    kp2 = kp.at[0].set(99.0)
    vp2 = vp.at[0].set(-99.0)
    out2 = ops.paged_decode_attention(q, kp2, vp2, tables, lengths)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ------------------------------------------------------------------- specs
def test_paged_cache_specs_resolve_for_every_policy():
    """The pool's block axis must land on HPU-lane mesh axes (and the
    specs must match the kernel-native leaf shapes) under every KV
    placement policy."""
    cfg = reduce_config("llama3.2-1b")
    axes = {"pod": 1, "data": 2, "model": 2}
    for policy in ("batch", "head", "sequence", "batch_seq", "none"):
        model = build_model(cfg, Env(axes=axes, kv_policy=policy))
        n_slots, n_blocks, bs, mb = 4, 32, 8, 4
        specs = model.paged_cache_specs(n_slots, n_blocks, bs, mb)
        shapes = model.paged_cache_shapes(n_slots, n_blocks, bs, mb)
        assert set(specs) == set(shapes) == {"k", "v", "block_tables", "lengths"}
        for name in ("k", "v"):
            assert len(specs[name]) <= shapes[name].ndim
            if policy == "batch":    # blocks split across HPU lanes
                assert "data" in jax.tree.leaves(tuple(specs[name]))
            if policy == "none":
                assert specs[name] == jax.sharding.PartitionSpec()


# ------------------------------------------------------------------ engine
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


def test_paged_engine_matches_dense_engine():
    model, params = _setup()
    prompts = [np.arange(1, 6, dtype=np.int32),      # mixed lengths
               np.arange(7, 10, dtype=np.int32),
               np.arange(2, 13, dtype=np.int32)]
    dense, ds, _ = _serve(model, params, prompts, 5, cache_kind="dense")
    paged, ps, eng = _serve(model, params, prompts, 5,
                            cache_kind="paged", block_size=8)
    for a, b in zip(dense, paged):
        assert b.done
        assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)
    assert ps.peak_active == 2                       # continuous batching ran
    assert eng.pool.in_use == 0                      # all blocks returned


def test_paged_engine_prefix_sharing_saves_blocks():
    model, params = _setup()
    prompt = np.arange(1, 13, dtype=np.int32)        # 12 tokens = 2 blocks of 8
    paged, _, eng = _serve(model, params, [prompt, prompt], 4,
                           cache_kind="paged", block_size=8)
    assert paged[0].out_tokens == paged[1].out_tokens
    # no-sharing would allocate 2 prompt blocks per request (4 total);
    # sharing allocates 2 + one COW copy on first divergent append
    assert eng.pool.stats.allocs < 4
    assert eng.pool.stats.hash_hits >= 2
    assert eng.pool.stats.cow_copies >= 1
    dense, _, _ = _serve(model, params, [prompt, prompt], 4, cache_kind="dense")
    assert dense[0].out_tokens == paged[0].out_tokens


def test_paged_engine_preemption_restores_exact_tokens():
    model, params = _setup()
    prompts = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 8, dtype=np.int32)]
    dense, _, _ = _serve(model, params, prompts, 10, cache_kind="dense")
    # 8 usable blocks of 4 tokens: both sequences cannot finish resident
    paged, ps, eng = _serve(model, params, prompts, 10,
                            cache_kind="paged", block_size=4, n_blocks=9)
    assert ps.preemptions >= 1
    for a, b in zip(dense, paged):
        assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)
    assert eng.pool.in_use == 0


def test_paged_engine_admission_gated_on_blocks():
    model, params = _setup()
    # pool holds one max-length sequence; second request must wait even
    # though a slot is free
    prompts = [np.arange(1, 9, dtype=np.int32), np.arange(11, 19, dtype=np.int32)]
    paged, ps, eng = _serve(model, params, prompts, 4,
                            cache_kind="paged", block_size=4, n_blocks=9)
    dense, _, _ = _serve(model, params, prompts, 4, cache_kind="dense")
    for a, b in zip(dense, paged):
        assert b.done and a.out_tokens == b.out_tokens


@pytest.mark.parametrize("table", ["block_tables", "host_tables"])
def test_table_push_sends_the_row_as_it_was(table):
    """A queued table push carries the host row of its call: the manager
    rewrites rows in place (a freed slot's zeros, then its next request's
    blocks) before the device runs the push.  The push here waits on a
    slow program, and the row sits in a 64-byte-aligned buffer that the
    CPU device could otherwise alias."""
    from repro.serving.paged import device as paged_dev

    @jax.jit
    def slow_zeros(a):
        a = jax.lax.fori_loop(0, 30, lambda i, a: jnp.tanh(a @ a), a)
        return jnp.zeros((4, 16), jnp.int32) + (a[0, 0] * 0).astype(jnp.int32)

    def push(row):
        slow = jnp.full((384, 384), 1e-3, jnp.float32)
        cache = {"block_tables": slow_zeros(slow),
                 "host_tables": slow_zeros(slow),
                 "cold_lengths": jnp.zeros((4,), jnp.int32)}
        if table == "block_tables":
            return paged_dev.sync_slot(cache, 1, row)
        return paged_dev.sync_host_slot(cache, 1, row, 0)

    raw = np.zeros(4 * 16 * 4 + 64, np.uint8)
    off = -raw.ctypes.data % 64
    rows = raw[off:off + 4 * 16 * 4].view(np.int32).reshape(4, 16)
    jax.block_until_ready(push(rows[1]))        # compiled before the race
    rows[1] = 7
    out = push(rows[1])
    rows[1] = 9                     # the slot's next request, in place
    assert np.asarray(out[table])[1].tolist() == [7] * 16
