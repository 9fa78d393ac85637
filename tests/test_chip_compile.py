"""Compile the served Pallas kernels for a described TPU v5e, at
llama3.2-1b widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, too much VMEM.  Each case lowers the kernel for one chip
of a described ``v5e:2x2`` topology and checks that the compiled program
holds it (``tpu_custom_call``); one more lowers the served paged entry
point for all four chips, the pool split by blocks, and checks that no
chip gathers the pool.  The topology is described inside a fixture: only
the test process that runs this file loads the TPU library.
"""
from __future__ import annotations

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import offload
from repro.core.placement import PAGED_KV_CACHE_AXES, Env
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import flash_attention_pallas
from repro.launch.mesh import auto_mesh, mesh_axes

CFG = get_config("llama3.2-1b")
HKV = CFG.n_kv_heads
G = CFG.n_heads // CFG.n_kv_heads
D = CFG.resolved_head_dim()
SCALE = D ** -0.5
SLOTS, MAX_SEQ, BLOCK_SIZE, N_BLOCKS = 16, 2048, 16, 2048
PAYLOAD = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _compiles_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_paged_decode_compiles(shape, kv_dtype):
    """Paged decode with a hot-window ``starts`` and the LSE output; the
    quantized pools carry their (N, Hkv, 1, block_size) scale pools."""
    pool = shape((N_BLOCKS, HKV, BLOCK_SIZE, D), PAYLOAD[kv_dtype])
    scales = None
    if kv_dtype != "bf16":
        scales = shape((N_BLOCKS, HKV, 1, BLOCK_SIZE), jnp.float32)
    fn = functools.partial(paged_decode_attention_pallas, scale=SCALE)
    _compiles_kernel(
        lambda q, k, v, t, n, st, ks, vs: fn(q, k, v, t, n, starts=st,
                                             k_scale=ks, v_scale=vs),
        shape((SLOTS, HKV, G, D), jnp.bfloat16), pool, pool,
        shape((SLOTS, MAX_SEQ // BLOCK_SIZE), jnp.int32),
        shape((SLOTS,), jnp.int32), shape((SLOTS,), jnp.int32), scales, scales,
    )


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("chunk", [16, 256])
def test_flash_prefill_compiles(shape, kv_dtype, chunk):
    """Chunked prefill against the staging window at a traced offset."""
    kv = shape((1, HKV, MAX_SEQ, D), PAYLOAD[kv_dtype])
    scales = None
    if kv_dtype != "bf16":
        scales = shape((1, HKV, 1, MAX_SEQ), jnp.float32)
    fn = functools.partial(flash_attention_pallas, scale=SCALE, causal=True,
                           block_q=min(512, chunk), block_k=512)
    _compiles_kernel(
        lambda q, k, v, off, ks, vs: fn(q, k, v, q_offset=off,
                                        k_scale=ks, v_scale=vs),
        shape((1, HKV * G, chunk, D), jnp.bfloat16), kv, kv,
        shape((), jnp.int32), scales, scales,
    )


def test_dense_decode_compiles(shape):
    kv = shape((SLOTS, HKV, MAX_SEQ, D), jnp.bfloat16)
    fn = functools.partial(decode_attention_pallas, scale=SCALE, block_s=512)
    _compiles_kernel(fn, shape((SLOTS, HKV, G, D), jnp.bfloat16), kv, kv,
                     shape((SLOTS,), jnp.int32))


def _gathered(text: str) -> list[int]:
    """Element counts of every all-gather result in compiled HLO text."""
    counts = []
    for line in text.splitlines():
        m = re.search(r"=\s*(.*?)\ball-gather(?:-start)?\(", line)
        if m:
            for dims in re.findall(r"\[([0-9,]*)\]", m.group(1)):
                counts.append(math.prod(int(d) for d in dims.split(",") if d))
    return counts


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_split_paged_decode_compiles_without_pool_gather(topo, monkeypatch,
                                                         kv_dtype):
    """The served paged entry point on all four chips of the described
    host, the pool split by blocks ((data 4, model 1), as the serve splits
    it): each chip runs the kernel on its own blocks, and no all-gather
    is as large as one chip's share of the pool."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = auto_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    env = Env(axes=mesh_axes(mesh), mesh=mesh)
    pool_dims = (N_BLOCKS, HKV, BLOCK_SIZE, D)
    spec = env.kv_spec(PAGED_KV_CACHE_AXES, pool_dims)
    assert spec[0] == "data"

    def arg(dims, dtype, spec=P()):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pool = arg(pool_dims, PAYLOAD[kv_dtype], spec)
    scales = None
    if kv_dtype != "bf16":
        scales = arg((N_BLOCKS, HKV, 1, BLOCK_SIZE), jnp.float32, P(*spec[:2]))
    text = jax.jit(
        lambda q, k, v, t, n, st, ks, vs: offload.paged_decode_attention(
            env, q, k, v, t, n, starts=st, k_scale=ks, v_scale=vs,
            return_lse=True)
    ).lower(
        arg((SLOTS, HKV * G, D), jnp.bfloat16), pool, pool,
        arg((SLOTS, MAX_SEQ // BLOCK_SIZE), jnp.int32),
        arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.int32), scales, scales,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    lane_share = math.prod(pool_dims) // 4
    assert max(_gathered(text), default=0) < lane_share
