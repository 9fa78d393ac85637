"""Backend choice and multi-device serving.

* ``chip_smoke.py`` refuses to run off the TPU;
* ``core/offload.py`` serves the jnp paths on the CPU, and its kernel
  path (forced here, kernels in interpret mode) agrees with them;
* the default serve path runs on a four-device host, the KV pool split
  across the devices, with and without the kernels, and cluster replicas
  either own disjoint devices or fail (subprocess: the device count is
  fixed before jax initializes).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import offload
from repro.core.placement import Env
from repro.kernels import ops, ref
from repro.models import attention as attn

REPO = Path(__file__).resolve().parents[1]


def _run(args, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run(args, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=timeout)


def test_chip_smoke_exits_nonzero_off_tpu():
    out = _run([sys.executable, "chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def _paged_case(quant=None):
    B, Hkv, G, D, bs, MB, N = 3, 2, 2, 16, 8, 6, 24
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
    lengths = np.array([5, 41, 20], np.int32)
    tables = np.zeros((B, MB), np.int32)
    free = iter(np.random.default_rng(0).permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // bs)):
            tables[b, j] = next(free)
    kw = dict(starts=jnp.asarray([0, 9, 3], jnp.int32))
    if quant:
        kp, ksc = ref.kv_quantize_pool(kp, quant)
        vp, vsc = ref.kv_quantize_pool(vp, quant)
        kw.update(k_scale=ksc, v_scale=vsc)
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)), kw


def _forbid_kernels(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("kernel called off the TPU")
    for name in ("decode_attention", "paged_decode_attention", "flash_attention"):
        monkeypatch.setattr(ops, name, boom)


def test_offload_takes_jnp_path_on_cpu(monkeypatch):
    assert not ops.on_tpu()
    _forbid_kernels(monkeypatch)
    env = Env()
    args, kw = _paged_case()
    out, lse = offload.paged_decode_attention(env, *args, return_lse=True, **kw)
    exp, exp_lse = ref.paged_decode_attention(*args, return_lse=True, **kw)
    np.testing.assert_allclose(out, exp, atol=1e-5)
    np.testing.assert_allclose(lse, exp_lse, atol=1e-5)
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 16, 2, 8)) for i in range(3))
    np.testing.assert_allclose(
        offload.prefill_attention(env, q, k, v, q_offset=0),
        attn.chunked_attention(q, k, v, causal=True), atol=1e-6)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_offload_kernel_path_matches_jnp(monkeypatch, quant):
    """What a TPU runs — the Pallas kernels — agrees with the CPU path."""
    args, kw = _paged_case(quant)
    exp, exp_lse = offload.paged_decode_attention(Env(), *args, return_lse=True, **kw)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: True)
    out, lse = offload.paged_decode_attention(Env(), *args, return_lse=True, **kw)
    np.testing.assert_allclose(out, exp, atol=1e-5)
    np.testing.assert_allclose(lse, exp_lse, atol=1e-5)


FOUR_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from repro.kernels import ops
if sys.argv[1] == "kernels":
    ops.on_tpu = lambda: True
    ops._interpret = lambda: True
from repro.launch import serve
from repro.launch.mesh import replica_meshes
from repro.serving.cluster import Cluster
from repro.serving.engine import Request

argv = ["--arch", "llama3.2-1b", "--reduced", "--cache", "paged",
        "--block-size", "8", "--blocks", "64", "--schedule", "hybrid",
        "--slots", "4", "--max-seq", "64", "--prefill-chunk", "16",
        "--kv-dtype", sys.argv[2]]
args = serve.build_parser().parse_args(argv)
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 500, size=n).astype(np.int32) for n in (5, 23, 40, 12)]
res = {}
for n in (1, 4):
    srv = serve.build_server(args, devices=jax.devices()[:n])
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)]
    for r in reqs:
        srv.front.submit(r)
    srv.front.run()
    res[f"tokens{n}"] = [r.out_tokens for r in reqs]
    res[f"lanes{n}"] = len(srv.engine.cache["k"].sharding.device_set)
try:
    replica_meshes(3)
    res["uneven"] = "accepted"
except ValueError:
    res["uneven"] = "refused"
res["disjoint"] = len({d for m in replica_meshes(2) for d in m.devices.flat})
srv = serve.build_server(args)
try:
    Cluster(srv.model, srv.params, 2, cache_kind="paged", n_slots=2,
            max_seq=64, block_size=8)
    res["shared"] = "accepted"
except ValueError:
    res["shared"] = "refused"
print(json.dumps(res))
"""


SPLIT_KERNEL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import offload
from repro.core.placement import Env
from repro.kernels import ops, ref
from repro.launch.mesh import auto_mesh, mesh_axes
ops.on_tpu = lambda: True
ops._interpret = lambda: True
B, Hkv, G, D, bs, MB, N = 3, 4, 2, 16, 8, 6, 32
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
kp = jax.random.normal(ks[1], (N, Hkv, bs, D), jnp.float32)
vp = jax.random.normal(ks[2], (N, Hkv, bs, D), jnp.float32)
lengths = np.array([5, 41, 20], np.int32)
tables = np.zeros((B, MB), np.int32)
free = iter(np.random.default_rng(0).permutation(np.arange(1, N)))
for b, n in enumerate(lengths):
    for j in range(-(-int(n) // bs)):
        tables[b, j] = next(free)
args = (jnp.asarray(tables), jnp.asarray(lengths))
kw = dict(starts=jnp.asarray([0, 9, 3], jnp.int32))
res = {}
for shape in ((4, 1), (2, 2)):
    mesh = auto_mesh(shape, ("data", "model"))
    env = Env(axes=mesh_axes(mesh), mesh=mesh)
    for quant in (None, "int8"):
        kq, vq, kwq = kp, vp, dict(kw)
        if quant:
            kq, ksc = ref.kv_quantize_pool(kp, quant)
            vq, vsc = ref.kv_quantize_pool(vp, quant)
            kwq.update(k_scale=ksc, v_scale=vsc)
        out, lse = jax.jit(lambda *a: offload.paged_decode_attention(
            env, *a, return_lse=True, **kwq))(q, kq, vq, *args)
        exp, exp_lse = ref.paged_decode_attention(q, kq, vq, *args,
                                                  return_lse=True, **kwq)
        res[f"{shape}-{quant}"] = max(float(jnp.abs(out - exp).max()),
                                      float(jnp.abs(lse - exp_lse).max()))
print(json.dumps(res))
"""


def test_split_paged_kernel_matches_oracle():
    """The paged kernel on a pool split over four devices (blocks over
    ``data``, heads over ``model``): each lane attends its own blocks and
    the lanes merge by log-sum-exp to the unsplit oracle."""
    out = _run([sys.executable, "-c", SPLIT_KERNEL])
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res) == 4 and all(err < 1e-5 for err in res.values()), res


@pytest.mark.parametrize("path,kv_dtype", [("jnp", "bf16"), ("kernels", "int8")])
def test_serve_on_four_devices(path, kv_dtype):
    out = _run([sys.executable, "-c", FOUR_DEVICES, path, kv_dtype])
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["lanes1"] == 1 and res["lanes4"] == 4
    assert all(len(t) == 6 for t in res["tokens4"])
    # the split decode (per-lane partials merged in float32) serves the
    # same greedy tokens as one device
    assert res["tokens4"] == res["tokens1"]
    assert res["uneven"] == "refused" and res["disjoint"] == 4
    assert res["shared"] == "refused"
