"""Sharded execution on 8 placeholder CPU devices (subprocess — the device
count must be fixed before jax initializes, which pytest already did)."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.reduced import reduce_config
from repro.core.placement import Env
from repro.models.registry import build_model
from repro.launch import specs as S
from repro.launch.mesh import auto_mesh, mesh_axes

mesh = auto_mesh((4, 2), ("data", "model"))
axes = mesh_axes(mesh)
cfg = reduce_config("llama3.2-1b").with_overrides(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256, head_dim=16)

# single-device reference
m0 = build_model(cfg, Env())
params = m0.init(jax.random.key(0))
B, Sq = 4, 8
toks = jax.random.randint(jax.random.key(1), (B, Sq), 0, cfg.vocab)
c0 = m0.init_cache(B, 16)
log0, c0 = jax.jit(m0.prefill)(params, toks, c0)
log0d, _ = jax.jit(m0.decode_step)(params, c0, jnp.argmax(log0, -1).astype(jnp.int32))

results = {}
for policy in ("batch", "head", "sequence"):
    env = Env(axes=axes, kv_policy=policy, offload="hpu")
    m = build_model(cfg, env)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), m.param_specs(),
                       is_leaf=lambda x: isinstance(x, P))
    params_sharded = jax.tree.map(lambda x, sh: jax.device_put(x, sh), params, psh)
    cache = m.init_cache(B, 16)
    csh = S.cache_shardings(m, jax.eval_shape(lambda: cache), mesh)
    cache = jax.tree.map(lambda x, sh: jax.device_put(x, sh), cache, csh)
    with mesh:
        log, cache = jax.jit(m.prefill)(params_sharded, toks, cache)
        logd, _ = jax.jit(m.decode_step)(
            params_sharded, cache, jnp.argmax(log, -1).astype(jnp.int32))
    err = float(jnp.max(jnp.abs(logd.astype(jnp.float32) - log0d.astype(jnp.float32))))
    results[policy] = err

# sharded train step
from repro.configs.base import ParallelConfig, RunConfig, TrainConfig
from repro.training.trainer import make_train_step
env = Env(axes=axes, fsdp=True)
mt = build_model(cfg, env)
run = RunConfig(model=cfg, parallel=ParallelConfig(zero_stage=1), train=TrainConfig())
init_state, train_step, state_specs, _ = make_train_step(mt, run)
with mesh:
    state = init_state(jax.random.key(0))
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs(),
                       is_leaf=lambda x: isinstance(x, P))
    state = jax.tree.map(lambda x, sh: jax.device_put(x, sh), state, ssh)
    batch = {
        "inputs": toks, "targets": toks,
        "mask": jnp.ones_like(toks, jnp.float32),
    }
    state, metrics = jax.jit(train_step)(state, batch)
results["train_loss"] = float(metrics["loss"])
print(json.dumps(results))
"""


def test_sharded_decode_matches_single_device_all_policies():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for policy in ("batch", "head", "sequence"):
        assert results[policy] < 5e-2, (policy, results)
    assert results["train_loss"] > 0 and results["train_loss"] == results["train_loss"]
