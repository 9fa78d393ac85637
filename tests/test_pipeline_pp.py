"""Pipeline parallelism: GPipe schedule == sequential reference, fwd + grad
(subprocess: needs >1 placeholder device)."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import auto_mesh
from repro.training.pipeline_pp import pipeline_forward, sequential_reference, split_stages

mesh = auto_mesh((4,), ("stage",))
L, D = 8, 16
n_micro, B, S = 6, 2, 4
key = jax.random.key(0)
w = jax.random.normal(key, (L, D, D)) * 0.3
params = {"w": w}

def block_fn(p, h):
    # p["w"]: (L/stages, D, D) — apply the stage's layers sequentially
    def body(hc, wl):
        return jnp.tanh(hc @ wl), None
    out, _ = jax.lax.scan(body, h, p["w"])
    return out

x = jax.random.normal(jax.random.key(1), (n_micro, B, S, D))
stage_params = split_stages(params, 4)

ref = sequential_reference(block_fn, stage_params, x, 4)
with mesh:
    got = jax.jit(lambda sp, xx: pipeline_forward(block_fn, sp, xx, mesh))(stage_params, x)
fwd_err = float(jnp.max(jnp.abs(ref - got)))

# gradient equivalence
def loss_pp(sp, xx):
    return jnp.sum(pipeline_forward(block_fn, sp, xx, mesh) ** 2)

def loss_ref(sp, xx):
    return jnp.sum(sequential_reference(block_fn, sp, xx, 4) ** 2)

with mesh:
    g_pp = jax.jit(jax.grad(loss_pp))(stage_params, x)
g_ref = jax.grad(loss_ref)(stage_params, x)
g_err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
            zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)))
print(json.dumps({"fwd_err": fwd_err, "grad_err": g_err}))
"""


def test_gpipe_matches_sequential_fwd_and_grad():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["fwd_err"] < 1e-5, res
    assert res["grad_err"] < 1e-4, res
