"""Compile a cell's step programs for a described TPU v5e, with no chip
attached, and print what the compiler says they hold in memory.

    JAX_PLATFORMS=cpu python bench/compile_check.py minicpm-2b.decode-long

For the cell's configuration and engine settings it lowers the two
programs a served step runs (the paged decode step, and one decode step
fused with a prefill chunk of the largest bucket into the staging cache)
with shapes only, the Pallas kernels on their TPU path, and prints
``memory_analysis()`` and the compile seconds of each.  Nothing runs, so
nothing here is a device measurement.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "lib"))
import spec  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))


def main(argv: list[str]) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.placement import Env
    from repro.kernels import ops
    from repro.models.registry import build_model
    from repro.serving.sampler import SamplerConfig, sample_on_device

    ops.on_tpu = lambda: True            # compile the TPU path off the TPU
    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    for name in argv:
        cell = spec.load_cell(name)
        e = cell.engine
        cfg = cell.family.model_config(cell.config)
        model = build_model(cfg, Env())
        max_blocks = -(-e["max_seq"] // e["block_size"])
        span = max_blocks * e["block_size"]

        def sds(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev),
                tree)

        params = sds(model.param_shapes())
        cache = sds(jax.eval_shape(lambda: model.init_paged_cache(
            e["n_slots"], e["n_blocks"], e["block_size"], max_blocks)))
        staging = sds(jax.eval_shape(lambda: model.init_cache(2, span)))
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
        toks = jax.ShapeDtypeStruct((e["n_slots"],), jnp.int32, sharding=dev)
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=dev)
        chunk = jax.ShapeDtypeStruct((1, e["prefill_chunk"]), jnp.int32,
                                     sharding=dev)
        greedy = SamplerConfig()

        def decode(params, cache, toks, rng, eos):
            return model.paged_decode_sample_step(params, cache, toks, rng,
                                                  eos, sampler=greedy)

        def fused(params, cache, staging, toks, chunk, lane, off, nv, rng, eos):
            pre, staging = model.prefill_step(params, staging, chunk, lane,
                                              off, nv)
            logits, cache = model.paged_decode_step(params, cache, toks)
            t = sample_on_device(logits, rng, greedy)
            return t, t == eos, sample_on_device(pre, rng, greedy), cache, staging

        progs = {
            "decode": (decode, (params, cache, toks, rng, toks)),
            "fused": (fused, (params, cache, staging, toks, chunk, i32, i32,
                              i32, rng, toks)),
        }
        for pname, (fn, args) in progs.items():
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            dt = time.perf_counter() - t0
            m = compiled.memory_analysis()
            gb = 1e9
            print(f"{name} {pname}: compile {dt:.1f}s; arguments "
                  f"{m.argument_size_in_bytes / gb:.3f} GB, outputs "
                  f"{m.output_size_in_bytes / gb:.3f} GB, aliased "
                  f"{m.alias_size_in_bytes / gb:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / gb:.3f} GB; kernel "
                  f"{'tpu_custom_call' in compiled.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
