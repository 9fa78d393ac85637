"""Pipeline parallelism (GPipe schedule) over a mesh axis.

Completes the framework's parallelism matrix (DP/TP/SP/EP/FSDP + PP): the
layer stack is split into ``n_stages`` contiguous stages whose parameters
live on different slices of a mesh axis (at scale: the `pod` axis — stage
boundaries cross the slow DCN link exactly once per microbatch, the
standard multi-pod layout).  Microbatches stream through with a GPipe
schedule inside ``shard_map``; boundary activations move by
``lax.ppermute`` and the bubble is the usual (n_stages-1)/(n_micro +
n_stages - 1).

Differentiable: ppermute has a transpose rule, so ``jax.grad`` through
``pipeline_forward`` yields exact gradients (verified against the
sequential reference in tests/test_pipeline_pp.py).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any


def split_stages(stacked_params: Pytree, n_stages: int) -> Pytree:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""

    def reshape(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def pipeline_forward(
    block_fn: Callable[[Pytree, jax.Array], jax.Array],
    stage_params: Pytree,          # (n_stages, L/stages, ...) sharded on axis
    x: jax.Array,                  # (n_micro, micro_B, S, D) replicated
    mesh,
    axis: str = "stage",
) -> jax.Array:
    """GPipe forward.  Returns (n_micro, micro_B, S, D) final activations.

    ``block_fn(params_one_stage, h)`` applies one stage's layers.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    T = n_micro + n_stages - 1

    def per_stage(params_local, x_local):
        # params_local: (1, L/stages, ...); x_local: full (n_micro, ...)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        micro = x_local  # only stage 0 actually consumes it
        # carries become stage-varying inside the loop; mark them up front
        buf = jax.lax.pcast(jnp.zeros_like(x_local[0]), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(x_local), (axis,), to="varying")

        def step(t, carry):
            buf, outs = carry
            mb = t - stage  # microbatch index active on this stage
            active = (mb >= 0) & (mb < n_micro)
            # stage 0 ingests the microbatch; others use the permuted buf
            inject = jnp.where(
                stage == 0,
                micro[jnp.clip(mb, 0, n_micro - 1)],
                buf,
            )
            h = block_fn(params_local, inject)
            h = jnp.where(active, h, jnp.zeros_like(h))
            # last stage records its output; others forward it
            take = active & (stage == n_stages - 1)
            upd = outs.at[jnp.clip(mb, 0, n_micro - 1)].set(h)
            outs = jnp.where(take, upd, outs)
            buf = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, T, step, (buf, outs))
        # every device returns its `outs`; only the last stage's is real —
        # psum after masking so the result is replicated across stages
        mask = (stage == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, x)


def sequential_reference(block_fn, stage_params, x, n_stages):
    """Same math without the pipeline (for tests): apply stages in order."""
    out = []
    for m in range(x.shape[0]):
        h = x[m]
        for s in range(n_stages):
            p_s = jax.tree.map(lambda a: a[s], stage_params)
            h = block_fn(p_s, h)
        out.append(h)
    return jnp.stack(out)
