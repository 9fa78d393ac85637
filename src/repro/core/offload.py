"""Disaggregated ("offloaded") decode attention — the paper's core mechanism.

The GPU↔HPU split becomes a *layout* split on the TPU mesh:

  compute side   activations sharded [batch -> (pod,data), heads -> model]
                 (linear layers are TP over `model`, DP over `data`)
  HPU side       KV cache + attention sharded per a placement policy
                 (``repro.core.placement``), maximizing the aggregate HBM
                 bandwidth serving the memory-bound GEMV-shaped attention.

The boundary resharding of per-token Q (and the freshly produced K/V) is
the analogue of the paper's PCIe Q/K/V descriptor transfer: a few
``batch*heads*head_dim`` vectors per layer per step, negligible next to
the KV cache itself.  We emit it as ``with_sharding_constraint`` and let
GSPMD schedule the all-to-all; the big cache is *already resident* in the
HPU layout (its in_sharding comes from ``cache_specs``), so no bulk data
moves — exactly the paper's design point.

``offload="none"`` runs everything in the compute layout (the GPU-only
baseline of the paper's evaluation).

Kernel choice follows the backend (:func:`repro.kernels.ops.on_tpu`): on
a TPU the entry points run the Pallas kernels, elsewhere the jnp paths.
On a mesh a kernel runs under ``shard_map`` in the cache's own layout
(GSPMD cannot partition a kernel call and would gather the whole cache
onto every chip): batch and head shards attend independently, and a
block-sharded paged pool attends each lane's own blocks and merges the
lanes' partials by log-sum-exp.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.placement import KV_CACHE_AXES, PAGED_KV_CACHE_AXES, Env
from repro.kernels import ops
from repro.models import attention as attn


def _wsc(env: Env, x: jax.Array, spec: P) -> jax.Array:
    if spec == P() or not spec:
        return x
    if env.mesh is not None:
        spec = NamedSharding(env.mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)


def _dims(spec: P, ndim: int) -> tuple:
    """A spec's per-dimension mesh axes, padded with ``None`` to ``ndim``."""
    return tuple(spec) + (None,) * (ndim - len(spec))


def _sharded(env: Env, fn, in_specs, out_specs):
    """Run a kernel call per shard of ``env``'s mesh."""
    return jax.shard_map(fn, mesh=env.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def constrain_cache(env: Env, k_cache: jax.Array, v_cache: jax.Array):
    """Pin caches to the policy layout (idempotent when already resident)."""
    if not env.axes:
        return k_cache, v_cache
    spec = env.kv_spec(KV_CACHE_AXES, k_cache.shape)
    return _wsc(env, k_cache, spec), _wsc(env, v_cache, spec)


def _decode_kernel(env: Env, q, k_cache, v_cache, lengths, scale):
    """Dense decode kernel, per (batch, kv-head) shard of the cache."""
    if not env.axes:
        return ops.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    b_ax, s_ax, h_ax, _ = _dims(env.kv_spec(KV_CACHE_AXES, k_cache.shape), 4)
    if s_ax is not None:
        raise NotImplementedError(
            f"kv policy {env.kv_policy!r} splits the dense cache's sequence "
            "axis; the decode kernel has no cross-lane merge for it"
        )
    q_spec = P(b_ax, h_ax, None)
    kv_spec = P(b_ax, None, h_ax, None)
    return _sharded(
        env,
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, scale=scale),
        (q_spec, kv_spec, kv_spec, P(b_ax)), q_spec,
    )(q, k_cache, v_cache, lengths)


def _paged_kernel(env: Env, q, k_pool, v_pool, tables, lengths, *, scale,
                  starts, k_scale, v_scale):
    """Paged decode kernel on the pool's lanes -> ``(out, lse)``.

    A lane holding a contiguous run of physical blocks attends only
    those: table entries it does not hold become ``-1`` (not fetched,
    and masked by the kernel), and the lanes' partial outputs
    merge exactly by log-sum-exp.  A head-sharded pool needs no merge.
    """
    if starts is None:
        starts = jnp.zeros(lengths.shape, jnp.int32)
    quant = k_scale is not None
    if not env.axes:
        return ops.paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, scale=scale, starts=starts,
            k_scale=k_scale, v_scale=v_scale, return_lse=True,
        )
    n_ax, h_ax, s_ax, _ = _dims(env.kv_spec(PAGED_KV_CACHE_AXES, k_pool.shape), 4)
    if s_ax is not None:
        raise NotImplementedError(
            f"kv policy {env.kv_policy!r} splits positions inside a block; "
            "the paged kernel needs whole blocks on a lane"
        )

    def body(q, k, v, tables, lengths, starts, *scales):
        ks, vs = scales if quant else (None, None)
        if n_ax is None:
            return ops.paged_decode_attention(
                q, k, v, tables, lengths, scale=scale, starts=starts,
                k_scale=ks, v_scale=vs, return_lse=True,
            )
        held = k.shape[0]
        local = tables - jax.lax.axis_index(n_ax) * held
        tables = jnp.where((local >= 0) & (local < held), local, -1)
        # each lane's partial stays float32 through the merge: rounding
        # it first would perturb every output element by up to an ulp
        out, lse = ops.paged_decode_attention(
            q, k, v, tables, lengths, scale=scale, starts=starts,
            k_scale=ks, v_scale=vs, return_lse=True, out_dtype=jnp.float32,
        )
        m = jax.lax.pmax(lse, n_ax)                        # (B, Hkv, G)
        w = jnp.exp(lse - m)
        den = jax.lax.psum(w, n_ax)
        wq = w.reshape(w.shape[0], -1, 1)                  # (B, Hq, 1)
        num = jax.lax.psum(out * wq, n_ax)
        out = (num / den.reshape(wq.shape)).astype(q.dtype)
        return out, m + jnp.log(den)

    q_spec = P(None, h_ax, None)
    pool_spec = P(n_ax, h_ax, None, None)
    in_specs = (q_spec, pool_spec, pool_spec, P(), P(), P())
    args = (q, k_pool, v_pool, tables, lengths, starts)
    if quant:
        in_specs += (pool_spec, pool_spec)
        args += (k_scale, v_scale)
    return _sharded(env, body, in_specs, (q_spec, q_spec))(*args)


def _flash_kernel(env: Env, q, k, v, q_offset):
    """Causal flash kernel, per (batch, head) shard of the activations."""
    if not env.axes:
        return ops.flash_attention(q, k, v, causal=True, q_offset=q_offset)
    b_ax, _, h_ax, _ = _dims(
        env.act_spec(("batch", None, "kv_heads", None), k.shape), 4)
    q_spec = P(b_ax, None, h_ax, None)
    return _sharded(
        env,
        lambda q, k, v, off: ops.flash_attention(q, k, v, causal=True,
                                                 q_offset=off),
        (q_spec, q_spec, q_spec, P()), q_spec,
    )(q, k, v, jnp.asarray(q_offset, jnp.int32))


def decode_attention(
    env: Env,
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """One decode step of attention, routed through the HPU layout.

    q (B, Hq, D); caches (B, S, Hkv, D); lengths (B,) -> (B, Hq, D).
    """
    if env.axes and env.offload == "hpu":
        # --- boundary transfer (PCIe analogue): per-token Q to HPU layout
        q = _wsc(env, q, env.kv_spec(("kv_batch", "kv_heads", "head_dim"), q.shape))
        k_cache, v_cache = constrain_cache(env, k_cache, v_cache)
    acc = jnp.bfloat16 if env.bf16_combine else jnp.float32
    if ops.on_tpu():
        scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        out = _decode_kernel(env, q, k_cache, v_cache, lengths, scale)
    else:
        out = attn.decode_attention(
            q, k_cache, v_cache, lengths, scale=scale, acc_dtype=acc
        )
    if env.axes and env.offload == "hpu":
        # --- gather results back to the compute layout (contiguous merge;
        # the paper's preferred batch-parallel merge order)
        out = _wsc(env, out, env.act_spec(("batch", "heads", "head_dim"), out.shape))
    return out


def paged_decode_attention(
    env: Env,
    q: jax.Array,             # (B, Hq, D)
    k_pool: jax.Array,        # (N_blocks, Hkv, block_size, D) — kernel-native
    v_pool: jax.Array,        # (N_blocks, Hkv, block_size, D)
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,       # (B,)
    *,
    scale: float | None = None,
    starts: jax.Array | None = None,    # (B,) first hot position
    k_scale: jax.Array | None = None,   # (N_blocks, Hkv, 1, block_size) f32
    v_scale: jax.Array | None = None,
    return_lse: bool = False,
):
    """One decode step against the paged block pool, in the HPU layout.

    The pool's *block* axis (not the batch axis) is what the HPU lanes
    split — a physical block lives wholly on one lane, so a sequence's
    block-table gather fans out across whichever lanes hold its blocks
    and the boundary traffic stays the per-token Q/K/V descriptors.

    Tiered-KV params (see ``kernels/ops.paged_decode_attention``):
    ``k_scale``/``v_scale`` mark an int8/fp8 pool dequantized in-kernel,
    ``starts`` restricts attention to the hot window ``[start, length)``,
    and ``return_lse`` returns ``(out, lse (B,Hkv,G))`` for the
    log-sum-exp merge with a cold-tier partial.
    """
    if env.axes and env.offload == "hpu":
        q = _wsc(env, q, env.kv_spec(("kv_batch", "kv_heads", "head_dim"), q.shape))
        pool_spec = env.kv_spec(PAGED_KV_CACHE_AXES, k_pool.shape)
        k_pool = _wsc(env, k_pool, pool_spec)
        v_pool = _wsc(env, v_pool, pool_spec)
    if ops.on_tpu():
        scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        out, lse = _paged_kernel(
            env, q, k_pool, v_pool, block_tables.astype(jnp.int32),
            lengths.astype(jnp.int32), scale=scale, starts=starts,
            k_scale=k_scale, v_scale=v_scale,
        )
        out = (out, lse) if return_lse else out
    else:
        # gather-to-contiguous oracle path: identical math to the dense
        # decode (valid positions land at the same indices, pad is masked)
        from repro.kernels import ref

        if starts is None and k_scale is None and not return_lse:
            k = ref.gather_paged_cache(k_pool, block_tables)
            v = ref.gather_paged_cache(v_pool, block_tables)
            out = attn.decode_attention(
                q, k, v, lengths, scale=scale,
                acc_dtype=jnp.bfloat16 if env.bf16_combine else jnp.float32,
            )
        else:
            out = ref.paged_decode_attention(
                q, k_pool, v_pool, block_tables, lengths, scale=scale,
                starts=starts, k_scale=k_scale, v_scale=v_scale,
                return_lse=return_lse,
            )
    if env.axes and env.offload == "hpu":
        if return_lse:
            o, lse = out
            o = _wsc(env, o, env.act_spec(("batch", "heads", "head_dim"), o.shape))
            return o, lse
        out = _wsc(env, out, env.act_spec(("batch", "heads", "head_dim"), out.shape))
    return out


def mla_decode_attention(
    env: Env,
    q_latent: jax.Array,
    q_rope: jax.Array,
    ckv_cache: jax.Array,
    krope_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
) -> jax.Array:
    """MLA absorbed decode through the HPU layout (cache = compressed latent).

    The latent cache has no head axis, so the `head` policy degrades to
    `sequence` automatically (resolve_spec drops non-existent axes).
    """
    if env.axes and env.offload == "hpu":
        q_latent = _wsc(
            env, q_latent, env.kv_spec(("kv_batch", "kv_heads", None), q_latent.shape)
        )
        q_rope = _wsc(
            env, q_rope, env.kv_spec(("kv_batch", "kv_heads", None), q_rope.shape)
        )
        cspec = env.kv_spec(("kv_batch", "kv_seq", None), ckv_cache.shape)
        ckv_cache = _wsc(env, ckv_cache, cspec)
        krope_cache = _wsc(
            env, krope_cache,
            env.kv_spec(("kv_batch", "kv_seq", None), krope_cache.shape),
        )
    out = attn.mla_decode_attention(
        q_latent, q_rope, ckv_cache, krope_cache, lengths, scale=scale,
        acc_dtype=jnp.bfloat16 if env.bf16_combine else jnp.float32,
    )
    if env.axes and env.offload == "hpu":
        out = _wsc(env, out, env.act_spec(("batch", "heads", None), out.shape))
    return out


def verify_attention(
    env: Env,
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
    chunk: int = 1024,
) -> jax.Array:
    """Multi-position draft-verify attention through the HPU layout.

    q (B, T, Hq, D) scores T speculative positions per slot against the
    live cache (B, S, Hkv, D); query ``t`` of slot ``b`` sits at absolute
    position ``lengths[b] + t`` (its K/V must already be written there).
    This is the decode-side twin of :func:`prefill_attention`'s
    ``q_offset`` continuation, generalized to *per-slot* offsets — the
    GEMM-shaped pass that lets one weight stream verify ``T`` tokens.

    The serving engine's verify path deliberately does NOT use this:
    greedy speculation must be bitwise token-identical to plain
    decoding, and this differently-shaped program rounds bf16 logits
    differently than the per-token decode attention, flipping argmax on
    near-ties — so ``dense.verify_step`` unrolls per-position decode
    passes instead.  Kept as the batched pass for future tree/batch
    verification where sampling absorbs the rounding.  No Pallas kernel:
    T is tiny, so the exact jnp flash path is used on every backend.
    """
    if env.axes and env.offload == "hpu":
        q = _wsc(env, q, env.kv_spec(("kv_batch", None, "kv_heads", "head_dim"), q.shape))
        k_cache, v_cache = constrain_cache(env, k_cache, v_cache)
    out = attn.chunked_attention(
        q, k_cache, v_cache, causal=True, q_offset=lengths, scale=scale, chunk=chunk
    )
    if env.axes and env.offload == "hpu":
        out = _wsc(env, out, env.act_spec(("batch", "seq", "heads", "head_dim"), out.shape))
    return out


def prefill_attention(
    env: Env,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_offset=0,
    chunk: int = 1024,
) -> jax.Array:
    """Prefill/train attention (compute-side; flash-chunked).

    ``q_offset`` (scalar, may be traced) places q[:, 0] at an absolute
    position for chunked-prefill continuation: k/v then cover the full
    cache window and only positions `<= q_offset + i` contribute to query
    ``i``.  Both the Pallas kernel and the jnp path honor it.

    With ``env.sequence_parallel`` the q/output sequence axis is sharded
    over `model` (context parallelism): the rule set gives `seq -> model`
    and GSPMD partitions the global attention math, all-gathering the much
    smaller K/V instead of replicating the O(S^2) compute.  This is how
    archs whose head count does not divide the model axis (yi-34b 56H,
    minicpm 36H, llama3.2-3b 24H on a 16-way axis) avoid 16x redundant
    attention FLOPs.
    """
    if env.axes:
        spec = env.act_spec(("batch", "seq", "heads", "head_dim"), q.shape)
        q = _wsc(env, q, spec)
    if ops.on_tpu():
        out = _flash_kernel(env, q, k, v, q_offset)
    else:
        out = attn.chunked_attention(q, k, v, causal=True, q_offset=q_offset, chunk=chunk)
    if env.axes:
        out = _wsc(env, out, env.act_spec(("batch", "seq", "heads", "head_dim"), out.shape))
    return out
