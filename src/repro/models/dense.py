"""Dense llama-family decoder LM (yi-34b, llama3.2-1b/3b, minicpm-2b,
internvl2-76b backbone).

Layers are *stacked* on a leading dim and iterated with ``lax.scan`` so
60-80 layer models lower/compile quickly on the dry-run host.  The decode
path routes attention through ``repro.core.offload`` (the paper's
technique); train/prefill use chunked flash-style attention.

Multimodal stub (internvl2): ``batch["embeds"]`` (B, F, d_model) patch
embeddings are prepended to the token embeddings; the loss covers token
positions only.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import offload
from repro.core.placement import Env
from repro.kernels import ref
from repro.models import common as cm
from repro.models.common import ParamDef
from repro.serving.sampler import sample_on_device

Pytree = Any


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_defs(cfg) -> Pytree:
    L, D, V, F = cfg.n_layers, cfg.d_model, cfg.padded_vocab(), cfg.d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()
    defs: dict[str, Any] = {
        "embed": ParamDef((V, D), ("vocab", "embed"), "embed"),
        "blocks": {
            "ln1": ParamDef((L, D), ("layers", "embed"), "zeros"),
            "wq": ParamDef((L, D, Hq, Dh), ("layers", "embed", "heads", "head_dim")),
            "wk": ParamDef((L, D, Hkv, Dh), ("layers", "embed", "kv_heads", "head_dim")),
            "wv": ParamDef((L, D, Hkv, Dh), ("layers", "embed", "kv_heads", "head_dim")),
            "wo": ParamDef((L, Hq, Dh, D), ("layers", "heads", "head_dim", "embed")),
            "ln2": ParamDef((L, D), ("layers", "embed"), "zeros"),
            "w_gate": ParamDef((L, D, F), ("layers", "embed", "mlp")),
            "w_up": ParamDef((L, D, F), ("layers", "embed", "mlp")),
            "w_down": ParamDef((L, F, D), ("layers", "mlp", "embed")),
        },
        "final_norm": ParamDef((D,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((V, D), ("vocab", "embed"), "embed")
    return defs


def _unembed_table(params):
    return params.get("unembed", params["embed"])


# ---------------------------------------------------------------------------
# forward (train / prefill shared block)
# ---------------------------------------------------------------------------
def _qkv(cfg, p, h):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    return q, k, v


def _block_train(cfg, env: Env, p, x, positions, chunk=1024):
    h = cm.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h)
    q = cm.rope(q, positions, cfg.rope_theta)
    k = cm.rope(k, positions, cfg.rope_theta)
    o = offload.prefill_attention(env, q, k, v, chunk=chunk)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    h = cm.rmsnorm(x, p["ln2"], cfg.norm_eps)
    # Megatron-style SP: only the attention section runs sequence-sharded;
    # the FFN gathers the sequence (small activations) so its weights stay
    # tensor-parallel over `model` — otherwise every chip computes with the
    # FULL (D,F) weight and its gradient all-reduces over all chips
    # (measured 2x ~1.6 TiB/chip per step on yi-34b; EXPERIMENTS.md §Perf).
    if env.axes and env.sequence_parallel:
        h = jax.lax.with_sharding_constraint(
            h, env.act_spec(("batch", None, "embed"), h.shape)
        )
    ffn = cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    if env.axes:
        ffn = jax.lax.with_sharding_constraint(
            ffn, env.act_spec(("batch", "seq", "embed"), ffn.shape)
        )
        x = jax.lax.with_sharding_constraint(
            x, env.act_spec(("batch", "seq", "embed"), x.shape)
        )
    x = x + ffn
    return x


def hidden_states(cfg, env: Env, params, tokens, embeds=None, remat: bool = True):
    """Token (+ optional prepended frontend) embeddings -> final hidden."""
    x = cm.embed_lookup(params["embed"], tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    block = partial(_block_train, cfg, env)
    if remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable
        )

    def scan_body(xc, p_slice):
        return block(p_slice, xc, positions), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    return cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)


def loss_fn(cfg, env: Env, params, batch):
    hid = hidden_states(cfg, env, params, batch["inputs"], batch.get("embeds"))
    n_front = 0 if "embeds" not in batch else batch["embeds"].shape[1]
    hid = hid[:, n_front:]
    logits = cm.unembed(hid, _unembed_table(params), cfg.vocab)
    if env.axes:
        logits = jax.lax.with_sharding_constraint(
            logits, env.act_spec(("batch", "seq", "vocab"), logits.shape)
        )
    loss = cm.cross_entropy_loss(logits, batch["targets"], batch.get("mask"))
    return loss, {"loss": loss}


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def cache_defs(cfg, batch: int, max_seq: int) -> Pytree:
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim()
    kv = ParamDef(
        (L, batch, max_seq, Hkv, Dh),
        ("layers", "kv_batch", "kv_seq", "kv_heads", "head_dim"),
        "zeros",
    )
    defs = {
        "k": kv,
        "v": kv,
        "lengths": ParamDef((batch,), ("kv_batch",), "zeros"),
    }
    if cfg.kv_quant:
        sc = ParamDef(
            (L, batch, max_seq, Hkv),
            ("layers", "kv_batch", "kv_seq", "kv_heads"),
            "zeros",
        )
        defs["k_scale"] = sc
        defs["v_scale"] = sc
    return defs


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Pytree:
    defs = cache_defs(cfg, batch, max_seq)
    if cfg.kv_quant:
        dt = {"k": jnp.int8, "v": jnp.int8, "k_scale": jnp.bfloat16,
              "v_scale": jnp.bfloat16, "lengths": jnp.int32}
        return {k: jnp.zeros(d.shape, dt[k]) for k, d in defs.items()}
    return {
        k: jnp.zeros(d.shape, dtype if k != "lengths" else jnp.int32)
        for k, d in defs.items()
    }


# ---------------------------------------------------------------------------
# paged cache (block pool + per-slot block tables; serving/paged/)
# ---------------------------------------------------------------------------
def paged_cache_defs(
    cfg, n_slots: int, n_blocks: int, block_size: int, max_blocks: int,
    kv_dtype: str = "bf16", host_blocks: int = 0,
) -> Pytree:
    """Physical KV as a pool of fixed-size blocks shared by all slots.

    ``k``/``v`` carry the *block* axis where the dense cache carries the
    batch axis — that axis is what the HPU lanes split (placement rule
    ``kv_blocks``).  ``block_tables`` maps (slot, logical block) ->
    physical block; entry 0 is the engine's null block.

    The pool is stored kernel-native — ``(blocks, kv_heads, block, head_
    dim)``, heads *before* positions, unlike the dense ``(B, S, H, D)``
    cache — so the per-layer decode attention consumes it with zero
    relayout.  A transposed layout would materialize a full-pool copy
    per layer per token: exactly the HBM traffic the paper's design
    removes.

    Tiered-KV extensions: ``kv_dtype`` in {"fp8", "int8"} stores the
    pool quantized with per-vector f32 absmax scale pools
    (``k_scale``/``v_scale``, one scale per stored (head, position)
    vector — ~``4/(2*Dh)`` relative overhead — laid out ``(blocks,
    kv_heads, 1, block)`` so a block's scales are the lane-major tile the
    kernel's BlockSpec reads); ``host_blocks > 0`` adds a
    host-tier pool (``host_k``/``host_v`` + per-slot ``host_tables`` and
    ``cold_lengths``) holding spilled cold prefix blocks, with host id 0
    reserved as the null block like the device pool.  Host leaves are
    deliberately *unsharded* (block axis placement ``None``): they model
    host DRAM, not HBM, and their bytes are excluded from the device KV
    budget accounting.
    """
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim()
    kv = ParamDef(
        (L, n_blocks, Hkv, block_size, Dh),
        ("layers", "kv_blocks", "kv_heads", "kv_seq", "head_dim"),
        "zeros",
    )
    defs = {
        "k": kv,
        "v": kv,
        "block_tables": ParamDef((n_slots, max_blocks), ("kv_batch", None), "zeros"),
        "lengths": ParamDef((n_slots,), ("kv_batch",), "zeros"),
    }
    quant = kv_dtype in ("fp8", "int8")
    if quant:
        sc = ParamDef(
            (L, n_blocks, Hkv, 1, block_size),
            ("layers", "kv_blocks", "kv_heads", None, "kv_seq"),
            "zeros",
        )
        defs["k_scale"] = sc
        defs["v_scale"] = sc
    if host_blocks > 0:
        hkv = ParamDef(
            (L, host_blocks + 1, Hkv, block_size, Dh),
            ("layers", None, "kv_heads", "kv_seq", "head_dim"),
            "zeros",
        )
        defs["host_k"] = hkv
        defs["host_v"] = hkv
        defs["host_tables"] = ParamDef(
            (n_slots, max_blocks), ("kv_batch", None), "zeros"
        )
        defs["cold_lengths"] = ParamDef((n_slots,), ("kv_batch",), "zeros")
        if quant:
            hsc = ParamDef(
                (L, host_blocks + 1, Hkv, 1, block_size),
                ("layers", None, "kv_heads", None, "kv_seq"),
                "zeros",
            )
            defs["host_k_scale"] = hsc
            defs["host_v_scale"] = hsc
    return defs


# kv_dtype name -> pool storage dtype (scales are always f32)
PAGED_KV_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
    "int8": jnp.int8,
}


def _kv_dtype_name(dtype) -> str | None:
    """Storage dtype -> quantization name (None = unquantized)."""
    if dtype == jnp.int8:
        return "int8"
    if dtype == jnp.float8_e4m3fn:
        return "fp8"
    return None


def init_paged_cache(
    cfg, n_slots: int, n_blocks: int, block_size: int, max_blocks: int,
    dtype=jnp.bfloat16, kv_dtype: str = "bf16", host_blocks: int = 0,
) -> Pytree:
    if cfg.kv_quant and kv_dtype == "bf16":
        kv_dtype = "int8"           # cfg-level quant maps onto the int8 tier
    defs = paged_cache_defs(cfg, n_slots, n_blocks, block_size, max_blocks,
                            kv_dtype=kv_dtype, host_blocks=host_blocks)
    pool_dt = PAGED_KV_DTYPES[kv_dtype] if kv_dtype != "bf16" else dtype
    dt = {
        "block_tables": jnp.int32, "lengths": jnp.int32,
        "host_tables": jnp.int32, "cold_lengths": jnp.int32,
        "k": pool_dt, "v": pool_dt, "host_k": pool_dt, "host_v": pool_dt,
        "k_scale": jnp.float32, "v_scale": jnp.float32,
        "host_k_scale": jnp.float32, "host_v_scale": jnp.float32,
    }
    return {k: jnp.zeros(d.shape, dt.get(k, dtype)) for k, d in defs.items()}


def paged_decode_step(cfg, env: Env, params, cache, tokens):
    """One autoregressive step against the paged pool.

    Identical math to ``decode_step``; only the KV addressing differs:
    the new token's K/V scatter to ``(tables[b, len//bs], len % bs)`` and
    attention gathers each sequence's blocks through its table.  Inactive
    slots (length 0, table all-null) write to the null block and their
    logits are ignored by the engine.

    The cache pytree's own leaves select the tier statically at trace
    time: a quantized pool (int8/fp8 ``k`` with ``k_scale``) appends
    quantized and dequantizes inside the kernel; a host tier (``host_k``
    present) runs HGCA-style hybrid attention — the device kernel over
    the hot window ``[cold_len, len]``, the host/oracle path over the
    spilled cold prefix ``[0, cold_len)``, merged by log-sum-exp — so a
    spilled sequence keeps decoding without a re-prefill.
    """
    lengths = cache["lengths"]          # (B,) current KV counts
    tables = cache["block_tables"]      # (B, max_blocks) int32
    bs = cache["k"].shape[3]
    B = tokens.shape[0]
    quant = _kv_dtype_name(cache["k"].dtype)     # None | "fp8" | "int8"
    hosted = "host_k" in cache
    cold = cache["cold_lengths"] if hosted else None
    x = cm.embed_lookup(params["embed"], tokens)  # (B, D)
    pos = lengths[:, None]
    bidx = jnp.arange(B)
    phys = tables[bidx, lengths // bs]  # (B,) physical append block
    off = lengths % bs

    def scan_body(xc, xs):
        p = xs["p"]
        k_l, v_l = xs["k"], xs["v"]     # (n_blocks, Hkv, bs, Dh)
        h = cm.rmsnorm(xc, p["ln1"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, p["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, p["wv"])
        q = cm.rope(q[:, None], pos, cfg.rope_theta)[:, 0]
        k = cm.rope(k[:, None], pos, cfg.rope_theta)[:, 0]
        # advanced indices (phys, off) straddle the head slice, so the
        # selected (B, Hkv, Dh) lands batch-first — matching k/v directly
        ks_l = vs_l = None
        if quant:
            kq, ksc = ref.kv_quantize(k, quant)
            vq, vsc = ref.kv_quantize(v, quant)
            ks_l = xs["ks"].at[phys, :, 0, off].set(ksc)
            vs_l = xs["vs"].at[phys, :, 0, off].set(vsc)
            k_l = k_l.at[phys, :, off].set(kq)
            v_l = v_l.at[phys, :, off].set(vq)
        else:
            k_l = k_l.at[phys, :, off].set(k.astype(k_l.dtype))
            v_l = v_l.at[phys, :, off].set(v.astype(v_l.dtype))
        if hosted:
            # hybrid: device kernel over the hot window, host/oracle path
            # over the cold prefix, exact log-sum-exp merge
            o, lse_h = offload.paged_decode_attention(
                env, q, k_l, v_l, tables, lengths + 1, starts=cold,
                k_scale=ks_l, v_scale=vs_l, return_lse=True,
            )
            o_c, lse_c = ref.paged_decode_attention(
                q, xs["hk"], xs["hv"], cache["host_tables"], cold,
                k_scale=xs.get("hks"), v_scale=xs.get("hvs"),
                return_lse=True,
            )
            o = ref.lse_merge([(o, lse_h), (o_c, lse_c)])
        else:
            o = offload.paged_decode_attention(
                env, q, k_l, v_l, tables, lengths + 1,
                k_scale=ks_l, v_scale=vs_l,
            )
        xc = xc + jnp.einsum("bhk,hkd->bd", o, p["wo"])
        h = cm.rmsnorm(xc, p["ln2"], cfg.norm_eps)
        xc = xc + cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        ys = {"k": k_l, "v": v_l}
        if quant:
            ys |= {"ks": ks_l, "vs": vs_l}
        return xc, ys

    xs = {"p": params["blocks"], "k": cache["k"], "v": cache["v"]}
    if quant:
        xs |= {"ks": cache["k_scale"], "vs": cache["v_scale"]}
    if hosted:
        xs |= {"hk": cache["host_k"], "hv": cache["host_v"]}
        if quant:
            xs |= {"hks": cache["host_k_scale"], "hvs": cache["host_v_scale"]}
    x, ys = jax.lax.scan(scan_body, x, xs)
    x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = cm.unembed(x, _unembed_table(params), cfg.vocab)
    new_cache = dict(cache)
    new_cache |= {"k": ys["k"], "v": ys["v"], "lengths": lengths + 1}
    if quant:
        new_cache |= {"k_scale": ys["ks"], "v_scale": ys["vs"]}
    return logits, new_cache


# ---------------------------------------------------------------------------
# int8 KV quantization (beyond-paper: 2x cache capacity — the paper's
# scalability axis §VI-B — at ~1e-2 relative attention error)
# ---------------------------------------------------------------------------
def _kv_quantize(x: jax.Array):
    """x (..., Dh) -> (int8 values, per-vector scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _kv_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    # on TPU this convert-multiply fuses into the attention dot's operand
    # read; the resident cache stays int8 (capacity win is in the args)
    return (q.astype(jnp.bfloat16) * scale[..., None].astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------
def prefill(cfg, env: Env, params, tokens, cache, embeds=None):
    """Fill the cache with S context tokens; return last-position logits.

    With a frontend, the prepended embeds also occupy cache positions (the
    KV cache covers the full multimodal prefix).
    """
    x = cm.embed_lookup(params["embed"], tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    quant = cfg.kv_quant

    def scan_body(xc, xs):
        if quant:
            p, k_l, v_l, ks_l, vs_l = xs
        else:
            p, k_l, v_l = xs
        h = cm.rmsnorm(xc, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h)
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
        o = offload.prefill_attention(env, q, k, v)
        xc = xc + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        h = cm.rmsnorm(xc, p["ln2"], cfg.norm_eps)
        xc = xc + cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        if quant:
            kq, ksc = _kv_quantize(k)
            vq, vsc = _kv_quantize(v)
            k_l = jax.lax.dynamic_update_slice(k_l, kq, (0, 0, 0, 0))
            v_l = jax.lax.dynamic_update_slice(v_l, vq, (0, 0, 0, 0))
            ks_l = jax.lax.dynamic_update_slice(ks_l, ksc, (0, 0, 0))
            vs_l = jax.lax.dynamic_update_slice(vs_l, vsc, (0, 0, 0))
            return xc, (k_l, v_l, ks_l, vs_l)
        k_l = jax.lax.dynamic_update_slice(k_l, k.astype(k_l.dtype), (0, 0, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v.astype(v_l.dtype), (0, 0, 0, 0))
        if env.axes:
            k_l, v_l = offload.constrain_cache(env, k_l, v_l)
        return xc, (k_l, v_l)

    if quant:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            scan_body, x,
            (params["blocks"], cache["k"], cache["v"], cache["k_scale"], cache["v_scale"]),
        )
    else:
        x, (k_new, v_new) = jax.lax.scan(
            scan_body, x, (params["blocks"], cache["k"], cache["v"])
        )
    x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = cm.unembed(x[:, -1], _unembed_table(params), cfg.vocab)
    new_cache = {
        "k": k_new,
        "v": v_new,
        "lengths": jnp.full((B,), S, jnp.int32),
    }
    if quant:
        new_cache["k_scale"] = ks_new
        new_cache["v_scale"] = vs_new
    return logits, new_cache


# ---------------------------------------------------------------------------
# chunked prefill (Sarathi-style continuation; serving/scheduler.py)
# ---------------------------------------------------------------------------
def prefill_step(cfg, env: Env, params, cache, tokens, slot, q_offset, n_valid):
    """Prefill continuation: one chunk of one slot's prompt against the
    live cache.

    ``tokens`` (1, C) is the chunk padded to a bucket size; ``slot``,
    ``q_offset`` and ``n_valid`` are traced scalars (no recompile across
    slots/offsets/prompt lengths — only the bucket C is a shape).  The
    chunk's K/V are written at absolute positions ``q_offset ..
    q_offset+C-1`` of ``slot``'s cache stripe (out-of-range pad positions
    drop; in-range pad garbage is causally masked and overwritten by the
    next chunk or decode append), attention runs at ``q_offset`` against
    the stripe, and the slot length becomes ``q_offset + n_valid``.
    Returns next-token logits (1, V) at chunk position ``n_valid - 1``
    and the updated cache.  This is the GEMM-shaped half of a fused
    hybrid step: one weight stream serves it and the GEMV-shaped decode
    batch together (the paper's co-processing, on one mesh).
    """
    if cfg.kv_quant:
        raise NotImplementedError("chunked prefill does not support kv_quant yet")
    C = tokens.shape[1]
    x = cm.embed_lookup(params["embed"], tokens)                  # (1, C, D)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    positions = q_offset + jnp.arange(C, dtype=jnp.int32)[None]   # (1, C)

    def scan_body(xc, xs):
        p, k_l, v_l = xs                   # k_l/v_l (B, S, Hkv, Dh)
        h = cm.rmsnorm(xc, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h)
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
        k_l = k_l.at[slot, positions[0]].set(k[0].astype(k_l.dtype))
        v_l = v_l.at[slot, positions[0]].set(v[0].astype(v_l.dtype))
        k_row = jax.lax.dynamic_index_in_dim(k_l, slot, axis=0, keepdims=True)
        v_row = jax.lax.dynamic_index_in_dim(v_l, slot, axis=0, keepdims=True)
        o = offload.prefill_attention(env, q, k_row, v_row, q_offset=q_offset)
        xc = xc + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        h = cm.rmsnorm(xc, p["ln2"], cfg.norm_eps)
        xc = xc + cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        if env.axes:
            k_l, v_l = offload.constrain_cache(env, k_l, v_l)
        return xc, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(
        scan_body, x, (params["blocks"], cache["k"], cache["v"])
    )
    x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # unembed only the last valid position (the chunk's next-token logits)
    h_last = jax.lax.dynamic_slice(
        x, (jnp.int32(0), jnp.asarray(n_valid, jnp.int32) - 1, jnp.int32(0)),
        (1, 1, x.shape[-1]),
    )[:, 0]
    logits = cm.unembed(h_last, _unembed_table(params), cfg.vocab)
    lengths = cache["lengths"].at[slot].set(q_offset + jnp.asarray(n_valid, jnp.int32))
    return logits, {"k": k_new, "v": v_new, "lengths": lengths}


# ---------------------------------------------------------------------------
# speculative verify (draft-verify decoding; serving/engine.py)
# ---------------------------------------------------------------------------
def verify_step(cfg, env: Env, params, cache, tokens):
    """Score T speculative tokens per slot against the live cache in one
    dispatched program.

    ``tokens`` (B, T) are each slot's next inputs ``[t0, d_1 .. d_{T-1}]``
    (the fed-back token followed by draft proposals); input ``t`` of slot
    ``b`` lands at absolute cache position ``lengths[b] + t``.  K/V for
    all T positions are written unconditionally — rejected tails are
    garbage *past* the committed length, causally invisible, and
    overwritten by whatever writes those positions next — and ``lengths``
    is returned unchanged: the caller commits the accepted prefix by
    setting ``lengths + n_accept + 1`` (the KV "rollback" is just not
    advancing past it).  Returns logits (B, T, V), position ``t`` scoring
    the successor of input ``t``, and the updated cache.

    Internally this is T statically-unrolled :func:`decode_step` passes
    — the *same* arithmetic, op for op, as non-speculative decoding —
    NOT one T-wide attention GEMM.  That choice is deliberate: greedy
    speculative serving must be token-identical to the plain engine, and
    a differently-shaped attention program (batched verify vs per-token
    decode) rounds bf16 logits differently, flipping argmax near ties.
    The speculative win this repo measures is dispatch-count (one
    program, one host round-trip, one scheduler step per k+1 tokens);
    the weights still stream T times within the program.
    """
    if cfg.kv_quant:
        raise NotImplementedError("verify_step does not support kv_quant yet")
    lengths = cache["lengths"]
    T = tokens.shape[1]
    step = {"k": cache["k"], "v": cache["v"], "lengths": lengths}
    logits = []
    for t in range(T):
        lg, step = decode_step(cfg, env, params, step, tokens[:, t])
        logits.append(lg)
    return jnp.stack(logits, axis=1), {
        "k": step["k"], "v": step["v"], "lengths": lengths,
    }


def paged_verify_step(cfg, env: Env, params, cache, tokens):
    """Paged-pool analogue of :func:`verify_step`: T statically-unrolled
    :func:`paged_decode_step`-equivalent passes in one dispatched program
    — the same arithmetic, op for op, as non-speculative paged decoding
    (see :func:`verify_step` for why bitwise-identical decode math is
    load-bearing for greedy serving).

    One addressing difference from the plain decode body: a position past
    the block table (speculative overshoot at the cache edge) is routed
    to the null block 0, the pool's designated garbage sink.  Plain
    decode can never append out of table (the engine finishes or preempts
    first), but a verify window writes k+1 positions ahead of the
    committed length, so the edge is reachable and a clamped gather would
    otherwise silently corrupt a live block.  Quantized pools and the
    host tier are not supported under speculation (the engine validates).
    """
    if _kv_dtype_name(cache["k"].dtype):
        raise NotImplementedError("paged_verify_step: quantized pools unsupported")
    if "host_k" in cache:
        raise NotImplementedError("paged_verify_step: host KV tier unsupported")
    lengths0 = cache["lengths"]         # (B,)
    tables = cache["block_tables"]      # (B, max_blocks) int32
    bs = cache["k"].shape[3]
    max_blocks = tables.shape[1]
    B, T = tokens.shape
    bidx = jnp.arange(B)
    k_pool, v_pool = cache["k"], cache["v"]
    lengths = lengths0
    logits = []
    for t in range(T):
        x = cm.embed_lookup(params["embed"], tokens[:, t])  # (B, D)
        pos = lengths[:, None]
        blk = lengths // bs
        phys = jnp.where(blk < max_blocks,
                         tables[bidx, jnp.minimum(blk, max_blocks - 1)], 0)
        off = lengths % bs

        def scan_body(xc, xs, pos=pos, phys=phys, off=off, lengths=lengths):
            p, k_l, v_l = xs            # pools (n_blocks, Hkv, bs, Dh)
            h = cm.rmsnorm(xc, p["ln1"], cfg.norm_eps)
            q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
            k = jnp.einsum("bd,dhk->bhk", h, p["wk"])
            v = jnp.einsum("bd,dhk->bhk", h, p["wv"])
            q = cm.rope(q[:, None], pos, cfg.rope_theta)[:, 0]
            k = cm.rope(k[:, None], pos, cfg.rope_theta)[:, 0]
            # advanced indices (phys, off) straddle the head slice, so
            # the selected (B, Hkv, Dh) lands batch-first — matching k/v
            k_l = k_l.at[phys, :, off].set(k.astype(k_l.dtype))
            v_l = v_l.at[phys, :, off].set(v.astype(v_l.dtype))
            o = offload.paged_decode_attention(
                env, q, k_l, v_l, tables, lengths + 1
            )
            xc = xc + jnp.einsum("bhk,hkd->bd", o, p["wo"])
            h = cm.rmsnorm(xc, p["ln2"], cfg.norm_eps)
            xc = xc + cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
            return xc, (k_l, v_l)

        x, (k_pool, v_pool) = jax.lax.scan(
            scan_body, x, (params["blocks"], k_pool, v_pool)
        )
        x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits.append(cm.unembed(x, _unembed_table(params), cfg.vocab))
        lengths = lengths + 1
    new_cache = dict(cache)
    new_cache |= {"k": k_pool, "v": v_pool, "lengths": lengths0}
    return jnp.stack(logits, axis=1), new_cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_step(cfg, env: Env, params, cache, tokens):
    """One autoregressive step.  tokens (B,) -> logits (B, V), updated cache."""
    lengths = cache["lengths"]  # (B,) current counts; new token at index lengths
    B = tokens.shape[0]
    x = cm.embed_lookup(params["embed"], tokens)  # (B, D)
    pos = lengths[:, None]  # (B, 1)
    bidx = jnp.arange(B)

    quant = cfg.kv_quant

    def scan_body(xc, xs):
        if quant:
            p, k_l, v_l, ks_l, vs_l = xs
        else:
            p, k_l, v_l = xs
        h = cm.rmsnorm(xc, p["ln1"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, p["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, p["wv"])
        q = cm.rope(q[:, None], pos, cfg.rope_theta)[:, 0]
        k = cm.rope(k[:, None], pos, cfg.rope_theta)[:, 0]
        if quant:
            kq, ksc = _kv_quantize(k)
            vq, vsc = _kv_quantize(v)
            k_l = k_l.at[bidx, lengths].set(kq)
            v_l = v_l.at[bidx, lengths].set(vq)
            ks_l = ks_l.at[bidx, lengths].set(ksc)
            vs_l = vs_l.at[bidx, lengths].set(vsc)
            o = offload.decode_attention(
                env, q, _kv_dequantize(k_l, ks_l), _kv_dequantize(v_l, vs_l),
                lengths + 1,
            )
        else:
            k_l = k_l.at[bidx, lengths].set(k.astype(k_l.dtype))
            v_l = v_l.at[bidx, lengths].set(v.astype(v_l.dtype))
            o = offload.decode_attention(env, q, k_l, v_l, lengths + 1)
        xc = xc + jnp.einsum("bhk,hkd->bd", o, p["wo"])
        h = cm.rmsnorm(xc, p["ln2"], cfg.norm_eps)
        xc = xc + cm.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        if quant:
            return xc, (k_l, v_l, ks_l, vs_l)
        return xc, (k_l, v_l)

    if quant:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            scan_body, x,
            (params["blocks"], cache["k"], cache["v"], cache["k_scale"], cache["v_scale"]),
        )
    else:
        x, (k_new, v_new) = jax.lax.scan(
            scan_body, x, (params["blocks"], cache["k"], cache["v"])
        )
    x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = cm.unembed(x, _unembed_table(params), cfg.vocab)
    new_cache = {"k": k_new, "v": v_new, "lengths": lengths + 1}
    if quant:
        new_cache["k_scale"] = ks_new
        new_cache["v_scale"] = vs_new
    return logits, new_cache


# ---------------------------------------------------------------------------
# sampled steps (async engine): tokens in -> sampled tokens out, on device
# ---------------------------------------------------------------------------
# The async engine never reads logits on the host: each step returns the
# sampled next-token ids plus a per-slot EOS hit flag, so only [batch]
# ints cross the host boundary and the next step's inputs can be fed back
# device-to-device (serving/engine.py dispatch-ahead pipeline).  ``rng``
# is a traced key (unused for greedy); ``eos_ids`` is a per-slot int32
# vector (-1 = never stops); ``sampler`` must be static under jit.

def decode_sample_step(cfg, env: Env, params, cache, tokens, rng, eos_ids, *, sampler):
    """One decode step with sampling fused: (tokens', eos_hit, cache)."""
    logits, cache = decode_step(cfg, env, params, cache, tokens)
    tok = sample_on_device(logits, rng, sampler)
    return tok, tok == eos_ids, cache


def paged_decode_sample_step(cfg, env: Env, params, cache, tokens, rng, eos_ids, *, sampler):
    """Paged-pool analogue of :func:`decode_sample_step`."""
    logits, cache = paged_decode_step(cfg, env, params, cache, tokens)
    tok = sample_on_device(logits, rng, sampler)
    return tok, tok == eos_ids, cache


def prefill_sample_step(cfg, env: Env, params, cache, tokens, slot, q_offset,
                        n_valid, rng, *, sampler):
    """Chunked-prefill continuation with the first generated token sampled
    on device: returns (token (1,), cache).  Only meaningful on a prompt's
    final chunk; earlier chunks' sampled token is dead and ignored."""
    logits, cache = prefill_step(cfg, env, params, cache, tokens, slot, q_offset, n_valid)
    return sample_on_device(logits, rng, sampler), cache
