"""KV-cache partitioning policies (paper Fig. 4) + activation sharding rules.

The paper distributes the KV cache across HPU cards in two ways:

  * **batch-parallel** (paper-preferred): each HPU owns whole sequences
    (all heads) for a slice of the batch; results merge contiguously.
  * **head-parallel**: each HPU owns a slice of the heads for the whole
    batch; merging interleaves per-head vectors (host-side overhead in the
    prototype).

On the TPU mesh we add a third, beyond-paper policy:

  * **sequence-parallel** ("flash-decoding" style): the cache is sharded
    along the sequence axis; partial softmax statistics are merged with a
    log-sum-exp combine (GSPMD inserts the small all-reduces).  This is
    the only policy whose shardable dimension is guaranteed divisible for
    every architecture (S >> #chips), so the balancer falls back to it.

A policy is a rules dict mapping *logical* axes of cache/boundary tensors
to mesh axes; ``repro.models.common.resolve_spec`` drops mesh axes that
would over-pad.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

from jax.sharding import PartitionSpec as P

from repro.models.common import resolve_spec

POLICIES = ("batch", "head", "sequence", "batch_seq", "none")

# logical axes used by caches / boundary tensors
KV_CACHE_AXES = ("kv_batch", "kv_seq", "kv_heads", "head_dim")
# paged pool leaves (kernel-native layout, heads before positions): the
# physical block axis replaces the batch axis as the unit the HPU lanes
# split (a block belongs to exactly one lane)
PAGED_KV_CACHE_AXES = ("kv_blocks", "kv_heads", "kv_seq", "head_dim")


def kv_rules(policy: str) -> dict[str, tuple[str, ...]]:
    if policy == "batch":
        return {
            "kv_batch": ("pod", "data"),
            "kv_blocks": ("pod", "data"),  # paged pool: blocks across HPU lanes
            "kv_heads": ("model",),
            "kv_seq": (),
            "head_dim": (),
            "state": ("model",),  # rwkv/mamba state channels
        }
    if policy == "head":
        return {
            "kv_batch": ("pod",),
            "kv_blocks": ("pod",),
            "kv_heads": ("data", "model"),
            "kv_seq": (),
            "head_dim": (),
            "state": ("data", "model"),
        }
    if policy == "sequence":
        return {
            "kv_batch": ("pod",),
            "kv_blocks": ("data", "model"),
            "kv_heads": (),
            "kv_seq": ("data", "model"),
            "head_dim": (),
            "state": ("data", "model"),
        }
    if policy == "batch_seq":
        # beyond-paper 2D policy: batch over (pod,data), sequence over
        # model.  The flash-decoding LSE combine then reduces a tensor that
        # is batch-sharded (16x smaller) over only the model group — §Perf
        # iteration 3 on the deepseek cell.
        return {
            "kv_batch": ("pod", "data"),
            "kv_blocks": ("pod", "data", "model"),
            "kv_seq": ("model",),
            "kv_heads": (),
            "head_dim": (),
            "state": ("model",),
        }
    if policy == "none":
        return {
            "kv_batch": ("pod", "data"),
            "kv_blocks": (),
            "kv_heads": (),
            "kv_seq": (),
            "head_dim": (),
            "state": (),
        }
    raise ValueError(f"unknown kv policy {policy!r}")


def activation_rules(sequence_parallel: bool = False) -> dict[str, tuple[str, ...]]:
    """Sharding rules for the compute (GPU-analogue) side: TP over `model`,
    DP over `pod`+`data`; optional sequence-parallel on the seq axis."""
    return {
        "batch": ("pod", "data"),
        "seq": ("model",) if sequence_parallel else (),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "embed": (),
        "head_dim": (),
        "layers": (),
        "state": (),
        # training-side cache axes (unused) map like activations
        "kv_batch": ("pod", "data"),
        "kv_seq": (),
    }


def param_rules(sequence_parallel: bool = False, fsdp: bool = False) -> dict[str, tuple[str, ...]]:
    """Weight sharding: TP over `model`; with ``fsdp`` the d_model axis of
    every weight is additionally sharded over (`pod`,`data`) (ZeRO-3 —
    GSPMD all-gathers per scanned layer)."""
    rules = dict(activation_rules(sequence_parallel))
    rules["embed"] = ("pod", "data") if fsdp else ()
    rules["batch"] = ()  # weights have no batch axis; guard misuse
    return rules


@dataclass(frozen=True)
class Env:
    """Everything the model code needs to know about the runtime context.

    ``axes`` is ``{mesh_axis_name: size}`` (empty dict = single device, no
    sharding constraints emitted).  ``mesh`` is the device mesh those axes
    name: serving passes it, so sharding constraints and the kernels'
    ``shard_map`` bind to it with no ambient mesh; lowering-only callers
    (the dry-run) may leave it ``None`` and lower inside ``with mesh:``.
    Threaded explicitly, so CPU unit tests and 512-device dry-runs share
    one code path.
    """
    axes: dict[str, int] = field(default_factory=dict)
    mesh: Any = None
    kv_policy: str = "batch"
    offload: str = "hpu"        # "hpu" | "none"
    sub_batches: int = 1
    sequence_parallel: bool = False
    fsdp: bool = False
    ep_wide: bool = False       # inference: experts over (data, model) — the
                                # DeepSeek deployment layout; tokens reach
                                # their expert shard via all-to-all
    bf16_combine: bool = False  # carry cross-shard attention LSE-combine
                                # partials in bf16 (halves wire bytes)
    moe_a2a: bool = False       # §Perf iter.4 (refuted on XLA:CPU: lowers
                                # to all-gather, not all-to-all; see
                                # EXPERIMENTS.md §Perf)

    def act_rules(self) -> dict[str, tuple[str, ...]]:
        rules = activation_rules(self.sequence_parallel)
        if self.ep_wide:
            rules = {**rules, "experts": ("pod", "data", "model")}
        return rules

    def param_rules(self) -> dict[str, tuple[str, ...]]:
        rules = param_rules(self.sequence_parallel, self.fsdp)
        if self.ep_wide:
            rules = {**rules, "experts": ("pod", "data", "model")}
        return rules

    def kv_spec(self, logical: tuple[str | None, ...], shape) -> P:
        policy = self.kv_policy if self.offload == "hpu" else "none"
        return resolve_spec(logical, kv_rules(policy), self.axes, tuple(shape))

    def act_spec(self, logical: tuple[str | None, ...], shape) -> P:
        return resolve_spec(logical, self.act_rules(), self.axes, tuple(shape))


def lanes(axes: dict[str, int]) -> int:
    """Number of 'HPU lanes' = chips the KV pool spans."""
    n = 1
    for v in axes.values():
        n *= v
    return n
