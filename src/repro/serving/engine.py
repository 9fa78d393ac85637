"""Continuous-batching serving engine with HPU-offloaded decode.

Slot-based continuous batching (Orca-style): a fixed decode batch of
``n_slots`` sequences; finished sequences free their slot and queued
requests are prefilled into it while decode keeps running for the rest —
this is what keeps the decode batch (and thus the offloaded-attention
bandwidth utilization the paper optimizes) high.

Two cache modes (``cache_kind``):

* ``"dense"`` — the seed baseline: every slot reserves a full
  ``max_seq`` stripe of KV, admission is gated on free *slots*.
* ``"paged"`` — physical KV is a :class:`~repro.serving.paged.BlockPool`
  of fixed-size blocks; admission is gated on free *blocks* (actual HPU
  memory), shared prompt prefixes share physical blocks (copy-on-write
  on first divergent append), and running out of blocks preempts the
  youngest sequence back to the queue — it re-prefills later from its
  prompt plus the tokens already generated, so greedy output is exact.

Two schedules (``schedule``; :mod:`repro.serving.scheduler`):

* ``"decode-only"`` — whole-prompt prefill at admission (one jit program
  per distinct prompt length), every model step is decode-only.
* ``"hybrid"`` — a token-budget :class:`Scheduler` packs each iteration
  as one decode token per active slot *plus* one bucket-padded chunk of
  the head-of-queue prompt, executed as a single fused model step: the
  chunk's GEMMs ride the decode batch's weight stream (the paper's
  GPU/HPU co-processing, expressed as one program on one mesh), and all
  jit shapes come from the scheduler's fixed bucket set.  Greedy outputs
  are token-identical to ``decode-only``.  Paged sequences admit
  partially — each chunk acquires only the blocks it needs.

Two execution modes (``async_mode``):

* ``async_mode=True`` (default) — the dispatch-ahead pipeline.  Every
  jit step samples **on device** and returns sampled token ids plus a
  per-slot EOS flag instead of logits, so the per-step host transfer is
  ``[batch]`` ints, and the token ids feed the next step device-to-device
  (``tok_state``).  The engine dispatches iteration *t+1* from *t*'s
  *planned* host state before *t*'s tokens are observed — JAX's async
  dispatch keeps the device busy through all host-side Python — then
  fetches *t*'s small token array in the background.  Length/max-new
  retirements are host-deterministic and gate dispatch exactly like the
  sync engine; EOS retirements are observed one step late, and the one
  speculative token dispatched past an EOS is masked (never emitted,
  its cache writes are reset with the slot).  Greedy outputs are
  token-identical to sync mode; temperature sampling is valid but
  consumes the rng stream in a different order.
* ``async_mode=False`` — the conservative synchronous fallback
  (``--async off``): block on each step's logits, sample on host.

Correctness of dispatch-ahead rests on device data-flow ordering: every
device op threads ``self.cache`` (and ``self.staging``/``tok_state``),
so host bookkeeping done at dispatch time (block flushes, table syncs,
resets) lands *after* the in-flight step's writes.  The one host action
that needs observed token values — preemption's exact-recovery refold —
observes only the victim slot's in-flight tokens first
(:meth:`Engine._observe_victim`), keeping the rest of the pipeline in
flight; the full drain is paid only when eviction is otherwise
imminent (an unobserved completion elsewhere may still avert it).

The decode step is wrapped by ``core.pipeline.pipelined_step`` when
``sub_batches > 1`` (paper Fig. 3), and attention runs through
``core.offload`` in the layout chosen by ``core.balance.plan``.

Step accounting: ``EngineStats.engine_steps`` counts fixed-shape model
dispatches; a decode-only whole prefill of ``L`` tokens counts
``ceil(L / prefill_chunk)`` steps (the hybrid-batch units it occupies),
so TTFT/throughput in steps are comparable across schedules.

Speculative multi-token decoding (``spec_depth=k`` with a draft model):
each decode dispatch becomes draft-then-verify — the small draft model
proposes ``k`` tokens autoregressively on device, the target model scores
all ``k+1`` positions in one fused ``verify_step`` (the chunked-prefill
``q_offset`` scoring path generalized to per-slot offsets), and
rejection sampling accepts a prefix of the drafts plus one
bonus/correction token.  The accepted prefix feeds back device-to-device
through the same ``tok_state`` plumbing; KV "rollback" is simply not
advancing ``lengths`` past the accepted prefix (garbage K/V beyond the
committed length is causally invisible and overwritten by later writes).
Greedy output is token-identical to non-speculative decoding;
temperature sampling matches the target distribution exactly (standard
rejection/residual sampling).  Speculation always runs on the
dispatch-ahead machinery — ``async_mode=False`` with ``spec_depth > 0``
collapses to a pipeline of depth zero (dispatch, then observe
immediately), which keeps one code path and stays greedy
token-identical.  A speculative dispatch carries ``k+1`` in-flight
token *charges* per slot (the router's load accounting sees the true
KV commitment upper bound) but only one guaranteed commit
(``in_flight_steps``), which is what dispatch prediction uses.

Cross-replica migration (disaggregated serving): a paged request whose
prefill just completed can leave this engine and continue decoding on
another — :meth:`Engine.preview_export` sizes the move without side
effects, :meth:`Engine.export_request` detaches the slot and returns a
``MigrationTicket`` (block payloads gathered in storage dtype, scale
pools included, shared-prefix blocks copied out so remaining owners
keep theirs), and :meth:`Engine.can_import` /
:meth:`Engine.import_request` admit it on the destination, deduping
against blocks already resident under the same chain hash.  The
cluster drives this; a refused import simply decodes in place.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.pipeline import pipelined_step
from repro.models.registry import Model
from repro.serving import kv_cache
from repro.serving.paged import BlockPool, PagedCacheManager
from repro.serving.paged import device as paged_dev
from repro.serving.sampler import (
    SamplerConfig,
    sample,
    sample_on_device,
    spec_draft_sample,
    spec_verify_tokens,
)
from repro.serving.scheduler import PrefillChunk, Scheduler
from repro.serving.telemetry import (
    NULL_PROFILER,
    NULL_TRACER,
    DispatchCostModel,
    StepRecord,
    percentile,
)

Pytree = Any


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int
    eos_id: int = -1                # -1: never stops early
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # latency accounting, in engine steps (-1 = not reached yet)
    submit_step: int = 0
    admit_step: int = -1
    first_token_step: int = -1
    finish_step: int = -1
    # async engine bookkeeping.  One dispatched step carries one token
    # charge normally; a speculative step carries spec_depth+1 charges
    # (the commit upper bound, what KV/load accounting must cover) but
    # guarantees only one commit — in_flight_steps counts the guaranteed
    # floor, which is what dispatch prediction may rely on.
    in_flight: int = 0              # token charges dispatched, not observed
    in_flight_steps: int = 0        # dispatched steps (>= 1 commit each)
    admit_base: int = 0             # len(out_tokens) at last (re-)admission


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0               # completed request prefills
    prefill_chunks: int = 0         # hybrid: chunks executed
    boundary_packs: int = 0         # hybrid: head chunks packed at a boundary
    decode_steps: int = 0           # model steps that carried a decode batch
    engine_steps: int = 0           # normalized step clock (see module doc)
    generated: int = 0
    peak_active: int = 0
    preemptions: int = 0
    victim_drains: int = 0          # async: partial (victim-only) drains
    spills: int = 0                 # KV blocks copied device -> host tier
    rehydrations: int = 0           # KV blocks copied host tier -> device
    migrations_out: int = 0         # resident requests exported to a peer
    migrations_in: int = 0          # resident requests imported from a peer
    spec_steps: int = 0             # speculative draft-verify dispatches
    draft_steps: int = 0            # draft-model steps (decode + prefill chunks)
    drafted_tokens: int = 0         # draft proposals consumed by verification
                                    # (windows masked past a finish don't count)
    accepted_tokens: int = 0        # draft proposals accepted
    ttft_steps_sum: int = 0
    ttft_count: int = 0
    # raw per-request samples (ttft: submit->first-token in engine steps;
    # per_token: decode steps per generated token after the first) so
    # percentiles are exact, not reconstructed from sums
    ttft_samples: list[int] = dataclasses.field(default_factory=list)
    per_token_samples: list[float] = dataclasses.field(default_factory=list)
    # per-observed-window acceptance fractions (accepted / spec_depth)
    spec_accept_samples: list[float] = dataclasses.field(default_factory=list)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verifier accepted."""
        return self.accepted_tokens / max(self.drafted_tokens, 1)

    @property
    def mean_ttft_steps(self) -> float:
        """Mean submit->first-token latency, in engine steps."""
        return self.ttft_steps_sum / max(self.ttft_count, 1)

    @property
    def tokens_per_step(self) -> float:
        return self.generated / max(self.engine_steps, 1)

    def ttft_percentile(self, p: float) -> float:
        """Exact nearest-rank TTFT percentile over per-request samples."""
        return percentile(self.ttft_samples, p)

    @property
    def ttft_p50_steps(self) -> float:
        return self.ttft_percentile(50)

    @property
    def ttft_p99_steps(self) -> float:
        return self.ttft_percentile(99)

    def per_token_percentile(self, p: float) -> float:
        return percentile(self.per_token_samples, p)


@dataclasses.dataclass
class EngineLoad:
    """One replica's load snapshot, read by the cluster router.

    ``inflight_tokens`` counts KV positions committed to this replica —
    prompt plus generated (observed and dispatched) tokens of every
    resident request, plus the prompt tokens of anything waiting in the
    local queue (a preempted request is still this replica's work).
    """

    free_slots: int
    queued: int
    inflight_tokens: int
    free_blocks: int | None         # paged only; None for the dense cache


@dataclasses.dataclass
class MigrationTicket:
    """Host-side description of an exported resident request's KV.

    ``keys`` is the paged hash-key chain aligned with the payload's block
    columns (None entries are diverged tails / decode headroom); the
    dense cache has no keys (``None``) and its payload is a batch-1
    sub-cache.  ``length`` is the KV positions held (prompt + observed
    output - 1: the last sampled token is the next step's *input*).
    """

    length: int
    kv_dtype: str
    keys: list | None = None         # paged: per-block hash chain
    n_blocks: int = 0                # paged: payload block count
    block_size: int = 0              # paged: source pool block granularity
    src_step: int = 0                # source engine-step clock at export


@dataclasses.dataclass
class _PendingStep:
    """One dispatched-but-unobserved model step (async pipeline).

    ``reqs`` pins the requests that were in the decode batch at dispatch
    — a slot may be retired and re-admitted to a different request
    before this record is observed, so slot indices alone are not
    enough.  ``tokens``/``eos`` are in-flight device arrays; fetching
    them blocks only until *this* step finishes while later steps keep
    the device busy.
    """

    step: int                            # engine_steps value at dispatch
    reqs: dict[int, Request]             # slot -> request in decode batch
    tokens: jax.Array | None             # (B,) sampled ids (device)
    eos: jax.Array | None                # (B,) bool EOS hits (device)
    work: PrefillChunk | None = None     # chunk fused into this step
    pre_tok: jax.Array | None = None     # (1,) first token when work.last
    work2: PrefillChunk | None = None    # boundary-packed second chunk
    pre_tok2: jax.Array | None = None    # (1,) first token when work2.last
    # speculative dispatch: tokens is (B, k+1) emitted rows, eos is None
    # (EOS is found host-side while walking the accepted prefix), and
    # each decode-batch request carried `charge` in-flight token charges
    n_accept: jax.Array | None = None    # (B,) accepted-draft counts (device)
    charge: int = 1                      # in-flight charges per batch slot


def _step_program(kind: str, fn, **jit_kw):
    """Jit one step program as ``step_<kind>`` (``kind`` as in
    ``StepRecord.kind``), so the device trace's program line reads
    ``jit_step_<kind>`` whichever model entry point or wrapper is behind
    it."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = f"step_{kind}"
    return jax.jit(program, **jit_kw)


class Engine:
    def __init__(
        self,
        model: Model,
        params: Pytree,
        n_slots: int,
        max_seq: int,
        sampler: SamplerConfig = SamplerConfig(),
        sub_batches: int = 1,
        rng: jax.Array | None = None,
        cache_kind: str = "dense",
        block_size: int = 16,
        n_blocks: int | None = None,
        kv_dtype: str = "bf16",
        host_blocks: int = 0,
        schedule: str = "decode-only",
        prefill_chunk: int = 32,
        token_budget: int | None = None,
        async_mode: bool = True,
        spec_depth: int = 0,
        draft_model: Model | None = None,
        draft_params: Pytree | None = None,
        tracer=None,
        profiler=None,
        replica: int = 0,
        role: str = "mixed",
    ):
        self.model = model
        # a model built on a mesh serves from it: weights, pools and
        # staging are committed to its devices per the placement rules
        # (a cluster replica's mesh is its own slice of the host)
        self.mesh = model.env.mesh
        self.params = self.place(params, model.param_specs())
        self.max_seq = max_seq
        self.sampler = sampler
        self.cache_kind = cache_kind
        self.schedule = schedule
        self.prefill_chunk = prefill_chunk
        self.async_mode = async_mode
        # speculative decoding always runs on the dispatch-ahead machinery;
        # --async off collapses to a pipeline of depth zero (dispatch, then
        # observe immediately) so there is exactly one speculative code
        # path and it stays greedy token-identical to the sync engine
        if spec_depth < 0:
            raise ValueError(f"spec_depth must be >= 0, got {spec_depth}")
        self.spec_depth = spec_depth
        self.draft_model = draft_model
        self.draft_params = draft_params
        self._sync_pipeline = False
        if spec_depth:
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "spec_depth > 0 needs a draft_model and draft_params"
                )
            if sub_batches != 1:
                raise NotImplementedError(
                    "speculative decoding does not compose with sub-batch "
                    "pipelining yet"
                )
            if model.cfg.kv_quant:
                raise NotImplementedError(
                    "speculative decoding does not support kv_quant yet"
                )
            if (model.paged_verify_step if cache_kind == "paged"
                    else model.verify_step) is None:
                raise ValueError(
                    f"{model.cfg.family} has no verify_step: speculative "
                    "decoding needs the multi-position scoring entry point"
                )
            if draft_model.prefill_step is None:
                raise ValueError(
                    f"draft family {draft_model.cfg.family} has no "
                    "prefill_step: the draft cache is filled chunk-wise"
                )
            if draft_model.cfg.vocab != model.cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab} != target vocab "
                    f"{model.cfg.vocab}: rejection sampling needs one "
                    "token space"
                )
            if cache_kind == "paged" and (kv_dtype != "bf16" or host_blocks):
                raise NotImplementedError(
                    "speculative verification reads the bf16 device pool "
                    "only (no quantized kv_dtype / host tier yet)"
                )
            self._sync_pipeline = not async_mode
            self.async_mode = async_mode = True
        # disaggregated serving: the role is *advisory* routing metadata
        # (the cluster admits prompts to prefill/mixed replicas and
        # migrates finished prefills off "prefill" replicas) — the engine
        # itself always handles both phases, so a migration that finds no
        # destination degrades gracefully to decoding in place
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(f"unknown role {role!r}")
        self.role = role
        self.slots: list[Request | None] = [None] * n_slots
        self.stats = EngineStats()
        self.rng = rng if rng is not None else jax.random.key(0)
        # telemetry: NULL_TRACER / NULL_PROFILER hooks are no-ops, and
        # `_telemetry` gates the per-dispatch StepRecord construction so a
        # disabled run does no extra host work at all; the tracer records
        # at dispatch/observe boundaries — never inside jit-traced code —
        # and only the profiler's explicitly sampled dispatches fence
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.profiler = NULL_PROFILER if profiler is None else profiler
        self._telemetry = self.tracer.enabled or self.profiler.enabled
        self.replica = replica
        self._cost_model = (
            DispatchCostModel(model.cfg) if self._telemetry else None
        )

        self._prefill = _step_program("prefill", model.prefill)
        if cache_kind != "paged" and (kv_dtype != "bf16" or host_blocks):
            raise ValueError(
                "kv_dtype / host_blocks are paged-cache features "
                f"(cache_kind={cache_kind!r})"
            )
        self.kv_dtype = kv_dtype
        self.host_blocks = host_blocks
        if cache_kind == "paged":
            if model.paged_decode_step is None:
                raise ValueError(f"{model.cfg.family} has no paged decode path")
            if sub_batches != 1:
                raise NotImplementedError(
                    "paged cache does not compose with sub-batch pipelining yet"
                )
            self.block_size = block_size
            self.max_blocks = -(-max_seq // block_size)
            # default: same physical budget as the dense cache, + null block
            self.n_blocks = (
                n_slots * self.max_blocks + 1 if n_blocks is None else n_blocks
            )
            if self.n_blocks - 1 < self.max_blocks:
                raise ValueError(
                    f"pool of {self.n_blocks - 1} usable blocks cannot hold one "
                    f"max_seq={max_seq} sequence ({self.max_blocks} blocks)"
                )
            self.pool = BlockPool(self.n_blocks, block_size, host_blocks=host_blocks)
            self.manager = PagedCacheManager(self.pool, n_slots, self.max_blocks)
            pool_kw = dict(kv_dtype=kv_dtype, host_blocks=host_blocks)
            shape = (n_slots, self.n_blocks, block_size, self.max_blocks)
            self.cache = self.place(
                model.init_paged_cache(*shape, **pool_kw),
                model.paged_cache_specs(*shape, **pool_kw),
            )
            self._decode = _step_program("decode", model.paged_decode_step)
            if async_mode:
                if model.paged_decode_sample_step is not None:
                    self._decode_sampled = _step_program(
                        "decode", model.paged_decode_sample_step,
                        static_argnames=("sampler",),
                    )
                else:
                    self._decode_sampled = self._wrap_sampled(model.paged_decode_step)
        elif cache_kind == "dense":
            self.cache = self.place(model.init_cache(n_slots, max_seq),
                                    model.cache_specs(n_slots, max_seq))
            step = pipelined_step(model.decode_step, sub_batches)
            self._decode = _step_program("decode", step)
            if async_mode:
                if sub_batches == 1 and model.decode_sample_step is not None:
                    self._decode_sampled = _step_program(
                        "decode", model.decode_sample_step,
                        static_argnames=("sampler",),
                    )
                else:
                    self._decode_sampled = self._wrap_sampled(step)
        else:
            raise ValueError(f"unknown cache_kind {cache_kind!r}")

        # async pipeline state (allocated in both modes so shared helpers
        # like _prepare_append can test `self._pending` unconditionally)
        self._pending: deque[_PendingStep] = deque()
        # admission prefills' first tokens, fetched with the step stream:
        # (request, device token, step id of the prefill)
        self._first_pending: list[tuple[Request, jax.Array, int]] = []
        self._dispatched: tuple[int, str] | None = None    # (step, kind)
        if async_mode:
            self._tok_state = jnp.zeros((n_slots,), jnp.int32)
            self._eos_dev = jnp.full((n_slots,), -1, jnp.int32)
            self._rng_zero = jax.random.key(0)
            self._jit_sample = jax.jit(sample_on_device, static_argnames=("cfg",))

        self.sched = Scheduler(
            n_slots=n_slots, max_seq=max_seq, mode=schedule,
            prefill_chunk=prefill_chunk, token_budget=token_budget,
            block_size=block_size if cache_kind == "paged" else None,
            spec_width=spec_depth + 1,
        )
        if schedule == "hybrid":
            self._init_hybrid(sub_batches)
        if spec_depth:
            # the draft cache is always dense: the draft model is small,
            # so one (n_slots, max_seq) stripe costs little, and its
            # lengths mirror the target's committed lengths slot-for-slot
            self.d_cache = draft_model.init_cache(n_slots, max_seq)
            self._draft_prefill = jax.jit(draft_model.prefill_step)
            self._init_spec()

    def place(self, tree: Pytree, specs: Pytree) -> Pytree:
        """Commit ``tree`` to this engine's mesh, leaf by leaf per
        ``specs`` (no-op without a mesh: the default device serves)."""
        if self.mesh is None:
            return tree
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.device_put(tree, shardings)

    @staticmethod
    def _wrap_sampled(base_step):
        """Fuse on-device sampling onto a logits step (used when the
        family has no *_sample_step, or the step is sub-batch pipelined)."""

        def _sampled(params, cache, tokens, rng, eos_ids, *, sampler):
            logits, new_cache = base_step(params, cache, tokens)
            tok = sample_on_device(logits, rng, sampler)
            return tok, tok == eos_ids, new_cache

        return _step_program("decode", _sampled, static_argnames=("sampler",))

    def _init_hybrid(self, sub_batches: int) -> None:
        model = self.model
        if model.prefill_step is None:
            raise ValueError(
                f"{model.cfg.family} has no prefill_step: hybrid scheduling "
                "needs the chunked-prefill model entry point"
            )
        if model.cfg.kv_quant:
            raise NotImplementedError("hybrid schedule does not support kv_quant yet")
        if sub_batches != 1:
            raise NotImplementedError(
                "hybrid schedule does not compose with sub-batch pipelining yet"
            )
        # per-slot chunked-prefill state (set by _begin_prefill): the
        # pinned token stream, prefix-cache-hit block count, and (paged)
        # the staging lane — boundary packing keeps TWO prompts mid-flight
        # for one dispatch, so none of this can be a single global
        self._pf_tokens: dict[int, np.ndarray] = {}
        self._pf_prefix: dict[int, int] = {}
        self._pf_lane: dict[int, int] = {}
        sampler = self.sampler
        if self.cache_kind == "paged":
            # persistent staging cache (one fixed shape): chunks accumulate
            # here, completed blocks flush into the pool.  Two lanes
            # (batch 2) so a boundary-packed second prompt can stage its
            # chunks while the finishing prompt still owns its lane.
            span = self.max_blocks * self.block_size
            self.staging = self.place(model.init_cache(2, span),
                                      model.cache_specs(2, span))

        if not self.async_mode:
            self._solo = _step_program("solo", model.prefill_step)
            if self.cache_kind == "paged":

                def _fused(params, cache, staging, dec_tokens, pre_tokens, lane, off, nv):
                    pre_logits, staging = model.prefill_step(
                        params, staging, pre_tokens, lane, off, nv
                    )
                    dec_logits, cache = model.paged_decode_step(params, cache, dec_tokens)
                    return dec_logits, pre_logits, cache, staging

                # boundary packing (Sarathi-SC), paged: prompt A's final
                # chunk and prompt B's head chunk stage in separate lanes
                # and ride one dispatch with the decode batch
                def _fused2(params, cache, staging, dec_tokens,
                            tokA, laneA, offA, nvA, tokB, laneB, offB, nvB):
                    la, staging = model.prefill_step(params, staging, tokA, laneA, offA, nvA)
                    lb, staging = model.prefill_step(params, staging, tokB, laneB, offB, nvB)
                    dec_logits, cache = model.paged_decode_step(params, cache, dec_tokens)
                    return dec_logits, la, lb, cache, staging

                def _solo2(params, staging, tokA, laneA, offA, nvA,
                           tokB, laneB, offB, nvB):
                    la, staging = model.prefill_step(params, staging, tokA, laneA, offA, nvA)
                    lb, staging = model.prefill_step(params, staging, tokB, laneB, offB, nvB)
                    return la, lb, staging

                self._fused2 = _step_program("fused2", _fused2)
                self._solo2 = _step_program("solo2", _solo2)
            else:

                def _fused(params, cache, dec_tokens, pre_tokens, slot, off, nv):
                    pre_logits, cache = model.prefill_step(
                        params, cache, pre_tokens, slot, off, nv
                    )
                    dec_logits, cache = model.decode_step(params, cache, dec_tokens)
                    # decode advanced every slot's length; the mid-prefill slot
                    # stays at its chunk end (its garbage append is overwritten
                    # by the next chunk / first decode token)
                    lengths = cache["lengths"].at[slot].set(off + nv)
                    return dec_logits, pre_logits, {**cache, "lengths": lengths}

                # boundary packing (Sarathi-SC): prompt A's final chunk and
                # prompt B's head chunk in ONE dispatch — both prefills ride
                # the same weight stream as the decode batch
                def _fused2(params, cache, dec_tokens, tokA, slotA, offA, nvA,
                            tokB, slotB, offB, nvB):
                    la, cache = model.prefill_step(params, cache, tokA, slotA, offA, nvA)
                    lb, cache = model.prefill_step(params, cache, tokB, slotB, offB, nvB)
                    dec_logits, cache = model.decode_step(params, cache, dec_tokens)
                    lengths = (cache["lengths"].at[slotA].set(offA + nvA)
                               .at[slotB].set(offB + nvB))
                    return dec_logits, la, lb, {**cache, "lengths": lengths}

                def _solo2(params, cache, tokA, slotA, offA, nvA,
                           tokB, slotB, offB, nvB):
                    la, cache = model.prefill_step(params, cache, tokA, slotA, offA, nvA)
                    lb, cache = model.prefill_step(params, cache, tokB, slotB, offB, nvB)
                    return la, lb, cache

                self._fused2 = _step_program("fused2", _fused2)
                self._solo2 = _step_program("solo2", _solo2)

            self._fused = _step_program("fused", _fused)
            return

        # ---- async closures: sampling fused, token state fed back on device.
        # The fused step returns sampled ids + EOS flags for the decode
        # batch and, on a prompt's final chunk, splices the chunk's first
        # generated token into tok_state at `slot` so the next decode step
        # consumes it without any host round-trip.
        if model.prefill_sample_step is not None:
            prefill_sample = model.prefill_sample_step
        else:
            def prefill_sample(params, cache, tokens, slot, off, nv, rng, *,
                               sampler):
                logits, cache = model.prefill_step(params, cache, tokens, slot, off, nv)
                return sample_on_device(logits, rng, sampler), cache

        if self.cache_kind == "paged":

            def _fused_async(params, cache, staging, tok_state, pre_tokens,
                             slot, lane, off, nv, rng, eos_ids, last):
                r_dec, r_pre = jax.random.split(rng)
                pre_logits, staging = model.prefill_step(
                    params, staging, pre_tokens, lane, off, nv
                )
                dec_logits, cache = model.paged_decode_step(params, cache, tok_state)
                toks = sample_on_device(dec_logits, r_dec, sampler)
                pre_tok = sample_on_device(pre_logits, r_pre, sampler)
                state = jnp.where(last, toks.at[slot].set(pre_tok[0]), toks)
                return state, toks, toks == eos_ids, pre_tok, cache, staging

            def _solo_async(params, staging, tok_state, pre_tokens,
                            slot, lane, off, nv, rng, last):
                pre_tok, staging = prefill_sample(
                    params, staging, pre_tokens, lane, off, nv, rng, sampler=sampler
                )
                state = jnp.where(last, tok_state.at[slot].set(pre_tok[0]), tok_state)
                return state, pre_tok, staging

            # boundary packing, paged async twins: two staging lanes, A
            # always completes (final by construction), B splices its
            # first token only when its head chunk is also its last
            def _fused2_async(params, cache, staging, tok_state,
                              tokA, slotA, laneA, offA, nvA,
                              tokB, slotB, laneB, offB, nvB,
                              rng, eos_ids, lastB):
                r_dec, r_a, r_b = jax.random.split(rng, 3)
                la, staging = model.prefill_step(params, staging, tokA, laneA, offA, nvA)
                lb, staging = model.prefill_step(params, staging, tokB, laneB, offB, nvB)
                dec_logits, cache = model.paged_decode_step(params, cache, tok_state)
                toks = sample_on_device(dec_logits, r_dec, sampler)
                ta = sample_on_device(la, r_a, sampler)
                tb = sample_on_device(lb, r_b, sampler)
                state = toks.at[slotA].set(ta[0])
                state = jnp.where(lastB, state.at[slotB].set(tb[0]), state)
                return state, toks, toks == eos_ids, ta, tb, cache, staging

            def _solo2_async(params, staging, tok_state,
                             tokA, slotA, laneA, offA, nvA,
                             tokB, slotB, laneB, offB, nvB, rng, lastB):
                r_a, r_b = jax.random.split(rng)
                la, staging = model.prefill_step(params, staging, tokA, laneA, offA, nvA)
                lb, staging = model.prefill_step(params, staging, tokB, laneB, offB, nvB)
                ta = sample_on_device(la, r_a, sampler)
                tb = sample_on_device(lb, r_b, sampler)
                state = tok_state.at[slotA].set(ta[0])
                state = jnp.where(lastB, state.at[slotB].set(tb[0]), state)
                return state, ta, tb, staging

            self._fused2 = _step_program("fused2", _fused2_async)
            self._solo2 = _step_program("solo2", _solo2_async)
        else:

            def _fused_async(params, cache, tok_state, pre_tokens,
                             slot, off, nv, rng, eos_ids, last):
                r_dec, r_pre = jax.random.split(rng)
                pre_logits, cache = model.prefill_step(
                    params, cache, pre_tokens, slot, off, nv
                )
                dec_logits, cache = model.decode_step(params, cache, tok_state)
                lengths = cache["lengths"].at[slot].set(off + nv)
                cache = {**cache, "lengths": lengths}
                toks = sample_on_device(dec_logits, r_dec, sampler)
                pre_tok = sample_on_device(pre_logits, r_pre, sampler)
                state = jnp.where(last, toks.at[slot].set(pre_tok[0]), toks)
                return state, toks, toks == eos_ids, pre_tok, cache

            def _solo_async(params, cache, tok_state, pre_tokens,
                            slot, off, nv, rng, last):
                pre_tok, cache = prefill_sample(
                    params, cache, pre_tokens, slot, off, nv, rng, sampler=sampler
                )
                state = jnp.where(last, tok_state.at[slot].set(pre_tok[0]), tok_state)
                return state, pre_tok, cache

            # boundary packing (Sarathi-SC), async twins: A always
            # completes (its chunk is final by construction), B's first
            # token splices only when its head chunk is also its last
            def _fused2_async(params, cache, tok_state, tokA, slotA, offA, nvA,
                              tokB, slotB, offB, nvB, rng, eos_ids, lastB):
                r_dec, r_a, r_b = jax.random.split(rng, 3)
                la, cache = model.prefill_step(params, cache, tokA, slotA, offA, nvA)
                lb, cache = model.prefill_step(params, cache, tokB, slotB, offB, nvB)
                dec_logits, cache = model.decode_step(params, cache, tok_state)
                lengths = (cache["lengths"].at[slotA].set(offA + nvA)
                           .at[slotB].set(offB + nvB))
                cache = {**cache, "lengths": lengths}
                toks = sample_on_device(dec_logits, r_dec, sampler)
                ta = sample_on_device(la, r_a, sampler)
                tb = sample_on_device(lb, r_b, sampler)
                state = toks.at[slotA].set(ta[0])
                state = jnp.where(lastB, state.at[slotB].set(tb[0]), state)
                return state, toks, toks == eos_ids, ta, tb, cache

            def _solo2_async(params, cache, tok_state, tokA, slotA, offA, nvA,
                             tokB, slotB, offB, nvB, rng, lastB):
                r_a, r_b = jax.random.split(rng)
                la, cache = model.prefill_step(params, cache, tokA, slotA, offA, nvA)
                lb, cache = model.prefill_step(params, cache, tokB, slotB, offB, nvB)
                ta = sample_on_device(la, r_a, sampler)
                tb = sample_on_device(lb, r_b, sampler)
                state = tok_state.at[slotA].set(ta[0])
                state = jnp.where(lastB, state.at[slotB].set(tb[0]), state)
                return state, ta, tb, cache

            self._fused2 = _step_program("fused2", _fused2_async)
            self._solo2 = _step_program("solo2", _solo2_async)

        self._fused = _step_program("fused", _fused_async)
        self._solo = _step_program("solo", _solo_async)

    # ------------------------------------------------- speculative decoding
    def _init_spec(self) -> None:
        """Build the jitted speculative programs (``spec_depth > 0``).

        ``spec_core`` is ONE device program per dispatch: k autoregressive
        draft decode+sample steps, one extra draft decode (so a fully
        accepted window leaves the draft cache holding every accepted
        position's K/V, including the last draft's), the target's
        (k+1)-position verify, rejection sampling, and both length
        commits.  The emitted token at ``n_accept`` becomes the next
        dispatch's ``tok_state`` entry without a host round-trip; the
        full ``(B, k+1)`` emitted array and the acceptance counts travel
        to the host lazily with the pipeline, like the non-speculative
        token/EOS arrays.
        """
        model, draft = self.model, self.draft_model
        k = self.spec_depth
        sampler = self.sampler
        d_decode = draft.decode_step
        verify = (model.paged_verify_step if self.cache_kind == "paged"
                  else model.verify_step)

        def spec_core(params, d_params, cache, d_cache, tok_state, rng):
            rngs = jax.random.split(rng, k + 1)
            tok = tok_state
            drafts, probs = [], []
            for j in range(k):
                d_logits, d_cache = d_decode(d_params, d_cache, tok)
                tok, p = spec_draft_sample(d_logits, rngs[j], sampler)
                drafts.append(tok)
                if p is not None:
                    probs.append(p)
            # write d_k's own K/V too: on full acceptance the next window
            # starts right after d_k, and its context must be complete
            _, d_cache = d_decode(d_params, d_cache, tok)
            tokens = jnp.stack([tok_state] + drafts, axis=1)      # (B, k+1)
            v_logits, cache = verify(params, cache, tokens)
            emitted, n_accept = spec_verify_tokens(
                v_logits,
                jnp.stack(drafts, axis=1),
                jnp.stack(probs, axis=1) if probs else None,
                rngs[k], sampler,
            )
            # KV rollback is just the commit: lengths advance only over
            # the accepted prefix + bonus token; rejected positions'
            # writes sit past the length and are causally invisible.  The
            # k+1 draft decodes advanced d_cache by k+1 — net it back to
            # the same n_accept+1 commit the target took.
            cache = {**cache, "lengths": cache["lengths"] + n_accept + 1}
            d_cache = {**d_cache,
                       "lengths": d_cache["lengths"] + n_accept - k}
            state = emitted[jnp.arange(emitted.shape[0]), n_accept]
            return state, emitted, n_accept, cache, d_cache

        self._spec_step = _step_program("spec", spec_core)
        if self.schedule != "hybrid":
            return
        if self.cache_kind == "paged":

            def _spec_fused(params, d_params, cache, staging, d_cache,
                            tok_state, pre_tokens, slot, lane, off, nv,
                            rng, last):
                r_pre, r_spec = jax.random.split(rng)
                pre_logits, staging = model.prefill_step(
                    params, staging, pre_tokens, lane, off, nv
                )
                state, emitted, n_accept, cache, d_cache = spec_core(
                    params, d_params, cache, d_cache, tok_state, r_spec
                )
                pre_tok = sample_on_device(pre_logits, r_pre, sampler)
                state = jnp.where(last, state.at[slot].set(pre_tok[0]), state)
                return (state, emitted, n_accept, pre_tok,
                        cache, staging, d_cache)
        else:

            def _spec_fused(params, d_params, cache, d_cache, tok_state,
                            pre_tokens, slot, off, nv, rng, last):
                r_pre, r_spec = jax.random.split(rng)
                pre_logits, cache = model.prefill_step(
                    params, cache, pre_tokens, slot, off, nv
                )
                state, emitted, n_accept, cache, d_cache = spec_core(
                    params, d_params, cache, d_cache, tok_state, r_spec
                )
                # the verify advanced every slot's length; the mid-prefill
                # slot stays at its chunk end (its garbage writes beyond
                # that are overwritten by the next chunk / first decode)
                lengths = cache["lengths"].at[slot].set(off + nv)
                cache = {**cache, "lengths": lengths}
                pre_tok = sample_on_device(pre_logits, r_pre, sampler)
                state = jnp.where(last, state.at[slot].set(pre_tok[0]), state)
                return state, emitted, n_accept, pre_tok, cache, d_cache

        self._spec_fused = _step_program("spec_fused", _spec_fused)

    def _draft_prefill_slot(self, slot: int, tokens: np.ndarray) -> None:
        """Prefill ``tokens`` into the draft cache at ``slot`` so draft
        and target lengths agree at the next dispatch boundary.  Chunked
        at ``prefill_chunk`` (one compiled shape per bucket); runs at
        dispatch time — device data-flow orders it after every in-flight
        step's d_cache writes and before the slot's next speculative
        dispatch reads it."""
        if not self.spec_depth:
            return
        bucket = self.prefill_chunk
        wslot = np.int32(slot)
        for start in range(0, len(tokens), bucket):
            nv = min(bucket, len(tokens) - start)
            buf = np.zeros((1, bucket), np.int32)
            buf[0, :nv] = tokens[start:start + nv]
            _, self.d_cache = self._draft_prefill(
                self.draft_params, self.d_cache, jnp.asarray(buf),
                wslot, np.int32(start), np.int32(nv),
            )
            self.stats.draft_steps += 1

    # ------------------------------------------------------------- requests
    def submit(self, req: Request):
        if len(req.prompt) >= self.max_seq - 1:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens does not fit max_seq="
                f"{self.max_seq}: admission needs len(prompt) <= max_seq - 2 "
                "so the cache holds the prompt plus at least one generated "
                "token without overflowing mid-decode"
            )
        req.submit_step = self.stats.engine_steps
        self.sched.submit(req)
        self.tracer.on_submit(self.replica, req, req.submit_step)

    # ------------------------------------------------- cluster router hooks
    def load(self) -> EngineLoad:
        """Load snapshot for ``least_loaded`` routing (read-only).  A
        chunked prefill in flight (``sched.inflight``) is committed work
        on a reserved slot even though the request is in neither
        ``slots`` nor the queue yet — count both."""
        inflight = sum(
            len(r.prompt) + len(r.out_tokens) + r.in_flight
            for r in self.slots if r is not None
        )
        inflight += sum(len(r.prompt) + len(r.out_tokens)
                        for r in self.sched.queue)
        fl = self.sched.inflight
        if fl is not None:
            inflight += fl.total
        return EngineLoad(
            free_slots=self.slots.count(None) - (0 if fl is None else 1),
            queued=len(self.sched),
            inflight_tokens=inflight,
            free_blocks=(self.pool.free_count if self.cache_kind == "paged"
                         else None),
        )

    def can_admit(self, req: Request) -> bool:
        """Would ``req`` be this replica's *next* prefill?  The cluster
        router's spill-over probe: read-only and conservative (counts
        resident prefix hits but never blocks a preemption could free).
        A chunked prefill already in flight counts as running — its slot
        is subtracted and the newcomer starts right behind it, a bounded
        wait — but any locally *queued* request means an unbounded park,
        so the answer is no."""
        fl = self.sched.inflight
        free = self.slots.count(None) - (0 if fl is None else 1)
        if len(self.sched) or free < 1:
            return False
        if self.cache_kind != "paged":
            return True
        # a preempted request re-admits with its generated tokens folded
        # into the prefill, so the block bill covers prompt + output
        tokens = self._refold(req) if req.out_tokens else np.asarray(
            req.prompt, np.int32
        )
        return self.manager.admit_shortfall(tokens) <= self.pool.free_count

    def probe_prefix(self, prompt: np.ndarray) -> int:
        """Longest resident prompt prefix, in tokens (0 for the dense
        cache — it has no prefix reuse).  Side-effect free; the router's
        ``prefix_affinity`` score."""
        if self.cache_kind != "paged":
            return 0
        return self.manager.probe_prefix(np.asarray(prompt, np.int32))

    # ---------------------------------------------------- KV block migration
    def export_request(self, slot: int):
        """Detach the resident request on ``slot``, with its KV, for
        migration to a peer replica (the disaggregated prefill->decode
        handoff; also load leveling).

        Async mode observes the victim's in-flight tokens first
        (:meth:`_observe_victim`) so the exported history is exact — which
        may reveal the request already finished; then, or when the slot
        holds a cold host-tier prefix (only fully device-resident
        sequences migrate), the export is declined and ``None`` returned.

        Otherwise returns ``(req, ticket, payload)``: the request (its
        slot here is freed), a :class:`MigrationTicket`, and the
        storage-dtype KV payload (:func:`paged.device.copy_blocks_out` /
        :func:`kv_cache.export_slot`).  Shared-prefix blocks are
        **copy-on-export**: the peer copies the payload while this
        replica's remaining owners keep the physical block and its hash
        entry; a dying private registered prefix still free-time-spills
        to the host tier, so migrating a sequence away never cold-starts
        this replica's prefix cache.
        """
        req = self.slots[slot]
        if req is None or req.done:
            return None
        if self.async_mode:
            self._observe_victim(slot)
            req = self.slots[slot]
            if req is None or req.done:
                return None             # finished while observing
        if self.cache_kind == "paged" and self.manager.cold_blocks[slot]:
            return None
        length = len(req.prompt) + len(req.out_tokens) - 1
        if self.cache_kind == "paged":
            ids = list(self.manager.blocks[slot])
            payload = paged_dev.copy_blocks_out(self.cache, ids)
            _, keys = self.manager.export_slot(slot)
            # dying private prefixes may free-time-spill host-ward: apply
            # before the freed device blocks can be reallocated/rewritten
            self._apply_pool_directives()
            self.cache = paged_dev.sync_slot(
                self.cache, slot, self.manager.tables[slot], 0
            )
            ticket = MigrationTicket(
                length=length, kv_dtype=self.kv_dtype, keys=keys,
                n_blocks=len(ids), block_size=self.block_size,
                src_step=self.stats.engine_steps,
            )
        else:
            payload = kv_cache.export_slot(self.cache, slot)
            self.cache = kv_cache.reset_slot(self.cache, slot)
            ticket = MigrationTicket(
                length=length, kv_dtype=self.kv_dtype,
                src_step=self.stats.engine_steps,
            )
        self.slots[slot] = None
        self.stats.migrations_out += 1
        return req, ticket, payload

    def preview_export(self, slot: int) -> MigrationTicket | None:
        """Read-only ticket for what :meth:`export_request` would produce
        — the cluster probes destinations (:meth:`can_import`) *before*
        paying the export.  Exact: the manager's block/key lists already
        reflect every dispatched append, and observing the victim's
        in-flight tokens at export time only converts them to observed
        output (same KV length) or finishes the request (export declines).
        None when the slot is empty, done, or holds a cold host-tier
        prefix."""
        req = self.slots[slot]
        if req is None or req.done:
            return None
        length = len(req.prompt) + len(req.out_tokens) + req.in_flight - 1
        if self.cache_kind != "paged":
            return MigrationTicket(
                length=length, kv_dtype=self.kv_dtype,
                src_step=self.stats.engine_steps,
            )
        if self.manager.cold_blocks[slot]:
            return None
        return MigrationTicket(
            length=length, kv_dtype=self.kv_dtype,
            keys=list(self.manager.keys[slot]),
            n_blocks=len(self.manager.blocks[slot]),
            block_size=self.block_size,
            src_step=self.stats.engine_steps,
        )

    def can_import(self, ticket: MigrationTicket) -> bool:
        """Read-only: could :meth:`import_request` land ``ticket`` right
        now without touching anyone?  Conservative — the import itself
        can additionally free blocks via spill-before-evict when a host
        tier exists, but it never preempts, so the cluster probes here
        before paying the export."""
        if ticket.kv_dtype != self.kv_dtype or ticket.length >= self.max_seq - 1:
            return False
        if (ticket.keys is None) != (self.cache_kind != "paged"):
            return False
        if not self._free_slots():
            return False
        if self.cache_kind != "paged":
            return True
        if ticket.block_size != self.block_size:
            return False
        return (
            self.manager.import_shortfall(ticket.keys, ticket.length)
            <= self.pool.free_count
        )

    def import_request(self, req: Request, ticket: MigrationTicket,
                       payload) -> int | None:
        """Land a migrating request: allocate/dedup blocks
        (:meth:`BlockPool.import_blocks`), scatter the payload columns the
        local prefix cache does not already hold, and resume decode with
        the same next-input token over the same KV — greedy output is
        token-identical to never having migrated.  Under block pressure
        with a host tier, resident cold prefixes spill host-ward
        (spill-before-evict) rather than preempting anyone.  Returns the
        landing slot, or ``None`` — nothing mutated — when capacity cannot
        be found."""
        if ticket.kv_dtype != self.kv_dtype:
            return None
        free = self._free_slots()
        if not free:
            return None
        slot = free[0]
        if self.cache_kind == "paged":
            fresh = self.manager.import_shortfall(ticket.keys, ticket.length)
            if fresh > self.pool.free_count:
                if not self.pool.host_blocks:
                    return None
                alive = [i for i, s in enumerate(self.slots) if s is not None]
                while fresh > self.pool.free_count and self._try_spill(alive):
                    pass
                if fresh > self.pool.free_count:
                    return None
            res = self.manager.import_slot(slot, ticket.keys, ticket.length)
            if res is None:
                return None
            ids, needs = res
            # copy only the payload columns the local prefix cache did not
            # already hold (a trailing headroom block has no payload column)
            sel = [j for j in range(ticket.n_blocks) if needs[j]]
            if sel:
                self.cache = paged_dev.copy_blocks_in(
                    self.cache, self._localize(payload), sel,
                    [ids[j] for j in sel],
                )
            self.cache = paged_dev.sync_slot(
                self.cache, slot, self.manager.tables[slot], ticket.length
            )
        else:
            self.cache = kv_cache.insert(self.cache, self._localize(payload), slot)
        self.slots[slot] = req
        # translate decode-latency accounting onto this engine's step
        # clock (finish_step will be stamped here; the elapsed decode
        # steps already spent on the source carry over)
        if req.first_token_step >= 0:
            req.first_token_step = (
                self.stats.engine_steps - (ticket.src_step - req.first_token_step)
            )
        if self.async_mode:
            # resume the device-side token feedback: the last sampled
            # token is the next decode input, exactly as on the source
            self._tok_state = paged_dev.feed_token(
                self._tok_state, slot, int(req.out_tokens[-1])
            )
            self._eos_dev = paged_dev.set_stop_id(self._eos_dev, slot, req.eos_id)
            # the draft cache did not travel: rebuild it from the history
            # (everything but the next-input token, matching the target's
            # imported KV length exactly)
            self._draft_prefill_slot(slot, self._refold(req)[:-1])
        self.stats.migrations_in += 1
        return slot

    def _localize(self, payload: Pytree) -> Pytree:
        """Move a migration payload onto this engine's devices: replicated
        over its mesh (the block scatter then lands each block on the
        lane that holds it), or onto its one device."""
        if self.mesh is not None:
            return jax.device_put(payload, NamedSharding(self.mesh, P()))
        (dev,) = self.cache["lengths"].devices()
        return jax.tree.map(lambda a: jax.device_put(a, dev), payload)

    # ---------------------------------------------- cluster refold leveling
    def can_admit_next(self) -> bool:
        """Will this engine's *own* queue head be admittable at the next
        step?  (:meth:`can_admit` answers for a *foreign* request and says
        no whenever anything is queued locally — this is the home-replica
        mirror the cluster consults before moving a preempted request's
        refold to a less-loaded replica.)"""
        if not len(self.sched):
            return False
        fl = self.sched.inflight
        if self.slots.count(None) - (0 if fl is None else 1) < 1:
            return False
        if self.cache_kind != "paged":
            return True
        head = self.sched.queue[0]
        tokens = self._refold(head) if head.out_tokens else np.asarray(
            head.prompt, np.int32
        )
        return self.manager.admit_shortfall(tokens) <= self.pool.free_count

    def take_refold(self) -> Request | None:
        """Pop this engine's queue head if it is a preempted (refolding)
        request the cluster wants to re-place elsewhere; None otherwise."""
        q = self.sched.queue
        if q and q[0].out_tokens and not q[0].done:
            return self.sched.pop()
        return None

    def adopt_refold(self, req: Request) -> None:
        """Accept a refolding request moved from another replica.  It
        keeps queue-front priority (it has already waited out a
        preemption) and re-enters on this engine's step clock."""
        req.submit_step = self.stats.engine_steps
        self.sched.push_front(req)

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _next_rng(self) -> jax.Array:
        self.rng, sub = jax.random.split(self.rng)
        return sub

    def _step_rng(self) -> jax.Array:
        """Per-dispatch rng for the async path.  Greedy never consumes
        randomness, so skip the per-step host-side key split entirely."""
        if self.sampler.temperature <= 0.0:
            return self._rng_zero
        return self._next_rng()

    @staticmethod
    def _refold(req: Request) -> np.ndarray:
        """Prompt plus already-generated tokens: prefilling this exactly
        reproduces a preempted request's decode state (greedy-exact)."""
        assert req.in_flight == 0 and req.in_flight_steps == 0, (
            "refold needs every dispatched token observed"
        )
        return np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out_tokens, np.int32)]
        )

    # --------------------------------------------- async pipeline primitives
    def _predicted_done(self, req: Request) -> bool:
        """Will the sync engine have marked ``req`` done once every
        dispatched token is observed?  Mirrors ``_finish_decode``'s check
        exactly: the first token after a (re-)admission comes from a
        prefill sample and is never length-checked, so a request is only
        predicted done once a *decode* token can trip the condition.

        Speculation: ``in_flight_steps`` is the guaranteed-commit floor
        (each dispatched window commits at least its bonus token), so a
        predicted-done here is certain — the engine never pauses a live
        slot whose device rows later dispatches would keep mutating.
        Extra tokens a window commits beyond the floor only finish the
        request *earlier*; the surplus dispatches are masked at observe
        exactly like the one-step EOS lag.
        """
        c = len(req.out_tokens) + req.in_flight_steps
        if c < req.admit_base + 2:
            return False
        return (c >= req.max_new_tokens
                or len(req.prompt) + c >= self.max_seq - 1)

    def _predicted_active(self) -> list[int]:
        if not self.async_mode:
            return [i for i, s in enumerate(self.slots) if s is not None]
        return [i for i, s in enumerate(self.slots)
                if s is not None and not self._predicted_done(s)]

    def _dispatch(self, rec: _PendingStep) -> None:
        """Queue a dispatched step; observe the previous one *after* the
        new one is in flight (the dispatch-ahead overlap).  A sync-mode
        speculative engine runs the same pipeline at depth zero: observe
        immediately after dispatch."""
        self._pending.append(rec)
        if self._sync_pipeline:
            self._drain()
            return
        if len(self._pending) > 1:
            self._observe(self._pending.popleft())

    def _readback(self, step: int, *arrays) -> tuple:
        """Fetch step ``step``'s device arrays (``None`` passes through)
        to the host: the blocking device-to-host copy, in an
        ``Engine.readback`` span (none when there is nothing to fetch)."""
        if all(a is None for a in arrays):
            return arrays
        with self.tracer.phase("Engine.readback", step=step):
            return jax.device_get(arrays)

    def _flush_first(self) -> None:
        for req, tok, step in self._first_pending:
            (tok,) = self._readback(step, tok)
            req.in_flight -= 1
            req.in_flight_steps -= 1
            req.out_tokens.append(int(tok[0]))
        self._first_pending.clear()

    def _observe(self, rec: _PendingStep) -> None:
        """Fetch one step's token/EOS arrays and apply completions.

        This is the only place the async engine blocks on the device, and
        by construction a newer step is already queued behind the one
        being fetched.  EOS retirements discovered here are one step
        late: the speculative token a later in-flight step sampled for a
        now-done request is masked (``req.done`` short-circuit below)."""
        self._flush_first()
        first = rec.work is not None and rec.work.last
        first2 = rec.work2 is not None and rec.work2.last
        pre_tok, pre_tok2, toks, eos, n_acc = self._readback(
            rec.step, rec.pre_tok if first else None,
            rec.pre_tok2 if first2 else None, rec.tokens, rec.eos,
            rec.n_accept,
        )
        if first:
            req = rec.work.req
            req.in_flight -= 1
            req.in_flight_steps -= 1
            req.out_tokens.append(int(pre_tok[0]))
        if first2:
            req = rec.work2.req
            req.in_flight -= 1
            req.in_flight_steps -= 1
            req.out_tokens.append(int(pre_tok2[0]))
        if toks is None:
            return
        if n_acc is not None:
            self._observe_spec(rec, toks, n_acc)
            return
        for i, req in rec.reqs.items():
            req.in_flight -= 1
            req.in_flight_steps -= 1
            if req.done:
                continue            # speculative token past EOS: masked
            tok = int(toks[i])
            req.out_tokens.append(tok)
            self.stats.generated += 1
            length = len(req.prompt) + len(req.out_tokens)
            if (
                bool(eos[i])
                or len(req.out_tokens) >= req.max_new_tokens
                or length >= self.max_seq - 1
            ):
                self._finish(i, req, rec.step)

    def _observe_spec(self, rec: _PendingStep, toks: np.ndarray,
                      n_acc: np.ndarray) -> None:
        """Apply one observed speculative window: per batch row, commit
        the accepted drafts plus the bonus/correction token (``toks[i]``
        holds ``n_acc[i] + 1`` valid leading positions), refund the
        unused in-flight charges, and stop at the first finish condition
        — an EOS *inside* the accepted window truncates the rest."""
        accepted = 0
        for i, req in rec.reqs.items():
            req.in_flight -= rec.charge
            req.in_flight_steps -= 1
            if req.done:
                continue            # window dispatched past EOS: masked
            n_emit = int(n_acc[i]) + 1
            accepted += n_emit - 1
            self.stats.drafted_tokens += self.spec_depth
            self.stats.accepted_tokens += n_emit - 1
            self.stats.spec_accept_samples.append(
                (n_emit - 1) / self.spec_depth
            )
            self._apply_spec_row(i, req, toks[i], n_emit, rec.step)
        if self.tracer.enabled:
            self.tracer.on_spec_verify(self.replica, rec.step, accepted,
                                       len(rec.reqs))

    def _apply_spec_row(self, slot: int, req: Request, row: np.ndarray,
                        n_emit: int, step: int) -> None:
        """Commit one slot's emitted tokens in stream order, applying the
        sync engine's finish conditions after each — identical to
        observing ``n_emit`` consecutive non-speculative steps."""
        for t in range(n_emit):
            tok = int(row[t])
            req.out_tokens.append(tok)
            self.stats.generated += 1
            length = len(req.prompt) + len(req.out_tokens)
            if (
                tok == req.eos_id
                or len(req.out_tokens) >= req.max_new_tokens
                or length >= self.max_seq - 1
            ):
                self._finish(slot, req, step)
                break

    def _drain(self) -> None:
        """Observe every in-flight step (pipeline empties; ``out_tokens``
        and ``in_flight`` become exact)."""
        while self._pending:
            self._observe(self._pending.popleft())
        self._flush_first()

    def _observe_victim(self, slot: int) -> None:
        """Observe only ``slot``'s in-flight tokens, in dispatch order,
        leaving every other slot's tokens (and the pending records
        themselves) in flight — the preemption refold needs *one* slot's
        exact history, so the rest of the pipeline stays overlapped
        instead of paying a full drain.  The victim's entries are
        consumed out of each record (``reqs``/``work`` cleared) so a
        later :meth:`_observe` of the same record skips them.  No-op when
        nothing of the victim's is in flight (sync mode always)."""
        req = self.slots[slot]
        if req is None or req.in_flight == 0:
            return
        self.stats.victim_drains += 1
        kept = []
        for r, tok, step in self._first_pending:
            if r is req:
                (tok,) = self._readback(step, tok)
                r.in_flight -= 1
                r.in_flight_steps -= 1
                r.out_tokens.append(int(tok[0]))
            else:
                kept.append((r, tok, step))
        self._first_pending[:] = kept
        for rec in self._pending:
            first = (rec.work is not None and rec.work.last
                     and rec.work.req is req)
            first2 = (rec.work2 is not None and rec.work2.last
                      and rec.work2.req is req)
            row = rec.tokens is not None and rec.reqs.get(slot) is req
            if not (first or first2 or row):
                continue
            pre_tok, pre_tok2, toks, eos, n_acc = self._readback(
                rec.step, rec.pre_tok if first else None,
                rec.pre_tok2 if first2 else None,
                *((rec.tokens, rec.eos, rec.n_accept) if row else (None,) * 3),
            )
            if first:
                req.in_flight -= 1
                req.in_flight_steps -= 1
                req.out_tokens.append(int(pre_tok[0]))
                rec.work = None          # consumed; _observe must not re-apply
            if first2:
                req.in_flight -= 1
                req.in_flight_steps -= 1
                req.out_tokens.append(int(pre_tok2[0]))
                rec.work2 = None
            if row:
                del rec.reqs[slot]
                req.in_flight -= rec.charge
                req.in_flight_steps -= 1
                if req.done:
                    continue
                if n_acc is not None:
                    n_emit = int(n_acc[slot]) + 1
                    self.stats.drafted_tokens += self.spec_depth
                    self.stats.accepted_tokens += n_emit - 1
                    self.stats.spec_accept_samples.append(
                        (n_emit - 1) / self.spec_depth
                    )
                    self._apply_spec_row(slot, req, toks[slot], n_emit,
                                         rec.step)
                    continue
                req.out_tokens.append(int(toks[slot]))
                self.stats.generated += 1
                length = len(req.prompt) + len(req.out_tokens)
                if (
                    bool(eos[slot])
                    or len(req.out_tokens) >= req.max_new_tokens
                    or length >= self.max_seq - 1
                ):
                    self._finish(slot, req, rec.step)
        assert req.in_flight == 0 and req.in_flight_steps == 0, (
            "victim drain left tokens in flight"
        )

    def _finish(self, slot: int, req: Request, step: int) -> None:
        """Retire a completed request: stats samples, trace, slot release.
        ``step`` is the engine-step clock value the finishing token was
        *dispatched* at (the async observe paths pass the pending
        record's stamp, keeping the clock identical to sync mode)."""
        req.done = True
        req.finish_step = step
        n_decode_tokens = len(req.out_tokens) - 1
        if n_decode_tokens > 0 and req.first_token_step >= 0:
            self.stats.per_token_samples.append(
                (req.finish_step - req.first_token_step) / n_decode_tokens
            )
        self.tracer.on_finish(self.replica, req, step, slot)
        self._release_slot(slot, req)

    def _release_slot(self, slot: int, req: Request) -> None:
        if self.slots[slot] is not req:
            return                  # slot already recycled past this record
        self.slots[slot] = None
        if self.cache_kind == "paged":
            self.manager.free_slot(slot)
            # dying registered blocks may spill host-ward: copy before
            # the freed device blocks can be reallocated and rewritten
            self._apply_pool_directives()
            self.cache = paged_dev.sync_slot(
                self.cache, slot, self.manager.tables[slot], 0
            )
        else:
            self.cache = kv_cache.reset_slot(self.cache, slot)

    # ------------------------------------------- admission (whole-prefill)
    def _prefill_cost(self, n_tokens: int) -> int:
        """Whole-prefill step cost, in fixed hybrid-batch units."""
        return max(1, -(-n_tokens // self.prefill_chunk))

    def _admit(self) -> None:
        """Whole-prompt admission (decode-only schedule): while a slot and,
        paged, the blocks allow, prefill the queue head into a slot; a head
        that does not fit waits (FCFS).  Each admission is scheduled, then
        dispatched as its own ``prefill`` step program; its prompt blocks
        and table are pushed in a second ``Engine.schedule`` span."""
        while True:
            with self.tracer.phase("Engine.schedule"):
                admitted = self._admit_next()
            if admitted is None:
                return
            req, slot, full, blocks, n_cached = admitted
            with self._dispatch_span("prefill"):
                if self.cache_kind == "paged":
                    pad = -(-len(full) // self.block_size) * self.block_size
                    sub_cache = self.model.init_cache(1, pad)
                else:
                    sub_cache = self.model.init_cache(1, self.max_seq)
                logits, sub_cache = self._prefill(
                    self.params, jnp.asarray(full, jnp.int32)[None], sub_cache
                )
            with self.tracer.phase("Engine.schedule"):
                if self.cache_kind == "paged":
                    # fill only the blocks the prefix cache didn't already hold
                    for j in range(n_cached, len(blocks)):
                        self.cache = paged_dev.write_prompt_block(
                            self.cache, sub_cache, blocks[j], j * self.block_size
                        )
                    self.cache = paged_dev.sync_slot(
                        self.cache, slot, self.manager.tables[slot], len(full)
                    )
                else:
                    self.cache = kv_cache.insert(self.cache, sub_cache, slot)
                self.slots[slot] = req
                self._draft_prefill_slot(slot, full)
                if self.async_mode:
                    self._sample_prefill(req, slot, logits)
            if not self.async_mode:
                (tok,) = self._sample_host(self.stats.engine_steps, logits)
                self._sample_prefill(req, slot, int(tok[0]))

    def _admit_next(self):
        """Take the queue head into the first free slot if it fits: charge
        its prefill's step cost and record the admission.  Returns
        ``(req, slot, tokens, blocks, n_cached)`` (paged: the slot's blocks
        and how many the prefix cache already held), or None.

        A preempted paged request re-enters with its generated tokens
        folded into the prefill, reproducing its exact decode state."""
        free = self._free_slots()
        if not free or not len(self.sched):
            return None
        slot = free[0]
        req = self.sched.peek()
        blocks, n_cached = None, 0
        if self.cache_kind == "paged":
            # the last sampled token is input, not cache content: the KV
            # written at admission covers full[:-1]'s context plus itself,
            # i.e. exactly len(full) positions after prefill
            full = self._refold(req)
            res = self.manager.try_admit(slot, full)
            if res is None:
                return None                 # out of blocks: wait/FCFS
            blocks, n_cached = res
        else:
            full = np.asarray(req.prompt, np.int32)
        self.sched.pop()
        step0 = self.stats.engine_steps
        self.stats.engine_steps += self._prefill_cost(len(full))
        if req.admit_step < 0:
            req.admit_step = self.stats.engine_steps
        self.tracer.on_admit(self.replica, req, step0, slot,
                             n_tokens=len(full), refold=bool(req.out_tokens))
        self.tracer.on_chunk(self.replica, req, slot, step0,
                             self.stats.engine_steps, 0, len(full), None, True)
        if self.tracer.enabled:
            self._trace_prefill_dispatch(len(full),
                                         self.stats.engine_steps - step0)
        if self.cache_kind == "paged":
            # host-tier prefix hits re-hydrate: apply the copies before
            # the prefill's own block writes go out
            self._apply_pool_directives()
        return req, slot, full, blocks, n_cached

    def _sample_prefill(self, req: Request, slot: int, first) -> None:
        """Commit a completed prompt's first token.  Sync mode: ``first``
        is the token id, sampled and fetched by the caller.  Async: the
        prompt's logits, sampled on device into ``tok_state`` for the next
        decode step; the id is fetched lazily with the step stream, so the
        host never blocks on the prefill here."""
        req.admit_base = len(req.out_tokens)
        if self.async_mode:
            tok = self._jit_sample(first, self._step_rng(), cfg=self.sampler)
            self._tok_state = paged_dev.feed_token(self._tok_state, slot, tok[0])
            self._eos_dev = paged_dev.set_stop_id(self._eos_dev, slot, req.eos_id)
            req.in_flight += 1
            req.in_flight_steps += 1
            self._first_pending.append((req, tok, self.stats.engine_steps))
        else:
            req.out_tokens.append(first)
        self._record_first_token(req, slot)

    def _record_first_token(self, req: Request, slot: int) -> None:
        """Shared prefill-completion accounting (sync and async paths)."""
        first = req.first_token_step < 0
        if first:
            req.first_token_step = self.stats.engine_steps
            ttft = req.first_token_step - req.submit_step
            self.stats.ttft_steps_sum += ttft
            self.stats.ttft_count += 1
            self.stats.ttft_samples.append(ttft)
        self.stats.prefills += 1
        self.stats.generated += 1
        self.tracer.on_first_token(self.replica, req, self.stats.engine_steps,
                                   slot, first=first)

    # --------------------------------------------- admission (chunked/hybrid)
    def _begin_prefill(self, req: Request, slot: int) -> tuple[int, int]:
        """Pin ``req``'s (possibly re-folded) prompt for chunked prefill;
        returns (first chunk position, total tokens)."""
        full = self._refold(req)
        self._pf_tokens[slot] = full
        if self.cache_kind != "paged":
            self._pf_prefix[slot] = 0
            return 0, len(full)
        bs = self.block_size
        # claim a free staging lane (at most two prompts mid-flight: the
        # boundary-packed newcomer takes whichever lane the finishing
        # prompt does not hold)
        lane = 0 if 0 not in self._pf_lane.values() else 1
        self._pf_lane[slot] = lane
        matched = self.manager.begin_chunked(slot, full)
        # host-tier hits re-hydrate into fresh device blocks: the copies
        # must land before the staging reads below consume them
        self._apply_pool_directives()
        self._pf_prefix[slot] = len(matched)
        for j, phys in enumerate(matched):
            self.staging = paged_dev.read_block(
                self.staging, self.cache, phys, j * bs, lane
            )
        # a fully prefix-cached prompt still recomputes its last chunk for
        # the first-token logits (pool writes for matched blocks skip)
        start = min(len(matched) * bs, (len(full) - 1) // bs * bs)
        return start, len(full)

    def _complete_chunk(self, work: PrefillChunk, first_tok: int | None,
                        advance: bool = True):
        """Commit an executed chunk (sync mode: ``first_tok`` is the
        prompt's first token, sampled from the chunk's logits, when the
        chunk completes the prompt).  ``advance=False`` when the scheduler
        was already advanced at boundary-packing time (the next prompt had
        to begin before the fused dispatch was built).  The prompt blocks and
        table go out in an ``Engine.schedule`` span."""
        with self.tracer.phase("Engine.schedule"):
            self.tracer.on_chunk(self.replica, work.req, work.slot,
                                 self.stats.engine_steps - 1,
                                 self.stats.engine_steps, work.start,
                                 work.n_valid, work.bucket, work.last)
            self._flush_chunk_blocks(work)
            if advance:
                self.sched.advance(work)
            if work.last:
                req = work.req
                self.slots[work.slot] = req
                if self.cache_kind == "paged":
                    self.cache = paged_dev.sync_slot(
                        self.cache, work.slot, self.manager.tables[work.slot],
                        work.start + work.n_valid,
                    )
                self._end_prefill(work.slot)
                self._sample_prefill(req, work.slot, first_tok)

    def _complete_chunk_async(self, work: PrefillChunk, advance: bool = True):
        """Async twin of :meth:`_complete_chunk`: the fused step already
        sampled the first token on device and spliced it into
        ``tok_state``; the host only does block/table bookkeeping (safe at
        dispatch time — device data-flow orders it after the step) and
        records that one more token is in flight, in an ``Engine.schedule``
        span."""
        with self.tracer.phase("Engine.schedule"):
            self.tracer.on_chunk(self.replica, work.req, work.slot,
                                 self.stats.engine_steps - 1,
                                 self.stats.engine_steps, work.start,
                                 work.n_valid, work.bucket, work.last)
            self._flush_chunk_blocks(work)
            if advance:
                self.sched.advance(work)
            if work.last:
                req = work.req
                self.slots[work.slot] = req
                if self.cache_kind == "paged":
                    self.cache = paged_dev.sync_slot(
                        self.cache, work.slot, self.manager.tables[work.slot],
                        work.start + work.n_valid,
                    )
                self._draft_prefill_slot(work.slot, self._pf_tokens[work.slot])
                self._end_prefill(work.slot)
                req.admit_base = len(req.out_tokens)
                req.in_flight += 1
                req.in_flight_steps += 1
                self._eos_dev = paged_dev.set_stop_id(
                    self._eos_dev, work.slot, req.eos_id
                )
                self._record_first_token(req, work.slot)

    def _end_prefill(self, slot: int) -> None:
        """Release a completed prompt's per-slot prefill state (and its
        staging lane, for the paged cache)."""
        self._pf_tokens.pop(slot, None)
        self._pf_prefix.pop(slot, None)
        self._pf_lane.pop(slot, None)

    def _flush_chunk_blocks(self, work: PrefillChunk) -> None:
        if self.cache_kind != "paged":
            return
        bs = self.block_size
        lane = self._pf_lane.get(work.slot, 0)
        end = work.start + work.n_valid
        for j in range(work.start // bs, (end - 1) // bs + 1):
            if j < self._pf_prefix.get(work.slot, 0):
                continue            # prefix-cache hit: already valid
            self.cache = paged_dev.write_prompt_block(
                self.cache, self.staging, self.manager.blocks[work.slot][j],
                j * bs, lane,
            )

    # ----------------------------------------------------- block management
    def _apply_pool_directives(self) -> None:
        """Drain the pool's pending device<->host copy directives into
        actual device ops.  Must run after every manager/pool call that
        can spill or re-hydrate, *before* any subsequent write could
        clobber an involved block — device data-flow ordering then makes
        the copy land ahead of later cache updates, because every op
        threads ``self.cache``."""
        for kind, a, b in self.pool.drain_directives():
            if kind == "spill":
                self.cache = paged_dev.spill_block(self.cache, a, b)
                self.stats.spills += 1
                self.tracer.on_spill(self.replica, self.stats.engine_steps, a, b)
            else:
                self.cache = paged_dev.rehydrate_block(self.cache, a, b)
                self.stats.rehydrations += 1
                self.tracer.on_rehydrate(self.replica, self.stats.engine_steps, a, b)

    def _try_spill(self, alive) -> bool:
        """Spill-before-evict: free one device block by moving the oldest
        sequence's coldest hot block to the host tier.  The sequence
        keeps decoding (hybrid hot/cold attention, LSE-merged) — no
        re-prefill, unlike preemption.  Returns False when nothing can
        spill (no qualifying block, or host tier saturated)."""
        for s in sorted(alive, key=lambda x: self.manager.admit_seq[x]):
            if self.slots[s] is None:
                continue
            if self.manager.spill_live_prefix(s, self._kv_len(s)):
                self._apply_pool_directives()
                self.cache = paged_dev.sync_slot(
                    self.cache, s, self.manager.tables[s]
                )
                self.cache = paged_dev.sync_host_slot(
                    self.cache, s, self.manager.host_tables[s],
                    self.manager.cold_len(s),
                )
                return True
        return False

    def _kv_len(self, slot: int) -> int:
        """KV positions held for ``slot`` (last sampled token not yet
        appended — it is this step's input).  Counts in-flight tokens:
        the async engine plans appends from dispatched, not observed,
        state.  Under speculation the charges are an upper bound on the
        commits, so this over- rather than under-states the device
        length — safe for spill/export sizing."""
        req = self.slots[slot]
        return len(req.prompt) + len(req.out_tokens) + req.in_flight - 1

    def _append_span(self, slot: int) -> tuple[int, int]:
        """Inclusive position range [lo, hi] the slot's next dispatch may
        write.  With in-flight speculative windows the device length is
        only known to lie in [committed + steps, committed + charges];
        the next window then writes up to ``spec_depth`` positions past
        its start, so every position through hi needs a mapped block.
        Without speculation lo == hi == :meth:`_kv_len` — the single
        append position of the original code."""
        req = self.slots[slot]
        base = len(req.prompt) + len(req.out_tokens)
        lo = base + req.in_flight_steps - 1
        hi = base + req.in_flight - 1 + self.spec_depth
        return lo, hi

    def _preempt(self, slot: int):
        """Evict ``slot`` to the queue front; blocks return to the pool.
        Its tokens are preserved and recomputed at re-admission."""
        req = self.slots[slot]
        self.slots[slot] = None
        self.manager.free_slot(slot)
        self._apply_pool_directives()
        self.cache = paged_dev.sync_slot(
            self.cache, slot, self.manager.tables[slot], 0
        )
        self.sched.push_front(req)
        self.stats.preemptions += 1
        self.pool.stats.preemptions += 1
        self.tracer.on_preempt(self.replica, req, self.stats.engine_steps, slot)

    def _prepare_append(self, active: list[int]) -> list[int]:
        """Guarantee every active slot can write its next dispatch's
        token span (one position, or up to ``spec_depth + 1`` per
        in-flight window under speculation — see :meth:`_append_span`):
        allocate boundary blocks, copy-on-write shared tails, preempt the
        youngest sequence when the pool runs dry.  Returns the surviving
        slots.

        Async: a preemption decision snapshots ``out_tokens`` for exact
        recovery, but only the *victim's* history has to be exact — so
        its in-flight tokens are observed first (:meth:`_observe_victim`)
        while every other slot's stay in flight and the pipeline keeps
        its overlap.  The observed tokens may reveal the victim already
        finished (EOS lags one step): then its blocks are free and no
        eviction is needed.  Only when the victim is genuinely alive is
        the rest of the pipeline drained before evicting — an unobserved
        EOS on *another* slot may free enough blocks to avoid the
        preemption entirely, and one settled iteration is far cheaper
        than re-prefilling the victim's whole KV."""
        alive = set(active)
        limit = self.max_blocks * self.block_size
        for slot in sorted(active, key=lambda s: self.manager.admit_seq[s]):
            pos = None
            while slot in alive:
                if self.slots[slot] is None:
                    alive.discard(slot)     # retired during a drain below
                    break
                # a drain below can move the span: observed commits raise
                # lo (each step commits at least one token) and shrink hi
                # (unused charges refund), so pos only ever moves forward
                lo, hi = self._append_span(slot)
                if pos is None or pos < lo:
                    pos = lo
                if pos > hi or pos >= limit:
                    break       # span mapped (or clamped at the cache top:
                                # writes past it are dropped/masked on device)
                directive, payload = self.manager.ensure_append(slot, pos)
                if directive == "oom":
                    if self.pool.host_blocks and self._try_spill(alive):
                        continue    # freed a block without evicting anyone
                    victim = self.manager.youngest(alive)
                    self._observe_victim(victim)
                    if self.slots[victim] is None:
                        alive.discard(victim)   # finished: blocks already free
                        continue                # retry without evicting
                    if self._pending or self._first_pending:
                        self._drain()       # settle completions elsewhere
                        alive = {s for s in alive if self.slots[s] is not None}
                        continue            # retry before paying a re-prefill
                    self._preempt(victim)
                    alive.discard(victim)
                    continue                # retry (unless we evicted slot)
                if directive == "cow":
                    src, dst = payload
                    self.cache = paged_dev.copy_block(self.cache, src, dst)
                if directive in ("cow", "new"):
                    self.cache = paged_dev.sync_slot(
                        self.cache, slot, self.manager.tables[slot]
                    )
                pos += 1
        return [s for s in active if s in alive]

    # ------------------------------------------- boundary packing (Sarathi-SC)
    def _chunk_arrays(self, work: PrefillChunk):
        chunk = np.zeros((1, work.bucket), np.int32)
        chunk[0, :work.n_valid] = self._pf_tokens[work.slot][
            work.start:work.start + work.n_valid
        ]
        return jnp.asarray(chunk), np.int32(work.start), np.int32(work.n_valid)

    def _boundary_chunk(self, budget: int, taken: int) -> PrefillChunk | None:
        """The final chunk of the prompt on slot ``taken`` was advanced
        and left ``budget`` tokens of this iteration's dispatch unused:
        begin the next queued prompt and pack its head chunk into the
        *same* dispatch (Sarathi-SC boundary packing — both chunks ride
        one weight stream via ``_fused2``/``_solo2``), so the token
        budget stays full across prompt boundaries.  The paged cache
        stages the newcomer's chunks in the second staging lane.
        ``taken`` is excluded from the slot choice — the finishing
        prompt claims it only after this dispatch completes."""
        sched = self.sched
        if budget <= 0 or sched.inflight is not None or not len(sched):
            return None
        if self.cache_kind == "paged" and len(self._pf_lane) >= 2:
            return None             # both staging lanes held
        free = [s for s in self._free_slots() if s != taken]
        if not free:
            return None
        req = sched.pop()
        slot = free[0]
        start, total = self._begin_prefill(req, slot)
        sched.begin(req, slot, start, total)
        if req.admit_step < 0:
            req.admit_step = self.stats.engine_steps
        self.tracer.on_admit(self.replica, req, self.stats.engine_steps,
                             slot, n_tokens=total,
                             refold=bool(req.out_tokens))
        work2 = sched.pack_boundary(budget)
        if work2 is not None and self.cache_kind == "paged":
            ok = self.manager.extend_chunked(
                work2.slot, len(self._pf_tokens[work2.slot]),
                work2.start + work2.n_valid, work2.last,
            )
            if not ok:
                return None         # pool dry now: B's chunks run later
        return work2

    # ------------------------------------------------------------ telemetry
    def _trace_prefill_dispatch(self, n_tokens: int, n_steps: int) -> StepRecord:
        """StepRecord for a whole-prompt admission prefill (decode-only
        schedule), charged at its ``ceil(L / prefill_chunk)``-step cost.
        Called only when telemetry is enabled; returns the record (already
        handed to the tracer) so the profiler can annotate it in place."""
        cm = self._cost_model
        ctx = cm.chunk_ctx_tokens(0, n_tokens)
        flops, bytes_ = cm.cost(0, 0, n_tokens, ctx)
        rec = StepRecord(
            replica=self.replica, step=self.stats.engine_steps,
            kind="prefill", decode_batch=0, prefill_tokens=n_tokens,
            bucket=None, bucket2=None,
            budget=n_steps * self.prefill_chunk,
            fill=n_tokens / max(n_steps * self.prefill_chunk, 1),
            kv_tokens=0,
            pool_util=(self.pool.utilization
                       if self.cache_kind == "paged" else None),
            host_util=(self.pool.host_utilization
                       if self.cache_kind == "paged" and self.host_blocks
                       else None),
            pipeline_depth=len(self._pending),
            flops=flops, bytes=bytes_, oi=flops / max(bytes_, 1.0),
            wall=self.tracer.wall(),
        )
        self.tracer.on_step(rec)
        return rec

    def _trace_step(self, kind: str, active: list[int],
                    work: PrefillChunk | None = None,
                    work2: PrefillChunk | None = None) -> StepRecord:
        """StepRecord for one decode/fused dispatch: composition (batch,
        chunk, budget fill, pool pressure, pipeline depth) plus analytic
        FLOPs/bytes so each dispatch lands on the paper's Fig-1 roofline.
        Called only when telemetry is enabled, from host bookkeeping the
        engine already holds — no device reads.  Returns the record
        (already handed to the tracer) so the sampled profiler can join
        its fenced wall-clock measurement onto it in place."""
        cm = self._cost_model
        kv = 0
        for i in active:
            r = self.slots[i]
            kv += len(r.prompt) + len(r.out_tokens) + r.in_flight
        pre = ctx = 0
        for w in (work, work2):
            if w is not None:
                pre += w.n_valid
                ctx += cm.chunk_ctx_tokens(w.start, w.n_valid)
        budget = (self.sched.token_budget if self.schedule == "hybrid"
                  else len(self.slots))
        flops, bytes_ = cm.cost(len(active), kv, pre, ctx)
        rec = StepRecord(
            replica=self.replica, step=self.stats.engine_steps, kind=kind,
            decode_batch=len(active), prefill_tokens=pre,
            bucket=work.bucket if work is not None else None,
            bucket2=work2.bucket if work2 is not None else None,
            budget=budget, fill=(len(active) + pre) / max(budget, 1),
            kv_tokens=kv,
            pool_util=(self.pool.utilization
                       if self.cache_kind == "paged" else None),
            host_util=(self.pool.host_utilization
                       if self.cache_kind == "paged" and self.host_blocks
                       else None),
            pipeline_depth=len(self._pending),
            flops=flops, bytes=bytes_, oi=flops / max(bytes_, 1.0),
            wall=self.tracer.wall(),
        )
        self.tracer.on_step(rec)
        return rec

    def _profile_fence(self):
        """The pytree the profiler blocks on to bracket a sampled
        dispatch: cache (+ paged staging buffers, + async token state)
        covers every array the jit chain writes.  ``block_until_ready``
        skips None subtrees, so missing pieces cost nothing."""
        return (
            self.cache,
            getattr(self, "staging", None),
            self._tok_state if self.async_mode else None,
        )

    def _dispatch_kind(self, active, work, work2) -> str:
        spec = bool(self.spec_depth and active)
        if work2 is not None:
            return "fused2" if active else "solo2"
        if work is not None:
            return ("spec_fused" if spec else "fused") if active else "solo"
        return "spec" if spec else "decode"

    # ----------------------------------------------------------------- step
    def _decode_tokens(self) -> jax.Array:
        tokens = np.zeros((len(self.slots),), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None and req.out_tokens:
                tokens[i] = req.out_tokens[-1]
        return jnp.asarray(tokens)

    def _dispatch_span(self, kind: str):
        """``Engine.dispatch`` span of the step program about to be
        enqueued as step ``engine_steps`` (building its inputs included);
        the enclosing ``Engine.step`` span takes the last one's id."""
        self._dispatched = (self.stats.engine_steps, kind)
        return self.tracer.phase("Engine.dispatch",
                                 step=self.stats.engine_steps, kind=kind)

    def _sample_host(self, step: int, *logits) -> tuple:
        """Sync mode: sample each logits array (``None`` skips) on the
        host's rng stream, in order, and fetch the ids."""
        return self._readback(step, *(
            None if lg is None else sample(lg, self._next_rng(), self.sampler)
            for lg in logits
        ))

    def _finish_decode(self, active: list[int], next_host: np.ndarray):
        for i in active:
            req = self.slots[i]
            tok = int(next_host[i])
            req.out_tokens.append(tok)
            self.stats.generated += 1
            length = len(req.prompt) + len(req.out_tokens)
            if (
                tok == req.eos_id
                or len(req.out_tokens) >= req.max_new_tokens
                or length >= self.max_seq - 1
            ):
                self._finish(i, req, self.stats.engine_steps)

    def step(self) -> bool:
        """One engine iteration.  Returns whether any work remains.

        Every path emits the same host spans (``Tracer.phase``; nothing
        without a tracer): ``Engine.step`` around the call, carrying the
        ``step`` id and ``kind`` of the step program it dispatched, if any;
        inside it ``Engine.schedule`` (admission, planning, block
        allocation, and the block-table and prompt-block pushes before the
        dispatch and, for a finished prompt, after it), ``Engine.dispatch``
        (inputs built and the program enqueued) and ``Engine.readback``
        (each blocking device-to-host fetch, with the id of the step
        fetched)."""
        with self.tracer.phase("Engine.step") as span:
            self._dispatched = None
            if self.schedule == "hybrid":
                more = (self._step_hybrid_async() if self.async_mode
                        else self._step_hybrid())
            elif self.async_mode:
                more = self._step_decode_only_async()
            else:
                more = self._step_decode_only()
            if self._dispatched is not None:
                step, kind = self._dispatched
                span.set_metadata(step=step, kind=kind)
        return more

    def _step_decode_only(self) -> bool:
        self._admit()
        with self.tracer.phase("Engine.schedule"):
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if self.cache_kind == "paged" and active:
                active = self._prepare_append(active)
        if not active:
            return self.sched.has_work()
        self.stats.peak_active = max(self.stats.peak_active, len(active))
        self.stats.engine_steps += 1

        prof = self.profiler
        sampling = prof.enabled and prof.tick()
        if sampling:
            prof.begin(self._profile_fence())
        with self._dispatch_span("decode"):
            logits, self.cache = self._decode(
                self.params, self.cache, self._decode_tokens()
            )
        if sampling:
            prof.end(self._profile_fence())
        self.stats.decode_steps += 1
        if self._telemetry:
            rec = self._trace_step("decode", active)
            if sampling:
                prof.commit(rec)
        (toks,) = self._sample_host(self.stats.engine_steps, logits)
        self._finish_decode(active, toks)
        return any(s is not None for s in self.slots) or self.sched.has_work()

    def _step_decode_only_async(self) -> bool:
        self._admit()
        with self.tracer.phase("Engine.schedule"):
            active = self._predicted_active()
            if self.cache_kind == "paged" and active:
                active = self._prepare_append(active)
        if not active:
            self._drain()               # nothing to dispatch: settle state
            return any(s is not None for s in self.slots) or self.sched.has_work()
        self.stats.peak_active = max(self.stats.peak_active, len(active))
        self.stats.engine_steps += 1
        kind = "spec" if self.spec_depth else "decode"

        prof = self.profiler
        sampling = prof.enabled and prof.tick()
        if sampling:
            prof.begin(self._profile_fence())    # settle in-flight steps
        eos = n_accept = None
        with self._dispatch_span(kind):
            if self.spec_depth:
                (self._tok_state, toks, n_accept,
                 self.cache, self.d_cache) = self._spec_step(
                    self.params, self.draft_params, self.cache, self.d_cache,
                    self._tok_state, self._step_rng(),
                )
            else:
                toks, eos, self.cache = self._decode_sampled(
                    self.params, self.cache, self._tok_state, self._step_rng(),
                    self._eos_dev, sampler=self.sampler,
                )
                self._tok_state = toks
        if sampling:
            prof.end(self._profile_fence())
        self.stats.decode_steps += 1
        charge = 1
        if self.spec_depth:
            charge = self.spec_depth + 1
            self.stats.spec_steps += 1
            self.stats.draft_steps += self.spec_depth + 1
        if self._telemetry:
            rec = self._trace_step(kind, active)
            if sampling:
                prof.commit(rec)
            if self.spec_depth:
                self.tracer.on_spec_propose(
                    self.replica, self.stats.engine_steps,
                    self.spec_depth, len(active),
                )
        reqs = {}
        for i in active:
            req = self.slots[i]
            req.in_flight += charge
            req.in_flight_steps += 1
            reqs[i] = req
        self._dispatch(_PendingStep(
            step=self.stats.engine_steps, reqs=reqs, tokens=toks, eos=eos,
            n_accept=n_accept, charge=charge,
        ))
        return True

    def _schedule_hybrid(self):
        """Plan one hybrid iteration (both execution modes): admit the
        queue head into chunked prefill, settle the decode batch's blocks,
        pack the token budget, allocate the chunk's blocks, and, when the
        chunk finishes its prompt, begin the next one and pack its head
        chunk into the same dispatch (Sarathi-SC boundary packing; not
        under speculation — the fused2 programs have no spec variant, and
        the budget a verify leaves over rarely fits two chunks).  Returns
        ``(active, work, work2, pre_advanced)``, with the step counted, or
        None when there is nothing to dispatch."""
        sched = self.sched
        if sched.inflight is None and len(sched):
            free = self._free_slots()
            if free:
                req = sched.pop()
                slot = free[0]
                start, total = self._begin_prefill(req, slot)
                sched.begin(req, slot, start, total)
                if req.admit_step < 0:
                    req.admit_step = self.stats.engine_steps + 1
                self.tracer.on_admit(self.replica, req,
                                     self.stats.engine_steps, slot,
                                     n_tokens=total,
                                     refold=bool(req.out_tokens))

        active = self._predicted_active()
        if self.cache_kind == "paged" and active:
            active = self._prepare_append(active)
        decision = (sched.plan_ahead if self.async_mode
                    else sched.schedule)(active)
        active = decision.decode_slots       # the scheduler owns the batch
        work = decision.prefill
        if work is not None and self.cache_kind == "paged":
            ok = self.manager.extend_chunked(
                work.slot, len(self._pf_tokens[work.slot]),
                work.start + work.n_valid, work.last,
            )
            if not ok:
                work = None             # pool dry: decode-only iteration
        if not active and work is None:
            return None

        self.stats.engine_steps += 1
        self.stats.peak_active = max(self.stats.peak_active, len(active))
        work2 = None
        pre_advanced = False
        if work is not None and work.last and len(sched) and not self.spec_depth:
            sched.advance(work)         # A rides this dispatch regardless
            pre_advanced = True
            work2 = self._boundary_chunk(
                sched.token_budget - len(active) - work.n_valid, work.slot
            )
        return active, work, work2, pre_advanced

    def _step_hybrid(self) -> bool:
        with self.tracer.phase("Engine.schedule"):
            plan = self._schedule_hybrid()
        if plan is None:
            return self.sched.has_work()
        active, work, work2, pre_advanced = plan
        if work2 is not None:
            self.stats.boundary_packs += 1
            self.tracer.on_boundary_pack(self.replica, work2.req,
                                         self.stats.engine_steps, work2.slot)
        kind = self._dispatch_kind(active, work, work2)

        prof = self.profiler
        sampling = prof.enabled and prof.tick()
        if sampling:
            prof.begin(self._profile_fence())
        dec_logits = pre_logits = logits2 = None
        paged = self.cache_kind == "paged"
        with self._dispatch_span(kind):
            if work is not None:
                chunk, off, nv = self._chunk_arrays(work)
                # the paged cache stages chunks in a lane, the dense one
                # writes them at the slot
                where = np.int32(self._pf_lane.get(work.slot, 0) if paged
                                 else work.slot)
            if work2 is not None:
                chunk2, off2, nv2 = self._chunk_arrays(work2)
                where2 = np.int32(self._pf_lane.get(work2.slot, 0) if paged
                                  else work2.slot)
                if paged and active:
                    (dec_logits, pre_logits, logits2,
                     self.cache, self.staging) = self._fused2(
                        self.params, self.cache, self.staging,
                        self._decode_tokens(),
                        chunk, where, off, nv, chunk2, where2, off2, nv2,
                    )
                elif paged:
                    pre_logits, logits2, self.staging = self._solo2(
                        self.params, self.staging,
                        chunk, where, off, nv, chunk2, where2, off2, nv2,
                    )
                elif active:
                    dec_logits, pre_logits, logits2, self.cache = self._fused2(
                        self.params, self.cache, self._decode_tokens(),
                        chunk, where, off, nv, chunk2, where2, off2, nv2,
                    )
                else:
                    pre_logits, logits2, self.cache = self._solo2(
                        self.params, self.cache,
                        chunk, where, off, nv, chunk2, where2, off2, nv2,
                    )
            elif active and work is not None:
                if paged:
                    dec_logits, pre_logits, self.cache, self.staging = self._fused(
                        self.params, self.cache, self.staging,
                        self._decode_tokens(), chunk, where, off, nv,
                    )
                else:
                    dec_logits, pre_logits, self.cache = self._fused(
                        self.params, self.cache, self._decode_tokens(), chunk,
                        where, off, nv,
                    )
            elif active:
                dec_logits, self.cache = self._decode(
                    self.params, self.cache, self._decode_tokens()
                )
            elif paged:
                pre_logits, self.staging = self._solo(
                    self.params, self.staging, chunk, where, off, nv
                )
            else:
                pre_logits, self.cache = self._solo(
                    self.params, self.cache, chunk, where, off, nv
                )
        if active:
            self.stats.decode_steps += 1

        if sampling:
            prof.end(self._profile_fence())
        if self._telemetry:
            rec = self._trace_step(kind, active, work, work2)
            if sampling:
                prof.commit(rec)
        dec, first, first2 = self._sample_host(
            self.stats.engine_steps, dec_logits,
            pre_logits if work is not None and work.last else None,
            logits2 if work2 is not None and work2.last else None,
        )
        if active:
            self._finish_decode(active, dec)
        if work is not None:
            self.stats.prefill_chunks += 1
            self._complete_chunk(work, None if first is None else int(first[0]),
                                 advance=not pre_advanced)
        if work2 is not None:
            self.stats.prefill_chunks += 1
            self._complete_chunk(work2,
                                 None if first2 is None else int(first2[0]))
        return any(s is not None for s in self.slots) or self.sched.has_work()

    def _step_hybrid_async(self) -> bool:
        with self.tracer.phase("Engine.schedule"):
            plan = self._schedule_hybrid()
        if plan is None:
            self._drain()
            return any(s is not None for s in self.slots) or self.sched.has_work()
        active, work, work2, pre_advanced = plan
        rng = self._step_rng()
        if work2 is not None:
            self.stats.boundary_packs += 1
            self.tracer.on_boundary_pack(self.replica, work2.req,
                                         self.stats.engine_steps, work2.slot)
        kind = self._dispatch_kind(active, work, work2)
        paged = self.cache_kind == "paged"

        prof = self.profiler
        sampling = prof.enabled and prof.tick()
        if sampling:
            prof.begin(self._profile_fence())    # settle in-flight steps
        toks = eos = pre_tok = pre_tok2 = n_accept = None
        with self._dispatch_span(kind):
            if work is not None:
                chunk, off, nv = self._chunk_arrays(work)
                wslot = np.int32(work.slot)
                lane = np.int32(self._pf_lane.get(work.slot, 0))
            if work2 is not None:
                chunk2, off2, nv2 = self._chunk_arrays(work2)
                wslot2 = np.int32(work2.slot)
                lane2 = np.int32(self._pf_lane.get(work2.slot, 0))
                if paged and active:
                    (self._tok_state, toks, eos, pre_tok, pre_tok2,
                     self.cache, self.staging) = self._fused2(
                        self.params, self.cache, self.staging, self._tok_state,
                        chunk, wslot, lane, off, nv,
                        chunk2, wslot2, lane2, off2, nv2,
                        rng, self._eos_dev, work2.last,
                    )
                elif paged:
                    (self._tok_state, pre_tok, pre_tok2,
                     self.staging) = self._solo2(
                        self.params, self.staging, self._tok_state,
                        chunk, wslot, lane, off, nv,
                        chunk2, wslot2, lane2, off2, nv2,
                        rng, work2.last,
                    )
                elif active:
                    (self._tok_state, toks, eos, pre_tok, pre_tok2,
                     self.cache) = self._fused2(
                        self.params, self.cache, self._tok_state,
                        chunk, wslot, off, nv, chunk2, wslot2, off2, nv2,
                        rng, self._eos_dev, work2.last,
                    )
                else:
                    self._tok_state, pre_tok, pre_tok2, self.cache = self._solo2(
                        self.params, self.cache, self._tok_state,
                        chunk, wslot, off, nv, chunk2, wslot2, off2, nv2,
                        rng, work2.last,
                    )
            elif active and work is not None:
                if self.spec_depth and paged:
                    (self._tok_state, toks, n_accept, pre_tok, self.cache,
                     self.staging, self.d_cache) = self._spec_fused(
                        self.params, self.draft_params, self.cache,
                        self.staging, self.d_cache, self._tok_state,
                        chunk, wslot, lane, off, nv, rng, work.last,
                    )
                elif self.spec_depth:
                    (self._tok_state, toks, n_accept, pre_tok,
                     self.cache, self.d_cache) = self._spec_fused(
                        self.params, self.draft_params, self.cache,
                        self.d_cache, self._tok_state,
                        chunk, wslot, off, nv, rng, work.last,
                    )
                elif paged:
                    (self._tok_state, toks, eos, pre_tok,
                     self.cache, self.staging) = self._fused(
                        self.params, self.cache, self.staging, self._tok_state,
                        chunk, wslot, lane, off, nv, rng, self._eos_dev,
                        work.last,
                    )
                else:
                    self._tok_state, toks, eos, pre_tok, self.cache = self._fused(
                        self.params, self.cache, self._tok_state,
                        chunk, wslot, off, nv, rng, self._eos_dev, work.last,
                    )
            elif active:
                if self.spec_depth:
                    (self._tok_state, toks, n_accept,
                     self.cache, self.d_cache) = self._spec_step(
                        self.params, self.draft_params, self.cache,
                        self.d_cache, self._tok_state, rng,
                    )
                else:
                    toks, eos, self.cache = self._decode_sampled(
                        self.params, self.cache, self._tok_state, rng,
                        self._eos_dev, sampler=self.sampler,
                    )
                    self._tok_state = toks
            elif paged:
                self._tok_state, pre_tok, self.staging = self._solo(
                    self.params, self.staging, self._tok_state, chunk, wslot,
                    lane, off, nv, rng, work.last,
                )
            else:
                self._tok_state, pre_tok, self.cache = self._solo(
                    self.params, self.cache, self._tok_state,
                    chunk, wslot, off, nv, rng, work.last,
                )
        if active:
            self.stats.decode_steps += 1

        if sampling:
            prof.end(self._profile_fence())
        charge = 1
        if self.spec_depth and active:
            charge = self.spec_depth + 1
            self.stats.spec_steps += 1
            self.stats.draft_steps += self.spec_depth + 1
            if self.tracer.enabled:
                self.tracer.on_spec_propose(
                    self.replica, self.stats.engine_steps,
                    self.spec_depth, len(active),
                )

        if self._telemetry:
            srec = self._trace_step(kind, active, work, work2)
            if sampling:
                prof.commit(srec)
        reqs = {}
        for i in active:
            req = self.slots[i]
            req.in_flight += charge
            req.in_flight_steps += 1
            reqs[i] = req
        rec = _PendingStep(
            step=self.stats.engine_steps, reqs=reqs, tokens=toks, eos=eos,
            work=work, pre_tok=pre_tok, work2=work2, pre_tok2=pre_tok2,
            n_accept=n_accept, charge=charge,
        )
        if work is not None:
            self.stats.prefill_chunks += 1
            self._complete_chunk_async(work, advance=not pre_advanced)
        if work2 is not None:
            self.stats.prefill_chunks += 1
            self._complete_chunk_async(work2)
        self._dispatch(rec)
        return True

    def run(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            if not self.step():
                break
        if self.async_mode:
            self._drain()           # settle out_tokens if max_steps truncated
        return self.stats

    # -------------------------------------------------------- introspection
    def kv_bytes(self) -> int:
        """Physical KV footprint of the resident cache (both modes)."""
        return kv_cache.kv_bytes(self.cache)
