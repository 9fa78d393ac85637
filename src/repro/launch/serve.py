"""End-to-end serving driver: continuous batching with offloaded decode.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --requests 16 --slots 4 --max-new 12

Add ``--cache paged [--block-size 16] [--blocks N]`` to serve from the
paged block pool (admission gated on free blocks, prefix sharing,
preemption under block pressure) instead of the dense per-slot cache.
``--kv-dtype {fp8,int8}`` stores the pool quantized with per-vector
scales (~2x effective KV capacity per device byte), and
``--host-blocks N`` adds a host KV tier: cold prefix blocks spill there
instead of forcing preemption, and spilled sequences keep decoding via
LSE-merged hybrid attention over the split hot/cold KV.

Add ``--schedule hybrid [--prefill-chunk 32] [--token-budget N]`` to run
the token-budget scheduler: each iteration fuses a bucket-padded prefill
chunk of the head-of-queue prompt into the decode batch (Sarathi-style
chunked prefill — the paper's compute/bandwidth co-processing expressed
as one model step), instead of whole-prompt prefills that recompile per
prompt length and stall decode.

``--async on`` (the default) runs the dispatch-ahead pipeline: sampling
happens on device inside the fused step and iteration *t+1* is
dispatched before *t*'s tokens are observed, so the device never idles
on the host round-trip.  ``--async off`` is the conservative synchronous
fallback (greedy outputs are token-identical either way).  Sampling is
picked with ``--sample {greedy,temperature,top-k}`` plus
``--temperature`` / ``--top-k`` values.

``--spec-depth K`` turns on speculative multi-token decoding: a
reduced-scale draft model (``--draft ARCH``, default a reduced variant
of ``--arch``) proposes K tokens per decode slot each step and the
target verifies all K+1 positions in one fused pass, committing the
accepted prefix device-to-device.  Greedy outputs are token-identical
to non-speculative serving; with temperature, rejection sampling keeps
every emitted token an exact sample from the target distribution.

Add ``--replicas N [--route round_robin|least_loaded|prefix_affinity]``
to serve from a :class:`~repro.serving.cluster.Cluster` of N engine
replicas behind a shared global queue: the router places each request on
the first replica (in policy order) that can admit it now, spilling over
when the first choice is saturated.  ``prefix_affinity`` (paged cache
only in effect) routes shared-prompt traffic to the replica already
holding its prefix blocks.

``--role-map SPEC`` disaggregates the cluster into prefill/decode
replicas (``1p+1d``, ``2p+2d``, ``2p+1d+1m``, or an explicit comma list
like ``prefill,decode``): prompts are admitted to prefill-role replicas
and their KV blocks migrate to the least-loaded decode-role replica when
the last prefill chunk completes.  ``--decode-slots N`` gives the
decode-role replicas a larger slot count than ``--slots`` (their block
budget scales along).  On a multi-device host each replica is placed on
its own even slice of the devices (an uneven split is an error); a
single-device host shares its one device among the replicas.

The compile cache persists at ``$JAX_COMPILATION_CACHE_DIR`` when set,
else at ``<repo>/.jax_cache``.

Observability (see ``docs/observability.md``):

* ``--workload {random,poisson,bursty,chat-fan,rag,agentic}`` replaces
  the all-at-round-0 prompt list with a seeded arrival process played by
  :class:`~repro.serving.workload.WorkloadDriver` (``--arrival-rate``,
  ``--fan``, ``--turns``, ``--workload-seed`` shape it);
* ``--slo-ttft N`` / ``--slo-tpot M`` declare engine-step SLO targets:
  the run reports sliding-window p50/p99, attainment fraction and
  goodput, and the trace gains ``slo_breach`` marks;
* ``--profile N`` samples every Nth dispatch with a fenced wall-clock
  measurement (``1`` = sync mode, times everything; ``0`` = off;
  default: 8 when ``--trace`` is given) and joins it with the analytic
  cost model into measured MFU/MBU/bandwidth counter tracks (peaks from
  the device's ``device_kind``; MFU/MBU are not measured on the CPU);
* ``--dashboard N`` prints a terminal snapshot every N rounds.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path
from typing import Any

import jax

from repro.configs import SHAPES, get_config
from repro.configs.reduced import reduce_config
from repro.core import balance
from repro.core.placement import Env
from repro.launch.mesh import make_host_mesh, mesh_axes, replica_meshes
from repro.models.registry import build_model
from repro.serving.cluster import ROUTE_POLICIES, Cluster, parse_roles
from repro.serving.engine import Engine
from repro.serving.sampler import SamplerConfig
from repro.serving.telemetry import (
    SLOMonitor,
    Tracer,
    cluster_registry,
    engine_registry,
    make_profiler,
    render_dashboard,
    write_metrics,
    write_trace,
)
from repro.serving.workload import WORKLOADS, WorkloadDriver, build_workload

REPO = Path(__file__).resolve().parents[3]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--sub-batches", type=int, default=1)
    ap.add_argument("--async", dest="async_mode", choices=("on", "off"),
                    default="on",
                    help="on: dispatch-ahead pipeline with on-device "
                         "sampling; off: synchronous fallback (greedy "
                         "token-identical)")
    ap.add_argument("--sample", choices=("greedy", "temperature", "top-k"),
                    default=None,
                    help="sampling mode (temperature/top-k use the values "
                         "of --temperature / --top-k); default: greedy, or "
                         "top-k when --temperature > 0 is passed")
    ap.add_argument("--temperature", type=float, default=None,
                    help="softmax temperature (default 1.0 when --sample "
                         "temperature/top-k is given, else greedy)")
    ap.add_argument("--top-k", type=int, default=40,
                    help="top-k truncation for --sample top-k")
    ap.add_argument("--cache", choices=("dense", "paged"), default="dense")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per physical KV block")
    ap.add_argument("--blocks", type=int, default=None,
                    help="paged: pool size incl. null block "
                         "(default: dense-equivalent budget)")
    ap.add_argument("--kv-dtype", choices=("bf16", "fp8", "int8"),
                    default="bf16",
                    help="paged: KV block storage dtype; fp8/int8 store "
                         "quantized blocks with per-vector scales (~2x KV "
                         "capacity at the same device byte budget)")
    ap.add_argument("--host-blocks", type=int, default=0,
                    help="paged: host-tier KV blocks; cold shared-prefix "
                         "blocks spill here instead of forcing preemption, "
                         "and spilled sequences keep decoding via LSE-merged "
                         "hybrid attention")
    ap.add_argument("--schedule", choices=("decode-only", "hybrid"),
                    default="decode-only",
                    help="hybrid: fuse chunked prefill into decode steps")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="hybrid: max prompt tokens prefilled per step")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="hybrid: per-step token budget "
                         "(default: slots + prefill_chunk)")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="speculative decoding: draft tokens proposed per "
                         "decode step (0 = off); each step verifies k+1 "
                         "positions in one fused target pass")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="draft model architecture for --spec-depth > 0 "
                         "(default: a reduced-config variant of --arch; "
                         "always instantiated at reduced scale so the "
                         "draft stays cheap relative to the target)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the shared global queue")
    ap.add_argument("--route", choices=ROUTE_POLICIES, default="round_robin",
                    help="replica routing policy (with --replicas > 1)")
    ap.add_argument("--role-map", default=None, metavar="SPEC",
                    help="disaggregated replica roles: shorthand like "
                         "'1p+1d' / '2p+2d+1m' or a comma list like "
                         "'prefill,decode' (default: all mixed)")
    ap.add_argument("--decode-slots", type=int, default=None,
                    help="slot count override for decode-role replicas "
                         "(default: --slots; their paged block budget "
                         "scales along)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request spans + step timeline and write a "
                         "Perfetto/Chrome-trace JSON here")
    ap.add_argument("--metrics-out", default=None, metavar="OUT.json",
                    help="write the metrics-registry snapshot as flat JSON")
    ap.add_argument("--workload", choices=WORKLOADS, default="random",
                    help="arrival-process shape (random = legacy: every "
                         "request at round 0)")
    ap.add_argument("--workload-seed", type=int, default=0,
                    help="seed for the workload generator (same seed = "
                         "byte-identical schedule)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="open-loop arrival rate in requests/round for "
                         "poisson/bursty/chat-fan/rag/agentic workloads")
    ap.add_argument("--fan", type=int, default=4,
                    help="chat-fan: requests sharing each prompt prefix")
    ap.add_argument("--turns", type=int, default=3,
                    help="agentic: total turns per session (each turn "
                         "resubmits with the prior output as grown prefix)")
    ap.add_argument("--slo-ttft", type=int, default=None, metavar="STEPS",
                    help="TTFT SLO target in engine steps; enables the "
                         "attainment/goodput report and slo_breach trace "
                         "marks")
    ap.add_argument("--slo-tpot", type=float, default=None, metavar="STEPS",
                    help="per-output-token SLO target in engine steps")
    ap.add_argument("--profile", type=int, default=None, metavar="N",
                    help="fence + wall-clock every Nth dispatch and join "
                         "with the analytic cost model into measured "
                         "MFU/MBU/bandwidth (1 = sync: every dispatch; "
                         "0 = off; default: 8 with --trace, else off)")
    ap.add_argument("--dashboard", type=int, default=0, metavar="N",
                    help="print a terminal snapshot every N driver rounds "
                         "(queue depth, active slots, pipeline depth, pool "
                         "util, SLO attainment, measured MFU/MBU)")
    return ap


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache across runs: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it; otherwise
    the cache lives at the fixed ``<repo>/.jax_cache`` (a moving path
    would never hit).  Called by entry points only, never on import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))


@dataclasses.dataclass
class Server:
    """What :func:`build_server` assembles from the CLI arguments."""

    cfg: Any
    plan: balance.Plan
    model: Any
    params: Any
    sampler: SamplerConfig
    mode: str                        # greedy | temperature | top-k
    engine: Engine                   # the engine, or replica 0's
    cluster: Cluster | None
    tracer: Tracer | None
    slo: SLOMonitor | None
    profiler: Any

    @property
    def front(self):
        """Where requests go: the cluster, or the lone engine."""
        return self.cluster if self.cluster is not None else self.engine


def build_server(args: argparse.Namespace, devices=None) -> Server:
    """Build the model, its seeded weights and the engine or cluster
    that :func:`main` serves with.  ``devices`` (default: every device
    of this host) form the serving mesh; with more than one, the KV
    cache and decode attention spread across them per the balancer's
    placement policy."""
    cfg = reduce_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh(devices=devices)
    axes = mesh_axes(mesh)
    plan = balance.plan(cfg, SHAPES["decode_32k"], axes)
    sharded = mesh.devices.size > 1
    env = Env(axes=axes if sharded else {}, mesh=mesh if sharded else None,
              kv_policy=plan.kv_policy)
    model = build_model(cfg, env)
    params = model.init(jax.random.key(0))

    mode = args.sample
    if mode is None:
        # pre---sample behavior: a bare --temperature > 0 meant top-40
        mode = "greedy" if not args.temperature else "top-k"
    if mode == "greedy":
        sampler = SamplerConfig()
    else:
        # an explicit sampling mode must actually sample: temperature 0
        # would silently degrade to greedy (both samplers branch on it)
        temp = args.temperature if args.temperature else 1.0
        sampler = SamplerConfig(
            temperature=temp, top_k=args.top_k if mode == "top-k" else 0
        )
    engine_kw = dict(
        n_slots=args.slots, max_seq=args.max_seq,
        sampler=sampler,
        sub_batches=args.sub_batches,
        cache_kind=args.cache, block_size=args.block_size, n_blocks=args.blocks,
        kv_dtype=args.kv_dtype, host_blocks=args.host_blocks,
        schedule=args.schedule, prefill_chunk=args.prefill_chunk,
        token_budget=args.token_budget,
        async_mode=args.async_mode == "on",
    )
    if args.spec_depth:
        # the draft shares the target's tokenizer/vocab but runs at
        # reduced scale — proposal cost stays small next to the verify
        draft_cfg = reduce_config(args.draft or args.arch, vocab=cfg.vocab)
        draft_model = build_model(draft_cfg, env)
        engine_kw.update(
            spec_depth=args.spec_depth,
            draft_model=draft_model,
            draft_params=draft_model.init(jax.random.key(1)),
        )
    # SLO monitoring rides the tracer's lifecycle hooks, so declaring a
    # target implies a tracer even without --trace (nothing is written)
    slo = None
    if args.slo_ttft is not None or args.slo_tpot is not None:
        slo = SLOMonitor(ttft_target=args.slo_ttft, tpot_target=args.slo_tpot)
    tracer = Tracer(wall=True, slo=slo) if (args.trace or slo) else None
    sample_every = args.profile
    if sample_every is None:
        sample_every = 8 if args.trace else 0
    profiler = make_profiler(sample_every)
    roles = parse_roles(args.role_map, args.replicas) if args.role_map else None
    role_kw = ({"decode": {"n_slots": args.decode_slots}}
               if args.decode_slots else None)
    model_factory = None
    if args.replicas > 1:
        meshes = replica_meshes(args.replicas)
        if len({id(m) for m in meshes}) > 1:
            # disjoint per-replica mesh slices: each replica engine gets
            # a model built against its own slice, and places its
            # weights and pools there
            def model_factory(i, _meshes=meshes):
                m = _meshes[i]
                split = m.devices.size > 1
                env_i = Env(axes=mesh_axes(m) if split else {},
                            mesh=m, kv_policy=plan.kv_policy)
                return build_model(cfg, env_i)
    cluster = (
        Cluster(model, params, args.replicas, route=args.route, tracer=tracer,
                profiler=profiler if profiler.enabled else None,
                roles=roles, role_kw=role_kw, model_factory=model_factory,
                **engine_kw)
        if args.replicas > 1 else None
    )
    eng = (cluster.engines[0] if cluster
           else Engine(model, params, tracer=tracer,
                       profiler=profiler if profiler.enabled else None,
                       **engine_kw))
    return Server(cfg=cfg, plan=plan, model=model, params=params,
                  sampler=sampler, mode=mode, engine=eng, cluster=cluster,
                  tracer=tracer, slo=slo, profiler=profiler)


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    srv = build_server(args)
    plan, cfg, sampler, mode = srv.plan, srv.cfg, srv.sampler, srv.mode
    print(f"balancer: policy={plan.kv_policy} sub_batches={plan.sub_batches} "
          f"bottleneck={plan.bottleneck} "
          f"(t_att={plan.t_attention*1e3:.2f}ms t_lin={plan.t_linear*1e3:.2f}ms)")
    cluster, eng, serv = srv.cluster, srv.engine, srv.front
    tracer, slo, profiler = srv.tracer, srv.slo, srv.profiler
    arrivals = build_workload(
        args.workload, args.requests, vocab=cfg.vocab, max_seq=args.max_seq,
        max_new=args.max_new, seed=args.workload_seed,
        rate=args.arrival_rate, fan=args.fan, turns=args.turns,
    )
    on_round = None
    if args.dashboard:
        def on_round(r, _every=args.dashboard):
            if r % _every == 0:
                print(render_dashboard(serv, r, slo=slo, profiler=profiler))
    driver = WorkloadDriver(serv, arrivals, vocab=cfg.vocab,
                            max_seq=args.max_seq, seed=args.workload_seed,
                            on_round=on_round)

    t0 = time.time()
    rounds = driver.run()
    dt = time.time() - t0
    stats = serv.stats() if cluster else eng.stats
    n_requests = len(driver.submitted)
    # all reported numbers flow through the metrics registry — the CLI
    # printout and the --metrics-out dump read the same snapshot
    registry = (
        cluster_registry(stats) if cluster
        else engine_registry(
            stats, eng.pool.stats if args.cache == "paged" else None
        )
    )
    if slo is not None:
        slo.register(registry, elapsed=rounds)
    if profiler.enabled:
        profiler.register(registry)
    snap = registry.snapshot()
    print(f"mode: async={args.async_mode} sample={mode} "
          f"(T={sampler.temperature} top_k={sampler.top_k})")
    print(f"workload: {args.workload} seed={args.workload_seed} "
          f"submitted={n_requests} resubmits={driver.resubmits} "
          f"rounds={rounds}")
    if cluster:
        role_str = (" roles=" + ",".join(cluster.roles)
                    if args.role_map else "")
        print(f"cluster: replicas={args.replicas} route={args.route}"
              f"{role_str}")
        print(f"requests={n_requests} {stats.summary()}")
        if stats.migrations:
            print(f"disagg: migrations={stats.migrations} "
                  f"refold_moves={stats.refold_moves} "
                  f"ttft_rounds mean {stats.mean_ttft_rounds:.1f} "
                  f"p99 {stats.ttft_rounds_percentile(99):.0f}")
        print(f"latency: TTFT mean {snap['mean_ttft_steps']:.1f} "
              f"p50 {snap['ttft_steps_p50']:.0f} "
              f"p99 {snap['ttft_steps_p99']:.0f} engine steps, "
              f"per-token p99 {snap['per_token_steps_p99']:.2f} steps")
        print(f"wall {dt:.2f}s -> {stats.generated/dt:.1f} tok/s")
        if args.cache == "paged":
            for i, e in enumerate(cluster.engines):
                print(f"pool[r{i}]: {e.pool.stats}")
    else:
        print(f"requests={n_requests} prefills={stats.prefills} "
              f"prefill_chunks={stats.prefill_chunks} "
              f"boundary_packs={stats.boundary_packs} "
              f"decode_steps={stats.decode_steps} "
              f"engine_steps={stats.engine_steps} "
              f"generated={stats.generated} peak_active={stats.peak_active}")
        if args.spec_depth:
            print(f"spec: depth={args.spec_depth} "
                  f"accept_rate={stats.acceptance_rate:.2f} "
                  f"drafted={stats.drafted_tokens} "
                  f"accepted={stats.accepted_tokens} "
                  f"spec_steps={stats.spec_steps}")
        print(f"latency: TTFT mean {snap['mean_ttft_steps']:.1f} "
              f"p50 {snap['ttft_steps_p50']:.0f} "
              f"p99 {snap['ttft_steps_p99']:.0f} engine steps, "
              f"{snap['tokens_per_step']:.2f} tokens/step")
        print(f"wall {dt:.2f}s -> {stats.generated/dt:.1f} tok/s "
              f"(batch efficiency "
              f"{stats.generated/max(stats.decode_steps*args.slots,1):.0%})")
        if args.cache == "paged":
            print(f"pool: {eng.pool.stats} kv_bytes={eng.kv_bytes()}")
            if args.host_blocks:
                print(f"kv tier: spills={stats.spills} "
                      f"rehydrations={stats.rehydrations} "
                      f"host_peak={eng.pool.stats.host_peak_in_use}"
                      f"/{args.host_blocks} blocks")
    if slo is not None:
        print(slo.describe())
        print(f"goodput: {slo.goodput(rounds):.2f} SLO-attaining "
              f"tokens/round over {rounds} rounds")
    if profiler.enabled:
        print(profiler.describe())
        for key, row in sorted(profiler.summary().items()):
            kind, bucket, batch = key
            print(f"  measured {kind:10s} bucket={bucket} batch={batch}: "
                  f"n={int(row['n'])} {row['seconds']*1e3:.2f}ms "
                  f"mfu={row['measured_mfu']:.4f} "
                  f"mbu={row['measured_mbu']:.4f} "
                  f"bw={row['achieved_gbps']:.1f}GB/s")
    if args.trace:
        path = write_trace(tracer, args.trace)
        print(f"trace: {path} (open at ui.perfetto.dev)")
    if args.metrics_out:
        path = write_metrics(
            registry, args.metrics_out,
            extra={"wall_s": dt, "rounds": float(rounds),
                   "requests": float(n_requests)},
        )
        print(f"metrics: {path}")


if __name__ == "__main__":
    main()
