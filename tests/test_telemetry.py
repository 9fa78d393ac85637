"""Serving telemetry: span-tree structure across every cache x schedule
combo (sync and async, incl. preemption/refold and boundary packing),
Perfetto trace export round-trips and validates, the metrics registry
matches legacy ``EngineStats`` exactly, tracing never changes tokens,
and the disabled tracer stays a no-op."""
import collections
import json

import jax
import numpy as np
import pytest

from repro.configs.reduced import reduce_config
from repro.core.placement import Env
from repro.models.registry import build_model
from repro.serving.cluster import Cluster
from repro.serving.cluster.stats import ClusterStats, ReplicaStats
from repro.serving.engine import Engine, EngineStats, Request
from repro.serving.telemetry import (
    NULL_TRACER,
    Counter,
    DispatchCostModel,
    Gauge,
    Histogram,
    MetricsRegistry,
    Tracer,
    build_request_trees,
    cluster_registry,
    engine_registry,
    percentile,
    to_chrome_trace,
    validate_trace,
    write_metrics,
    write_trace,
)


@pytest.fixture(scope="module")
def model_params():
    cfg = reduce_config("llama3.2-1b")
    model = build_model(cfg, Env())
    return model, model.init(jax.random.key(0))


PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(7, 10, dtype=np.int32),
           np.arange(2, 13, dtype=np.int32),
           np.arange(4, 25, dtype=np.int32)]      # multi-chunk


def _serve_traced(model, params, prompts, n_new=5, tracer=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq", 32)
    tracer = Tracer() if tracer is None else tracer
    eng = Engine(model, params, tracer=tracer, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs, eng, tracer


def _assert_all_well_formed(tracer, n_requests):
    trees = build_request_trees(tracer)
    assert len(trees) == n_requests
    for tree in trees.values():
        assert tree.finished
        assert tree.well_formed() == [], tree.well_formed()
    return trees


# ------------------------------------------------------------- span trees
COMBOS = [
    dict(),                                                   # dense/decode-only
    dict(schedule="hybrid", prefill_chunk=8),                 # dense/hybrid
    dict(cache_kind="paged", block_size=8),                   # paged/decode-only
    dict(cache_kind="paged", block_size=8,
         schedule="hybrid", prefill_chunk=8),                 # paged/hybrid
]


@pytest.mark.parametrize("combo", COMBOS,
                         ids=["dense-decode", "dense-hybrid",
                              "paged-decode", "paged-hybrid"])
@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_span_trees_well_formed(model_params, combo, async_mode):
    model, params = model_params
    _, eng, tracer = _serve_traced(model, params, PROMPTS,
                                   async_mode=async_mode, **combo)
    trees = _assert_all_well_formed(tracer, len(PROMPTS))
    # per-dispatch timeline covered every engine step exactly once
    assert len(tracer.steps) == eng.stats.engine_steps
    assert [r.step for r in tracer.steps] == \
        list(range(1, eng.stats.engine_steps + 1))
    # the multi-chunk prompt produced multiple chunk spans under hybrid
    if combo.get("schedule") == "hybrid":
        assert len(trees[(0, 3)].child("prefill_chunk")) >= 2


def test_preemption_refold_trace(model_params):
    """Under block pressure the victim's decode span closes at the
    preemption, a fresh queued span opens, and the re-admission carries a
    ``refolded`` mark — in both engine modes."""
    model, params = model_params
    prompts = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 8, dtype=np.int32)]
    kw = dict(cache_kind="paged", block_size=4, n_blocks=9,
              schedule="hybrid", prefill_chunk=8)
    for async_mode in (False, True):
        _, eng, tracer = _serve_traced(model, params, prompts, n_new=10,
                                       async_mode=async_mode, **kw)
        assert eng.stats.preemptions >= 1
        trees = _assert_all_well_formed(tracer, len(prompts))
        victim = next(t for t in trees.values() if t.marks("preempted"))
        assert len(victim.marks("refolded")) == len(victim.marks("preempted"))
        assert len(victim.child("queued")) >= 2        # requeued while evicted
        assert len(victim.child("decode")) >= 2        # decode resumed
        pre_step = victim.marks("preempted")[0].step
        closed_at_pre = [s for s in victim.child("decode")
                        if s.end == pre_step and s.attrs.get("preempted")]
        assert closed_at_pre, "no decode span closed at the preemption"


def test_boundary_pack_trace(model_params):
    """A packed boundary leaves a ``boundary_packed`` mark on the head
    request and both chunks appear as spans on their own slot tracks."""
    model, params = model_params
    for async_mode in (False, True):
        _, eng, tracer = _serve_traced(model, params, PROMPTS,
                                       schedule="hybrid", prefill_chunk=8,
                                       async_mode=async_mode)
        assert eng.stats.boundary_packs >= 1
        packs = [e for e in tracer.events if e.name == "boundary_packed"]
        assert len(packs) == eng.stats.boundary_packs
        trees = _assert_all_well_formed(tracer, len(PROMPTS))
        packed = trees[(0, packs[0].uid)]
        # the packed head chunk is a real span at the pack step
        assert any(s.end == packs[0].step
                   for s in packed.child("prefill_chunk"))


# ---------------------------------------------------------------- export
def test_trace_json_round_trip(model_params, tmp_path):
    model, params = model_params
    _, _, tracer = _serve_traced(model, params, PROMPTS,
                                 schedule="hybrid", prefill_chunk=8,
                                 cache_kind="paged", block_size=8)
    path = write_trace(tracer, tmp_path / "trace.json")
    obj = json.loads(path.read_text())
    assert validate_trace(obj) == []
    evs = obj["traceEvents"]
    # every slot/queue/steps track is named for the Perfetto UI
    names = {(e["pid"], e["tid"], e["args"]["name"])
             for e in evs if e["ph"] == "M"}
    assert (0, 0, "replica 0") in {(p, t, n) for p, t, n in names} or \
        any(n == "replica 0" for _, _, n in names)
    assert any(n == "queue" for _, _, n in names)
    assert any(n == "steps" for _, _, n in names)
    # spans and counters made it through JSON intact
    assert any(e["ph"] == "X" and e.get("cat") == "request" for e in evs)
    assert any(e["ph"] == "X" and e.get("cat") == "dispatch" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "oi" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "pool_util" for e in evs)


def test_validate_trace_rejects_malformed():
    assert validate_trace([]) == ["top level is not an object"]
    assert validate_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [{"ph": "Q", "name": "x", "pid": 0, "tid": 0,
                            "ts": 0}]}
    assert any("bad ph" in p for p in validate_trace(bad))
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                            "ts": -1, "dur": 1}]}
    assert any("bad ts" in p for p in validate_trace(bad))
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                            "ts": 0}]}
    assert any("bad dur" in p for p in validate_trace(bad))


# -------------------------------------------------------------- registry
def test_engine_registry_matches_legacy_stats(model_params, tmp_path):
    """The registry is a *view* over EngineStats — every reported number
    equals the legacy field exactly on a greedy run."""
    model, params = model_params
    _, eng, _ = _serve_traced(model, params, PROMPTS,
                              schedule="hybrid", prefill_chunk=8,
                              cache_kind="paged", block_size=8)
    stats = eng.stats
    reg = engine_registry(stats, eng.pool.stats)
    snap = reg.snapshot()
    for name in ("prefills", "prefill_chunks", "boundary_packs",
                 "decode_steps", "engine_steps", "generated",
                 "preemptions", "victim_drains"):
        assert snap[name] == float(getattr(stats, name)), name
    assert snap["peak_active"] == float(stats.peak_active)
    assert snap["tokens_per_step"] == stats.tokens_per_step
    assert snap["mean_ttft_steps"] == stats.mean_ttft_steps
    assert snap["ttft_steps_count"] == float(stats.ttft_count)
    assert snap["ttft_steps_p50"] == stats.ttft_p50_steps
    assert snap["ttft_steps_p99"] == stats.ttft_p99_steps
    assert snap["pool_allocs"] == float(eng.pool.stats.allocs)
    # and the flat JSON dump is the same snapshot
    out = write_metrics(reg, tmp_path / "metrics.json", extra={"wall_s": 1.0})
    dumped = json.loads(out.read_text())
    assert dumped.pop("wall_s") == 1.0
    assert dumped == snap


def test_metrics_primitives():
    assert percentile([], 99) == 0.0
    assert percentile([3.0], 50) == 3.0
    samples = list(range(1, 101))
    assert percentile(samples, 50) == 50.0
    assert percentile(samples, 99) == 99.0
    assert percentile(samples, 100) == 100.0
    # a measured percentile is a value some sample actually took
    odd = [1.0, 10.0, 100.0]
    assert percentile(odd, 90) in odd

    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    h = reg.histogram("h")
    h.extend([1, 2, 3, 4])
    assert isinstance(reg.counter("c"), Counter)
    assert isinstance(reg.gauge("g"), Gauge)
    assert isinstance(reg.histogram("h"), Histogram)
    with pytest.raises(TypeError):
        reg.gauge("c")
    snap = reg.snapshot()
    assert snap["c"] == 3.0 and snap["g"] == 7.0
    assert snap["h_count"] == 4.0 and snap["h_mean"] == 2.5
    assert "c=3" in reg.render()


# ------------------------------------------------------------- zero cost
def test_null_tracer_is_default_and_inert(model_params):
    model, params = model_params
    eng = Engine(model, params, n_slots=2, max_seq=32)
    assert eng.tracer is NULL_TRACER
    assert not NULL_TRACER.enabled
    assert eng._cost_model is None          # record building skipped entirely
    # every hook is a no-op returning None
    req = Request(uid=0, prompt=np.arange(1, 4, dtype=np.int32),
                  max_new_tokens=1)
    assert NULL_TRACER.on_submit(0, req, 0) is None
    assert NULL_TRACER.on_step(None) is None
    assert NULL_TRACER.wall() is None


def test_tracing_never_changes_tokens(model_params):
    model, params = model_params
    plain, _, _ = _serve_traced(model, params, PROMPTS, tracer=NULL_TRACER,
                                schedule="hybrid", prefill_chunk=8)
    traced, _, tracer = _serve_traced(model, params, PROMPTS,
                                      schedule="hybrid", prefill_chunk=8)
    assert tracer.spans                     # actually recorded something
    for a, b in zip(plain, traced):
        assert a.out_tokens == b.out_tokens, a.uid


# --------------------------------------------------------------- cluster
def test_cluster_trace_and_registry(model_params, tmp_path):
    model, params = model_params
    tracer = Tracer()
    cl = Cluster(model, params, 2, route="prefix_affinity", tracer=tracer,
                 n_slots=2, max_seq=32, cache_kind="paged", block_size=8)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        cl.submit(r)
    cstats = cl.run()
    # every request traced on the replica it was placed on
    trees = build_request_trees(tracer)
    assert len(trees) == len(reqs)
    for (replica, uid), tree in trees.items():
        assert cl.placement[uid] == replica
        assert tree.finished and tree.well_formed() == []
    # one route event per request, stamped with the chosen replica
    routes = [e for e in tracer.events if e.name == "route"]
    assert len(routes) == len(reqs)
    for e in routes:
        assert e.attrs["chosen"] == cl.placement[e.uid]
        assert e.attrs["policy"] == "prefix_affinity"
    # both replicas produced at least one complete span tree
    assert {r for r, _ in trees} == {0, 1}
    # trace exports with a cluster row for the router track
    path = write_trace(tracer, tmp_path / "cluster.json")
    obj = json.loads(path.read_text())
    assert validate_trace(obj) == []
    assert any(e["ph"] == "M" and e["args"]["name"] == "cluster"
               for e in obj["traceEvents"])
    # cluster registry pools replica samples for its percentiles
    reg = cluster_registry(cstats)
    snap = reg.snapshot()
    n = sum(len(r.engine.ttft_samples) for r in cstats.replicas)
    assert snap["ttft_steps_count"] == float(n) == float(len(reqs))
    assert snap["ttft_steps_p99"] == cstats.ttft_p99_steps
    assert snap["generated"] == float(cstats.generated)


def test_cluster_stats_zero_guards():
    empty = ClusterStats(rounds=0, replicas=[], spills=0,
                         prefix_hit_tokens=0, probed_tokens=0,
                         queue_wait_sum=0, queue_wait_count=0)
    assert empty.load_imbalance == 1.0
    assert empty.tokens_per_round == 0.0
    assert empty.ttft_p99_steps == 0.0
    assert empty.per_token_percentile(50) == 0.0
    rs = ReplicaStats(replica=0, routed=0, n_slots=2, engine=EngineStats())
    assert rs.utilization(0) == 0.0
    assert rs.routed_share == 0.0


# ------------------------------------------------------------ cost model
def test_dispatch_cost_model_oi_ordering():
    """Decode-only dispatches sit deep in the memory-bound regime; fusing
    a prefill chunk raises operational intensity — the paper's Fig-1
    co-processing premise, reproduced by the analytic model."""
    cfg = reduce_config("llama3.2-1b")
    cm = DispatchCostModel(cfg)
    d_flops, d_bytes = cm.cost(n_decode=4, kv_tokens=400)
    f_flops, f_bytes = cm.cost(n_decode=4, kv_tokens=400, prefill_tokens=16,
                               prefill_ctx_tokens=cm.chunk_ctx_tokens(0, 16))
    assert d_flops > 0 and d_bytes > 0
    assert f_flops > d_flops                # the chunk adds real work
    assert f_flops / f_bytes > d_flops / d_bytes    # ...at higher OI
    assert cm.chunk_ctx_tokens(0, 4) == 1 + 2 + 3 + 4
    assert cm.chunk_ctx_tokens(8, 2) == 9 + 10


def test_step_records_cover_composition(model_params):
    """The step timeline distinguishes dispatch kinds and its budget-fill
    fraction stays in (0, 1]."""
    model, params = model_params
    _, eng, tracer = _serve_traced(model, params, PROMPTS,
                                   schedule="hybrid", prefill_chunk=8)
    kinds = {r.kind for r in tracer.steps}
    assert "decode" in kinds
    assert kinds & {"fused", "solo", "fused2", "solo2"}
    for r in tracer.steps:
        assert 0.0 < r.fill <= 1.0, r
        assert r.oi > 0.0
        assert r.bytes > 0.0
        assert (r.prefill_tokens > 0) == (r.bucket is not None)
    fused = [r for r in tracer.steps if r.kind.startswith("fused")]
    decode = [r for r in tracer.steps if r.kind == "decode"]
    if fused and decode:
        assert max(f.oi for f in fused) > min(d.oi for d in decode)


# ------------------------------------------ phase spans on the device clock
def _phase(events, name):
    return [e for e in events if e.name == name]


@pytest.mark.parametrize("combo", COMBOS,
                         ids=["dense-decode", "dense-hybrid",
                              "paged-decode", "paged-hybrid"])
@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_engine_phase_spans(model_params, profiled, combo, async_mode):
    """Every step path emits the same phase spans: one ``Engine.step`` per
    call; ``Engine.schedule`` before each ``Engine.dispatch``, whose step
    id and kind are the matching ``StepRecord``'s; every prompt-block
    write inside an ``Engine.schedule``; every step whose program returns
    tokens read back once, outside any dispatch."""
    model, params = model_params

    def serve():
        tracer = Tracer()
        eng = Engine(model, params, tracer=tracer, n_slots=2, max_seq=32,
                     async_mode=async_mode, **combo)
        for i, p in enumerate(PROMPTS):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=5))
        calls = 1
        while eng.step():
            calls += 1
        return eng, tracer, calls

    (eng, tracer, calls), events = profiled(serve)
    assert not eng._pending and eng.stats.victim_drains == 0
    steps = _phase(events, "Engine.step")
    schedules = _phase(events, "Engine.schedule")
    dispatches = _phase(events, "Engine.dispatch")
    readbacks = _phase(events, "Engine.readback")
    assert len(steps) == calls
    assert collections.Counter((d.stats["step"], d.stats["kind"]) for d in dispatches) \
        == collections.Counter((r.step, r.kind) for r in tracer.steps)
    for st in steps:
        inner = [d for d in dispatches if d.inside(st)]
        if not inner:
            assert "step" not in st.stats
            continue
        assert (st.stats["step"], st.stats["kind"]) == \
            (inner[-1].stats["step"], inner[-1].stats["kind"])
        assert any(s.inside(st) and s.end <= inner[0].start
                   for s in schedules)
    for e in schedules + dispatches + readbacks:
        assert sum(e.inside(st) for st in steps) == 1, e
    for r in readbacks:
        assert not any(r.inside(d) for d in dispatches), r
    # prompt blocks go to the pool in the schedule phase, whether pushed
    # before the dispatch or, for a finished prompt, after it
    writes = _phase(events, "PjitFunction(_write_block)")
    assert bool(writes) == (combo.get("cache_kind") == "paged")
    for w in writes:
        assert any(w.inside(s) for s in schedules), w
    fetched = {r.step for r in tracer.steps
               if r.decode_batch > 0 or r.kind == "prefill"}
    fetched |= {s.end for s in tracer.spans
                if s.name == "prefill_chunk" and s.attrs["last"]}
    counts = collections.Counter(r.stats["step"] for r in readbacks)
    assert set(counts) == fetched
    assert set(counts.values()) == {1}


def test_untraced_engine_records_no_phase_spans(model_params, profiled):
    model, params = model_params
    (reqs, _, _), events = profiled(lambda: _serve_traced(
        model, params, PROMPTS, tracer=NULL_TRACER, cache_kind="paged",
        block_size=8, schedule="hybrid", prefill_chunk=8))
    assert all(r.done for r in reqs) and events
    assert not [e for e in events if e.name.startswith("Engine.")]
    # one shared no-op span: nothing is allocated per step
    assert NULL_TRACER.phase("Engine.step") is \
        NULL_TRACER.phase("Engine.dispatch", step=1, kind="decode")


def test_step_programs_are_named_by_kind(model_params, profiled):
    """The paged-hybrid async engine's step programs run as the HLO
    modules ``jit_step_<kind>``, ``kind`` as in ``StepRecord.kind``."""
    model, params = model_params
    (_, eng, tracer), events = profiled(lambda: _serve_traced(
        model, params, PROMPTS, cache_kind="paged", block_size=8,
        schedule="hybrid", prefill_chunk=8))
    kinds = {r.kind for r in tracer.steps}
    assert {"decode", "fused", "solo", "solo2"} <= kinds
    modules = {e.stats["hlo_module"] for e in events
               if str(e.stats.get("hlo_module", "")).startswith("jit_step_")}
    assert modules == {f"jit_step_{k}" for k in kinds}
    built = {"decode": eng._decode_sampled, "fused": eng._fused,
             "solo": eng._solo, "fused2": eng._fused2, "solo2": eng._solo2}
    for kind, program in built.items():
        assert program.__name__ == f"step_{kind}"
