"""Serving observatory: sampled dispatch profiler (measured MFU/MBU
joins, zero-cost NULL profiler, token identity across every combo),
SLO attainment arithmetic (hand-built span replay, breach marks),
workload generator determinism and shapes, percentile edge cases, the
terminal dashboard, and the bench trend report."""
import json

import jax
import numpy as np
import pytest

from repro.configs.reduced import reduce_config
from repro.core.oi import DEVICES
from repro.core.placement import Env
from repro.models.registry import build_model
from repro.serving.cluster import Cluster
from repro.serving.cluster.stats import ClusterStats, ReplicaStats
from repro.serving.engine import Engine, EngineStats, Request
from repro.serving.telemetry import (
    NULL_PROFILER,
    DispatchProfiler,
    MetricsRegistry,
    SLOMonitor,
    Span,
    Tracer,
    cluster_registry,
    device_peaks,
    make_profiler,
    percentile,
    render_dashboard,
    to_chrome_trace,
    validate_trace,
)
from repro.serving.workload import (
    WORKLOADS,
    WorkloadDriver,
    build_workload,
    grow_prompt,
)


@pytest.fixture(scope="module")
def model_params():
    cfg = reduce_config("llama3.2-1b")
    model = build_model(cfg, Env())
    return model, model.init(jax.random.key(0))


VOCAB = reduce_config("llama3.2-1b").vocab

PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(7, 10, dtype=np.int32),
           np.arange(2, 13, dtype=np.int32),
           np.arange(4, 25, dtype=np.int32)]      # multi-chunk

COMBOS = [
    dict(),                                                   # dense/decode-only
    dict(schedule="hybrid", prefill_chunk=8),                 # dense/hybrid
    dict(cache_kind="paged", block_size=8),                   # paged/decode-only
    dict(cache_kind="paged", block_size=8,
         schedule="hybrid", prefill_chunk=8),                 # paged/hybrid
]
COMBO_IDS = ["dense-decode", "dense-hybrid", "paged-decode", "paged-hybrid"]


def _serve(model, params, prompts, n_new=5, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq", 32)
    eng = Engine(model, params, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs, eng


# ---------------------------------------------------------------- percentiles
def test_percentile_empty_is_zero():
    assert percentile([], 50) == 0.0
    assert percentile([], 99) == 0.0


def test_percentile_single_sample_every_p():
    for p in (0, 1, 50, 90, 99, 100):
        assert percentile([7.0], p) == 7.0


def test_percentile_exact_nearest_rank():
    s = list(range(1, 11))                      # 1..10
    assert percentile(s, 50) == 5
    assert percentile(s, 90) == 9
    assert percentile(s, 99) == 10
    assert percentile(s, 100) == 10
    assert percentile(s, 10) == 1


def test_percentile_clamps_out_of_range_p():
    s = [1.0, 2.0, 3.0]
    assert percentile(s, -5) == 1.0
    assert percentile(s, 150) == 3.0


def test_empty_histogram_snapshot():
    reg = MetricsRegistry()
    reg.histogram("ttft_steps")                 # zero samples
    snap = reg.snapshot()
    assert snap["ttft_steps_count"] == 0.0
    assert snap["ttft_steps_p99"] == 0.0
    reg.histogram("one").observe(4.0)           # single sample
    snap = reg.snapshot()
    assert snap["one_p50"] == 4.0 and snap["one_p99"] == 4.0


def test_cluster_registry_zero_finished_requests():
    """Pooled cluster percentiles must snapshot with zero finished
    requests on every replica (empty sample lists everywhere)."""
    stats = ClusterStats(
        rounds=0,
        replicas=[ReplicaStats(replica=0, routed=0, n_slots=2,
                               engine=EngineStats(), role="mixed")],
        spills=0, prefix_hit_tokens=0, probed_tokens=0,
        queue_wait_sum=0, queue_wait_count=0,
    )
    snap = cluster_registry(stats).snapshot()
    assert snap["ttft_steps_count"] == 0.0
    assert snap["ttft_steps_p99"] == 0.0
    assert stats.ttft_percentile(99) == 0.0


# ------------------------------------------------------------------ workloads
@pytest.mark.parametrize("kind", WORKLOADS)
def test_workload_deterministic_by_seed(kind):
    a = build_workload(kind, 12, vocab=VOCAB, max_seq=32, max_new=4, seed=3)
    b = build_workload(kind, 12, vocab=VOCAB, max_seq=32, max_new=4, seed=3)
    c = build_workload(kind, 12, vocab=VOCAB, max_seq=32, max_new=4, seed=4)
    assert len(a) == len(b) == 12
    for x, y in zip(a, b):
        assert x.round == y.round
        assert np.array_equal(x.prompt, y.prompt)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("kind", WORKLOADS)
def test_workload_admissible_and_sorted(kind):
    arr = build_workload(kind, 12, vocab=VOCAB, max_seq=32, max_new=4, seed=0)
    rounds = [a.round for a in arr]
    assert rounds == sorted(rounds)
    for a in arr:
        assert len(a.prompt) + a.max_new_tokens <= 30     # max_seq - 2
        assert a.prompt.dtype == np.int32
        assert (a.prompt >= 1).all() and (a.prompt < VOCAB).all()
    if kind == "random":
        assert all(r == 0 for r in rounds)                # legacy shape


def test_chat_fan_shares_prefixes():
    arr = build_workload("chat-fan", 8, vocab=VOCAB, max_seq=32, max_new=4,
                         seed=0, fan=4)
    # at least one pair shares a long common prefix
    shared = 0
    for i in range(len(arr)):
        for j in range(i + 1, len(arr)):
            a, b = arr[i].prompt, arr[j].prompt
            n = min(len(a), len(b))
            if n >= 4 and np.array_equal(a[:4], b[:4]):
                shared += 1
    assert shared >= 3


def test_grow_prompt_tail_clips():
    prompt = np.arange(1, 20, dtype=np.int32)
    grown = grow_prompt(prompt, [100, 101, 102], np.array([7, 8], np.int32),
                        max_seq=24, max_new=4)
    assert len(grown) == 18                    # max_seq - max_new - 2
    # tail window: the newest tokens survive the clip
    assert grown[-1] == 8 and grown[-2] == 7 and 102 in grown


def test_workload_driver_agentic_resubmits(model_params):
    model, params = model_params
    eng = Engine(model, params, n_slots=2, max_seq=32,
                 schedule="hybrid", prefill_chunk=8)
    arr = build_workload("agentic", 2, vocab=VOCAB, max_seq=32, max_new=4,
                         seed=0, turns=3)
    drv = WorkloadDriver(eng, arr, vocab=VOCAB, max_seq=32, seed=0)
    rounds = drv.run()
    assert rounds > 0
    assert drv.resubmits == 4                  # 2 sessions x (3 - 1) turns
    assert len(drv.submitted) == 6
    assert all(r.done for r in drv.submitted)
    assert eng.stats.generated == 6 * 4


def test_workload_driver_arrivals_respect_rounds(model_params):
    model, params = model_params
    eng = Engine(model, params, n_slots=2, max_seq=32,
                 schedule="hybrid", prefill_chunk=8)
    arr = build_workload("poisson", 4, vocab=VOCAB, max_seq=32, max_new=3,
                         seed=1, rate=0.25)
    drv = WorkloadDriver(eng, arr, vocab=VOCAB, max_seq=32, seed=1)
    rounds = drv.run()
    assert rounds >= max(a.round for a in arr)
    assert all(r.done for r in drv.submitted)


# ------------------------------------------------------------------- profiler
def test_null_profiler_zero_cost(model_params):
    model, params = model_params
    eng = Engine(model, params, n_slots=2, max_seq=32)
    assert eng.profiler is NULL_PROFILER
    assert eng._telemetry is False
    assert eng._cost_model is None
    assert make_profiler(0) is NULL_PROFILER
    assert NULL_PROFILER.tick() is False
    assert NULL_PROFILER.samples == ()


def test_profiler_validates_sample_every():
    with pytest.raises(ValueError):
        DispatchProfiler(sample_every=0)
    assert DispatchProfiler(sample_every=1).sync
    assert not DispatchProfiler(sample_every=4).sync


@pytest.mark.parametrize("combo", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_profiler_token_identity(model_params, combo, async_mode):
    """Greedy outputs are bit-identical with the profiler on: fencing
    changes timing, never tokens."""
    model, params = model_params
    base, _ = _serve(model, params, PROMPTS, async_mode=async_mode, **combo)
    prof = DispatchProfiler(sample_every=2)
    with_prof, eng = _serve(model, params, PROMPTS, async_mode=async_mode,
                            profiler=prof, **combo)
    for b, w in zip(base, with_prof):
        assert b.out_tokens == w.out_tokens
    assert len(prof.samples) > 0
    assert eng._telemetry and eng._cost_model is not None


def test_profiler_joins_measured_with_analytic(model_params):
    model, params = model_params
    prof = DispatchProfiler(sample_every=1, device="TPU-V5E")
    _, eng = _serve(model, params, PROMPTS[:2], schedule="hybrid",
                    prefill_chunk=8, profiler=prof)
    assert len(prof.samples) == eng.stats.engine_steps   # sync: every step
    dev = DEVICES["TPU-V5E"]
    for s in prof.samples:
        assert s.seconds > 0
        assert s.measured_mfu == pytest.approx(
            s.flops / (s.seconds * dev.flops))
        assert s.measured_mbu == pytest.approx(
            s.bytes / (s.seconds * dev.bw))
        assert s.achieved_gbps == pytest.approx(s.bytes / s.seconds / 1e9)
    summary = prof.summary()
    assert summary and all(row["n"] >= 1 for row in summary.values())
    reg = MetricsRegistry()
    prof.register(reg)
    snap = reg.snapshot()
    assert snap["profiled_dispatches"] == len(prof.samples)
    assert snap["measured_mbu"] > 0
    assert snap["dispatch_seconds_count"] == len(prof.samples)


def test_profiler_without_device_peaks_reports_not_measured(model_params):
    """Off the TPU no peaks apply: seconds and bandwidth are kept, and
    MFU/MBU stay unset everywhere they would be reported."""
    model, params = model_params
    assert device_peaks() is None            # the tests run on the CPU
    prof = DispatchProfiler(sample_every=1)
    assert prof.device is None
    tracer = Tracer()
    _, eng = _serve(model, params, PROMPTS[:2], schedule="hybrid",
                    prefill_chunk=8, profiler=prof, tracer=tracer)
    assert prof.samples and all(
        s.seconds > 0 and s.measured_mfu is None and s.measured_mbu is None
        for s in prof.samples)
    assert all("measured_mfu" not in row for row in prof.summary().values())
    reg = MetricsRegistry()
    prof.register(reg)
    snap = reg.snapshot()
    assert "measured_mfu" not in snap and snap["achieved_gbps"] > 0
    assert "not measured" in prof.describe()
    names = {e["name"] for e in to_chrome_trace(tracer)["traceEvents"]
             if e["ph"] == "C"}
    assert "achieved_gbps" in names and "measured_mfu" not in names


def test_device_peaks_from_device_kind():
    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert device_peaks(_Dev("tpu", "TPU v5 lite")) is DEVICES["TPU-V5E"]
    assert device_peaks(_Dev("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="no peaks"):
        device_peaks(_Dev("tpu", "TPU v99"))


def test_profiler_sampling_rate(model_params):
    """sample_every=N fences ~1/N of dispatches, and unsampled steps
    carry no measured fields."""
    model, params = model_params
    prof = DispatchProfiler(sample_every=3)
    tracer = Tracer()
    _, eng = _serve(model, params, PROMPTS, schedule="hybrid",
                    prefill_chunk=8, profiler=prof, tracer=tracer)
    n_steps = eng.stats.engine_steps
    assert len(prof.samples) == sum(
        1 for rec in tracer.steps
        if rec.kind != "prefill" and rec.measured_s is not None
    )
    assert 0 < len(prof.samples) <= n_steps // 3 + 1
    unmeasured = [r for r in tracer.steps if r.measured_s is None]
    assert all(r.measured_mfu is None for r in unmeasured)


def test_measured_counter_tracks_in_trace(model_params):
    model, params = model_params
    tracer = Tracer(wall=True)
    # explicit peaks: the CPU has none, and the tracks under test need them
    prof = DispatchProfiler(sample_every=2, device="TPU-V5E")
    _, _ = _serve(model, params, PROMPTS, schedule="hybrid",
                  prefill_chunk=8, tracer=tracer, profiler=prof)
    obj = to_chrome_trace(tracer)
    assert validate_trace(obj) == []
    counters = {}
    last_ts = {}
    for e in obj["traceEvents"]:
        if e["ph"] != "C":
            continue
        counters[e["name"]] = counters.get(e["name"], 0) + 1
        key = (e["pid"], e["name"])
        assert e["ts"] >= last_ts.get(key, -1)      # monotone per series
        last_ts[key] = e["ts"]
    for name in ("measured_mfu", "measured_mbu", "achieved_gbps"):
        assert counters.get(name, 0) == len(prof.samples)
    # sampled only: fewer measured points than oi points
    assert counters["measured_mfu"] < counters["oi"]


def test_profiler_through_cluster(model_params):
    model, params = model_params
    prof = DispatchProfiler(sample_every=2)
    cl = Cluster(model, params, 2, profiler=prof, n_slots=2, max_seq=32,
                 schedule="hybrid", prefill_chunk=8)
    for i, p in enumerate(PROMPTS):
        cl.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    cl.run()
    assert all(e.profiler is prof for e in cl.engines)
    assert len(prof.samples) > 0
    assert {s.replica for s in prof.samples} <= {0, 1}


# ------------------------------------------------------------------------ slo
def _span(uid, name, start, end, generated=None, track=0):
    attrs = {} if generated is None else {"generated": generated}
    return Span(replica=0, track=track, uid=uid, name=name,
                start=start, end=end, attrs=attrs)


def test_slo_from_spans_exact_arithmetic():
    """Hand-built span set with known TTFT/TPOT values: u0 attains both,
    u1 breaches TTFT, u2 breaches TPOT, u3 never finished (skipped)."""
    spans = [
        _span(0, "queued", 0, 1), _span(0, "decode", 2, 10, generated=5),
        _span(1, "queued", 0, 6), _span(1, "decode", 8, 12, generated=5),
        _span(2, "queued", 1, 2), _span(2, "decode", 3, 23, generated=5),
        _span(3, "queued", 4, None),
    ]
    mon = SLOMonitor.from_spans(spans, ttft_target=4, tpot_target=3)
    assert mon.finished == 3
    # u0: ttft 2, tpot 8/4=2 -> attains; u1: ttft 8 breach, tpot 1 ok;
    # u2: ttft 2 ok, tpot 20/4=5 breach
    assert mon.attained_count == 1
    assert mon.attainment == pytest.approx(1 / 3)
    assert mon.window_attainment == pytest.approx(1 / 3)
    assert mon.breaches == 2
    assert mon.good_tokens == 5 and mon.total_tokens == 15
    assert mon.goodput(10) == pytest.approx(0.5)
    assert sorted(mon.ttft_samples) == [2, 2, 8]
    assert sorted(mon.tpot_samples) == [1.0, 2.0, 5.0]
    assert mon.ttft_percentile(50) == 2 and mon.ttft_percentile(99) == 8


def test_slo_from_spans_preemption_uses_first_decode():
    """A preempted request re-opens its decode span; TTFT must come from
    the *earliest* decode start, TPOT from the final end."""
    spans = [
        _span(0, "queued", 0, 1),
        _span(0, "decode", 2, 5, generated=2),     # before preemption
        _span(0, "decode", 9, 15, generated=6),    # re-admitted
    ]
    mon = SLOMonitor.from_spans(spans, ttft_target=3, tpot_target=10)
    assert mon.finished == 1
    assert list(mon.ttft_samples) == [2]           # 2 - 0, not 9 - 0
    assert list(mon.tpot_samples) == [pytest.approx((15 - 2) / 5)]
    assert mon.attained_count == 1


def test_slo_unset_targets_always_attain():
    mon = SLOMonitor()
    mon.observe_ttft(0, 100.0)
    mon.observe_finish(0, 50.0, tokens=3)
    assert mon.attainment == 1.0 and mon.breaches == 0


def test_slo_register_publishes_goodput():
    mon = SLOMonitor(ttft_target=2, tpot_target=1)
    mon.observe_ttft(0, 1.0)
    mon.observe_finish(0, 0.5, tokens=8)
    mon.observe_ttft(1, 9.0)                       # breach
    mon.observe_finish(1, 0.5, tokens=8)
    reg = MetricsRegistry()
    mon.register(reg, elapsed=16)
    snap = reg.snapshot()
    assert snap["slo_ttft_target"] == 2.0
    assert snap["slo_finished"] == 2.0
    assert snap["slo_attained"] == 1.0
    assert snap["slo_breaches"] == 1.0
    assert snap["slo_attainment"] == 0.5
    assert snap["slo_goodput_tokens_per_round"] == 0.5
    assert snap["slo_ttft_count"] == 2.0


def test_slo_breach_marks_in_trace(model_params):
    """A tight TTFT target under queued load must drop slo_breach marks
    the trace check can gate on, without changing tokens."""
    model, params = model_params
    base, _ = _serve(model, params, PROMPTS, schedule="hybrid",
                     prefill_chunk=8)
    slo = SLOMonitor(ttft_target=0, tpot_target=0.1)    # unattainable
    tracer = Tracer(wall=True, slo=slo)
    monitored, _ = _serve(model, params, PROMPTS, schedule="hybrid",
                          prefill_chunk=8, tracer=tracer)
    for b, w in zip(base, monitored):
        assert b.out_tokens == w.out_tokens
    assert slo.finished == len(PROMPTS)
    assert slo.attainment == 0.0
    obj = to_chrome_trace(tracer)
    marks = [e for e in obj["traceEvents"]
             if e["ph"] == "i" and e["name"] == "slo_breach"]
    assert len(marks) >= len(PROMPTS)
    for m in marks:
        assert m["args"]["metric"] in ("ttft", "tpot")
        assert m["args"]["value"] > m["args"]["target"]


def test_tracer_wall_dispatch_annotations(model_params, profiled):
    """Async spans close at observe time, a step after their dispatch; the
    engine's phase spans show the overlap on the profiler's clock: a
    step's tokens are read back only once a later step's program is
    enqueued, except in the drain at the end."""
    model, params = model_params
    tracer = Tracer(wall=True)
    _, events = profiled(lambda: _serve(
        model, params, PROMPTS[:2], schedule="hybrid", prefill_chunk=8,
        async_mode=True, tracer=tracer))
    steps = [e for e in events if e.name == "Engine.step"]
    dispatches = [e for e in events if e.name == "Engine.dispatch"]
    readbacks = [e for e in events if e.name == "Engine.readback"]
    ahead = 0
    for r in readbacks:
        if any(d.stats["step"] > r.stats["step"] and d.end <= r.start
               for d in dispatches):
            ahead += 1
            continue
        (st,) = [st for st in steps if r.inside(st)]
        assert "step" not in st.stats, "read back before the next dispatch"
    assert ahead >= 1
    assert [s for s in tracer.spans if s.name == "decode" and s.closed]
    for s in tracer.spans:
        if s.t_start is not None and s.t_end is not None:
            assert s.t_start <= s.t_end


# -------------------------------------------------------------- dashboard
def test_dashboard_renders_engine_and_cluster(model_params):
    model, params = model_params
    prof = DispatchProfiler(sample_every=2)
    slo = SLOMonitor(ttft_target=3)
    cl = Cluster(model, params, 2, profiler=prof,
                 n_slots=2, max_seq=32, cache_kind="paged", block_size=8,
                 schedule="hybrid", prefill_chunk=8)
    for i, p in enumerate(PROMPTS):
        cl.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    cl.run()
    out = render_dashboard(cl, 7, slo=slo, profiler=prof)
    assert "[round 7]" in out and "global_queue=" in out
    assert "r0[M]" in out and "r1[M]" in out and "pool=" in out
    assert "slo[" in out and "measured[" in out
    solo = render_dashboard(cl.engines[0], 1)
    assert "r0[M]" in solo and "global_queue" not in solo


# ------------------------------------------------------------ bench report
def test_bench_report_trend_and_drift(tmp_path):
    import sys
    sys.path.insert(0, "scripts")
    try:
        import bench_report
    finally:
        sys.path.pop(0)
    (tmp_path / "BENCH_1.json").write_text(json.dumps(
        {"b": {"x": 1.0, "y": 5.0}}))
    (tmp_path / "BENCH_2.json").write_text(json.dumps(
        {"b": {"x": 2.0, "y": 5.0}, "c": {"z": 3.0}}))
    (tmp_path / "BENCH_ci.json").write_text(json.dumps(
        {"metrics": {"x": 2.0}}))
    snaps = bench_report.load_snapshots(tmp_path)
    assert [n for n, _ in snaps] == [1, 2]
    report = bench_report.render(snaps, drift_pct=25.0,
                                 ci=json.loads(
                                     (tmp_path / "BENCH_ci.json").read_text()))
    assert "b.x" in report and "c.z" in report
    assert "DRIFTS" in report and "b.x: 1 -> 2 (+100.0%)" in report
    assert "b.y" in report and "b.y: " not in report.split("DRIFTS")[1]
    out = tmp_path / "report.txt"
    assert bench_report.main(["--root", str(tmp_path),
                              "--out", str(out)]) == 0
    assert out.read_text() == report
    assert bench_report.main(["--root", str(tmp_path / "empty")]) == 1
