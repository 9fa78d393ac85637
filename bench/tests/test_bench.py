"""Tests of the benchmark itself, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

* the cell files are found by name, and every per-layer metric has its
  reader;
* the trace reduction gives the busy, idle and kernel times of a small
  trace recorded on a TPU v5e, cross-checked by a plain timeline;
* a rehearsal (reduced size, kernels in interpret mode) of every cell
  drives a whole run and prints the result line's keys and no metric;
* with no TPU a run exits 2 and prints nothing on standard output;
* the control (the reference in float8) and each planted fault fail the
  cell's limit;
* on fixed requests, stepped without the wall clock, the synchronous
  engine stays within the limit (the witness for the async fault that
  PERF.md records).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "lib"), str(BENCH.parent / "src")]

import run  # noqa: E402
import spec  # noqa: E402
import trace as tr  # noqa: E402
import traffic  # noqa: E402

SPEC = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
RECORDED = BENCH / "tests" / "data" / "trace_v5e.json"


def rehearse(cell: str, seed: int = 2**31 + 5, seconds: float = 2.0, trace=0):
    ns = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                            trace=trace, rehearse=True, keep_trace=None)
    return run.run(ns)


def test_cells_found_by_name():
    for name in CELLS:
        cell = spec.load_cell(name)
        assert cell.config["name"] == name.split(".")[0]
        assert {"n_slots", "max_seq", "n_blocks", "check"} <= set(cell.engine)
        for m in cell.per_layer:
            assert hasattr(spec.metric_reader(m["name"]), "read")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        spec.peaks("no such chip")


def test_stratified_lengths_are_the_same_set_for_every_seed():
    mix = json.loads((BENCH / "traffic" / "decode-long.json").read_text())
    n = int(mix.get("strata", traffic.STRATA))
    a = traffic.Generator(mix, 1, 1000)
    b = traffic.Generator(mix, 2**33 + 1, 1000)
    la = sorted(len(a.next().prompt) for _ in range(n))
    lb = sorted(len(b.next().prompt) for _ in range(n))
    assert la == lb
    assert mix["prompt"]["min"] <= la[0] and la[-1] <= mix["prompt"]["max"]


def test_opening_round_is_the_same_for_every_seed():
    mix = json.loads((BENCH / "traffic" / "decode-long.json").read_text())
    rounds = []
    for seed in (1, 2**33 + 1):
        g = traffic.Generator(mix, seed, 1000, first=5)
        rounds.append([(len(i.prompt), i.max_new) for i in
                       (g.next() for _ in range(5))])
    assert rounds[0] == rounds[1]
    assert rounds[0][0] == max(rounds[0])


def test_open_loop_window_draws_one_set():
    """With ``strata`` set to rate x window seconds, a window's first
    ``strata`` arrivals are one whole stratified set for every seed."""
    mix = {"loop": "open", "rate": 0.6, "strata": 18, "greedy": True,
           "prompt": {"dist": "lognormal", "median": 2560, "sigma": 0.4,
                      "min": 1536, "max": 3840},
           "output": {"dist": "uniform", "min": 16, "max": 128}}
    traffic.check_mix(mix)
    sets = []
    for seed in (3, 2**31 + 7):
        g = traffic.Generator(mix, seed, 1000)
        items = [g.next() for _ in range(18)]
        sets.append((sorted(len(i.prompt) for i in items),
                     sorted(i.max_new for i in items),
                     sorted(round(i.gap_s, 9) for i in items)))
    assert sets[0] == sets[1]
    assert sum(sets[0][2]) == pytest.approx(18 / 0.6, rel=0.15)


def _timeline_busy(t: tr.Trace) -> float:
    lo, hi = t.window()
    us = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in t.ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            us[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return us.sum() / 1e6


def test_trace_reduction_on_a_recorded_trace():
    t = tr.Trace.from_json(RECORDED)
    w, busy = tr.window_s(t), tr.busy_s(t)
    assert 0 < busy <= w
    assert busy == pytest.approx(_timeline_busy(t), abs=2e-6 * len(t.ops) + 1e-4)
    gaps = tr.idle_gaps(t, run.SPANS)
    assert sum(g for _, g in gaps) <= w - busy + 1e-9
    assert all(name in run.SPANS + ("other",) for name, _ in gaps)
    top = tr.top_ops(t)
    assert len(top) <= 10 and top == sorted(top, key=lambda r: -r[1])
    import layer
    k = tr.kernel_s(t, layer.PAGED_KERNEL)
    assert 0 < k <= sum(d for _, d in top) + busy


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_keys(cell, capsys):
    """Keys and types only: at this size the async engine sometimes
    serves a token far below the reference's best (PERF.md, Open
    questions), so ``correct`` is read, not required."""
    rc, res = rehearse(cell)
    assert rc == 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "checks"
    assert isinstance(res["correct"], bool) and res["metrics"] == {}
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"


def test_no_tpu_exits_without_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_control_fails_the_limit():
    """The reference in float8 in the program's place, at every position
    the program served on fixed requests: on every seed the gap of the token it puts first exceeds the cell's
    limit.  At width 256 (4 layers) the control's rounding shows as it
    does at full width on the chip."""
    limit = spec.load_cell(CELLS[0]).engine["check"]["logit_gap_max"]
    for seed in (11, 12, 13):
        cell, sample = fixed_requests(seed, async_mode=True)
        gaps = run.logit_gaps(cell, seed, sample, "fp8")
        assert max(g.max() for g in gaps) > limit


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every sampled id that is a
    multiple of 5 becomes its neighbour."""
    from repro.models import dense
    from repro.serving import engine, sampler

    real = sampler.sample_on_device

    def altered(logits, rng, cfg):
        tok = real(logits, rng, cfg)
        return tok + (tok % 5 == 0).astype(tok.dtype)

    monkeypatch.setattr(engine, "sample_on_device", altered)
    monkeypatch.setattr(dense, "sample_on_device", altered)


def _drop_prompt_kv(monkeypatch):
    """A step that leaves its state unchanged: prompt blocks are never
    written to the pool."""
    from repro.serving.paged import device

    monkeypatch.setattr(device, "write_prompt_block",
                        lambda cache, *a, **k: cache)


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
@pytest.mark.parametrize("fault", [_alter_tokens, _drop_prompt_kv])
def test_planted_fault_is_not_correct(fault, seed, monkeypatch):
    fault(monkeypatch)
    rc, res = rehearse(CELLS[0], seed=seed)
    assert rc == 0 and res["correct"] is False


def fixed_requests(seed: int, async_mode: bool, steps: int = 200):
    """The harness's own closed loop stepped a fixed number of times (no
    wall clock, so both engine modes serve the same requests) at width
    256 and 4 layers; returns the cell and every request served, as
    (prompt, served tokens)."""
    from load import Load
    from repro.serving.engine import Request

    base = spec.load_cell(CELLS[0])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(base.family, "REHEARSAL",
                   {**base.family.REHEARSAL, "hidden_size": 256,
                    "num_hidden_layers": 4, "intermediate_size": 512,
                    "vocab_size": 2000})
        cell = run.rehearsal_cell(base)
    run.setup_jax(True)
    shape, eng = run.build(cell, seed, None, async_mode=async_mode)
    load = Load(eng, Request, cell.traffic, seed, shape.vocab,
                cell.engine["n_slots"])
    load.start(0.0)
    for _ in range(steps):
        load._tick()
    while eng.step():
        load._stamp()
    return cell, [(np.asarray(t.req.prompt), np.asarray(t.req.out_tokens))
                  for t in load.all if t.req.out_tokens]


def fixed_request_gap(seed: int, async_mode: bool) -> float:
    """The widest gap below the reference's best logit, over every token
    served."""
    cell, sample = fixed_requests(seed, async_mode)
    return float(max(g.max() for g in run.logit_gaps(cell, seed, sample)))


def test_fixed_requests_without_async_match_the_reference(capsys):
    """Witness for PERF.md's first open question: on the same requests,
    the synchronous engine stays within the cell's limit of the reference
    while the async pipeline (printed, not asserted) has served a token
    far below it on this seed."""
    limit = spec.load_cell(CELLS[0]).engine["check"]["logit_gap_max"]
    sync = fixed_request_gap(14, async_mode=False)
    dispatch_ahead = fixed_request_gap(14, async_mode=True)
    with capsys.disabled():
        print(f"\nseed 14, fixed requests: async_mode=False gap {sync:.4f}, "
              f"async_mode=True gap {dispatch_ahead:.4f}, limit {limit}")
    assert sync <= limit


def test_warm_chunks_leave_nothing_to_compile_for_a_returning_client():
    """After ``Load.warm_chunks``, a prompt of any length the chunk
    buckets cover, prefilled beside a decoding lane as a client that
    returns inside the window is, runs only programs already in memory."""
    from compile_log import CompileLog
    from load import Load
    from repro.serving.engine import Request
    from repro.serving.scheduler import chunk_buckets

    cell = run.rehearsal_cell(spec.load_cell(CELLS[0]))
    run.setup_jax(True)
    shape, eng = run.build(cell, 31, None)
    clog = CompileLog()
    load = Load(eng, Request, cell.traffic, 31, shape.vocab, 1)
    chunk = cell.engine["prefill_chunk"]
    load.warm_chunks(chunk_buckets(chunk))
    rng = np.random.default_rng(31)
    lane = Request(uid=1, prompt=rng.integers(1, shape.vocab, chunk, dtype=np.int32),
                   max_new_tokens=3 * chunk)
    eng.submit(lane)
    while not lane.out_tokens:
        eng.step()
    n0 = clog.mark()[0]
    for n in (chunk + 1, chunk + chunk // 2, chunk + chunk // 2 + 1, 2 * chunk):
        req = Request(uid=1 + n, prompt=rng.integers(1, shape.vocab, n, dtype=np.int32),
                      max_new_tokens=1)
        eng.submit(req)
        while not req.done:
            eng.step()
        assert not lane.done
    assert clog.names[n0:] == []
