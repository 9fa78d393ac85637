"""The engine's phase spans and step programs as the benchmark reads
them, on a trace of ``minicpm-2b.decode-long`` recorded on a TPU v5e
(``bench/run.py --trace 1 --keep-trace``, seed 3000001301), with the
program line and phase stats of the same run beside it
(``trace_v5e_steps_programs.json``, written by ``programs.py record``):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "lib"), str(BENCH.parent / "src")]

import layer  # noqa: E402
import programs as pr  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import trace as tr  # noqa: E402

DATA = BENCH / "tests" / "data"
STEPS = tr.Trace.from_json(DATA / "trace_v5e_steps.json")
PROGRAMS = json.loads((DATA / "trace_v5e_steps_programs.json").read_text())
PHASES = pr.PHASES
# the untraced run of the same seed on the same chip
UNTRACED_TOKENS_PER_S, LANES = 16.5, 5


def _read(trace: tr.Trace):
    ctx = layer.Context(shape=None, peak=None, steps=[], trace=trace)
    return spec.metric_reader("engine_host_ms").read(ctx)


def _in_window(trace: tr.Trace, name: str):
    lo, hi = trace.window()
    return [(s, s + d) for n, s, d in trace.spans
            if n == name and lo <= s and s + d <= hi]


def test_each_engine_step_nests_its_phases_in_order():
    """decode-long's window is decode steps only: each ``Engine.step``
    holds one schedule, one dispatch and one readback, in that order,
    inside the harness's ``engine.step``."""
    steps = _in_window(STEPS, "Engine.step")
    harness = _in_window(STEPS, "engine.step")
    assert 12 <= len(steps) <= 14
    for s0, s1 in steps:
        assert any(h0 <= s0 and s1 <= h1 for h0, h1 in harness)
        inner = sorted((a, b, n) for n, a, d in STEPS.spans if n in PHASES
                       for b in [a + d] if s0 <= a and b <= s1)
        assert [n for _, _, n in inner] == list(PHASES)
        assert all(b <= a2 for (_, b, _), (a2, _, _) in zip(inner, inner[1:]))


def test_engine_host_ms_is_step_time_less_blocked_time():
    """The reader against a count of the microseconds of each step in
    which no fetch or runtime wait was open: ``Engine.step`` less its
    blocked time, median over the window's steps, in ms."""
    blocked = spec.metric_reader("engine_host_ms").BLOCKED
    own = []
    for s0, s1 in _in_window(STEPS, "Engine.step"):
        free = np.ones((s1 - s0 + 999) // 1000, bool)
        for n, a, d in STEPS.spans:
            if n in blocked and s0 <= a and a + d <= s1:
                free[(a - s0) // 1000:(a + d - s0 + 999) // 1000] = False
        own.append(free.sum() / 1e3)
    got = _read(STEPS)
    assert abs(got - float(np.median(own))) < 0.05
    # the enqueue waits about a step for the previous step's pool buffers
    # (AllocateBufferAwait); the host's own work is about 1 ms of 301
    waits = [d for n, s, d in STEPS.spans if n == "AllocateBufferAwait"]
    assert len(waits) == 13 and min(waits) > 250e6
    assert 0.5 < got < 2.0


def test_step_programs_pair_with_their_dispatch():
    """Each ``jit_step_decode`` program of the window starts while the
    ``Engine.dispatch`` that enqueued it is open, and that span's ``step``
    and ``kind`` stats name it: the step id on the device's clock."""
    steps = pr.step_programs(PROGRAMS["programs"], STEPS)
    dispatches = [(s, s + d, st) for n, s, d, st in PROGRAMS["stats"]
                  if n == "Engine.dispatch"]
    assert len(steps) == 12 and {k for k, _, _ in steps} == {"decode"}
    ids = []
    for kind, s, _ in steps:
        (st,) = [st for d0, d1, st in dispatches if d0 <= s <= d1]
        assert st["kind"] == kind
        ids.append(st["step"])
    assert ids == list(range(ids[0], ids[0] + len(ids)))


def test_decode_step_time_and_gaps_fit_the_window():
    """Device time per decode program times their number, plus the idle
    between them, fits the window; per step it agrees within 3% with
    the untraced run's step (5 lanes / tokens per second)."""
    steps = pr.step_programs(PROGRAMS["programs"], STEPS)
    decode_ms = pr.step_device_ms(PROGRAMS["programs"], STEPS)
    gaps = pr.step_gaps_ns(PROGRAMS["programs"], STEPS)
    assert len(gaps) == len(steps) - 1
    assert decode_ms * len(steps) + sum(gaps) / 1e6 <= tr.window_s(STEPS) * 1e3
    gap_ms = float(np.median(gaps)) / 1e6
    assert 299 < decode_ms < 302 and 1.0 < gap_ms < 2.0
    untraced_ms = LANES * 1e3 / UNTRACED_TOKENS_PER_S
    assert abs(decode_ms + gap_ms - untraced_ms) / untraced_ms < 0.03


def test_idle_gaps_are_named_by_the_innermost_phase():
    """The breakdown's ten longest gaps, named by the engine phase open
    in each rather than by the harness's ``engine.step`` around it: the
    enqueue that waits for the previous step's buffers holds nine."""
    got = pr.readings(STEPS, PROGRAMS)["longest_gaps"]
    harness = tr.idle_gaps(STEPS, run.SPANS)
    assert np.allclose([d / 1e3 for _, d in got], [d for _, d in harness])
    assert {n for n, _ in harness} == {"engine.step"}
    names = [n for n, _ in got]
    assert set(names) <= set(PHASES)
    assert names.count("Engine.dispatch") >= 9
    for g0, g1 in pr.idle_gaps(STEPS):
        assert pr.gap_phase(STEPS, g0, g1) in set(PHASES) | {"other"}


def test_a_trace_without_phase_spans_still_loads_and_reads_nothing():
    old = tr.Trace.from_json(DATA / "trace_v5e.json")
    assert old.ops and tr.busy_s(old) > 0
    assert _read(old) is None
