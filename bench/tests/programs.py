"""The device's program line and the engine's phase spans, read from a
profiler trace of a ``--trace 1`` run: what each step program took on the
device, the idle time between consecutive step programs, and the engine
phase that was open in each idle gap.

``bench/lib/trace.py`` keeps only the device's op line and the host
spans' names; this module keeps the rest of one trace beside it (the
``XLA Modules`` line and the stats of the ``Engine.*`` spans) and reads
both together:

    python bench/tests/programs.py record RUN.xplane.pb OUT.json
    python bench/tests/programs.py read TRACE.json PROGRAMS.json
    python bench/tests/programs.py read RUN.xplane.pb

``record`` writes the program line and the phase stats of an
``.xplane.pb``; ``read`` prints the readings, from a trace written by
``Trace.to_json`` and the file ``record`` wrote for the same run, or
from the ``.xplane.pb`` itself.
"""
from __future__ import annotations

import json
import statistics
import sys
import warnings
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH / "lib")]

import trace as tr  # noqa: E402

PROGRAM_LINE = "XLA Modules"
STEP = "jit_step_"
PHASES = ("Engine.schedule", "Engine.dispatch", "Engine.readback")
# a gap is named by the innermost tier with a span open in it
TIERS = (PHASES, ("Engine.step",), ("engine.step", "submit", "generator wait"))


def record(xplane: Path, device_plane: str = "/device:TPU:0") -> dict:
    """The program line of ``device_plane`` (name without the module's
    fingerprint, start ns, duration ns), and every ``Engine.*`` host span
    that carries a ``step`` or ``kind`` stat, with them."""
    from jax.profiler import ProfileData

    programs, stats = [], []
    with warnings.catch_warnings():     # jaxlib's stats type, py3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        planes = ProfileData.from_file(str(xplane)).planes
        events = {plane.name: [(line.name, [(e.name, e.start_ns, e.duration_ns,
                                             dict(e.stats)) for e in line.events])
                               for line in plane.lines] for plane in planes}
    for plane, lines in events.items():
        if plane == device_plane:
            for line, evs in lines:
                if line == PROGRAM_LINE:
                    programs += [[n.split("(", 1)[0], int(s), int(d)]
                                 for n, s, d, _ in evs]
        elif plane.startswith("/host:"):
            for _, evs in lines:
                for n, s, d, st in evs:
                    st = {k: v for k, v in st.items() if k in ("step", "kind")}
                    if n.startswith("Engine.") and st:
                        stats.append([n, int(s), int(d), st])
    if not programs:
        raise ValueError(f"no {PROGRAM_LINE!r} events on {device_plane}")
    return {"programs": programs, "stats": stats}


def step_programs(programs, trace: tr.Trace) -> list[tuple[str, int, int]]:
    """The ``jit_step_*`` programs wholly inside the traced window, in
    start order: (kind, start ns, end ns).  The device's recording stops
    before the host's window closes, cutting the program then running
    short: a program that ends with the device's last op is left out."""
    lo, hi = trace.window()
    last = max(s + d for _, s, d in trace.ops)
    return sorted((n[len(STEP):], s, s + d) for n, s, d in programs
                  if n.startswith(STEP) and lo <= s and s + d <= hi
                  and s + d < last)


def step_device_ms(programs, trace: tr.Trace, kind: str = "decode") -> float:
    """Median device time of the window's ``jit_step_<kind>`` programs."""
    return statistics.median(
        (e - s) / 1e6 for k, s, e in step_programs(programs, trace) if k == kind)


def step_gaps_ns(programs, trace: tr.Trace) -> list[int]:
    """Device idle between the end of each step program and the start of
    the next; the table pushes between them count as busy."""
    busy = tr.busy_intervals(trace)
    steps = step_programs(programs, trace)
    gaps = []
    for (_, _, a), (_, b, _) in zip(steps, steps[1:]):
        covered = sum(max(0, min(e, b) - max(s, a)) for s, e in busy)
        gaps.append(b - a - covered)
    return gaps


def idle_gaps(trace: tr.Trace) -> list[tuple[int, int]]:
    """Every idle interval of the device inside the window."""
    lo, hi = trace.window()
    gaps, t = [], lo
    for s, e in tr.busy_intervals(trace):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gap_phase(trace: tr.Trace, g0: int, g1: int) -> str:
    """The span open in the gap ``[g0, g1)``: of the innermost tier with
    any span overlapping it, the one that overlaps it most; ``other`` if
    none does."""
    for tier in TIERS:
        best, cover = None, 0
        for name, s, d in trace.spans:
            if name in tier:
                ov = min(g1, s + d) - max(g0, s)
                if ov > cover:
                    best, cover = name, ov
        if best is not None:
            return best
    return "other"


def readings(trace: tr.Trace, data: dict) -> dict:
    programs = data["programs"]
    gaps = step_gaps_ns(programs, trace)
    idle: dict[str, int] = {}
    for g0, g1 in idle_gaps(trace):
        name = gap_phase(trace, g0, g1)
        idle[name] = idle.get(name, 0) + g1 - g0
    longest = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:10]
    return {
        "decode_step_device_ms": step_device_ms(programs, trace),
        "step_gap_ms": statistics.median(gaps) / 1e6,
        "step_programs": len(step_programs(programs, trace)),
        "idle_ms_by_phase": {k: v / 1e6 for k, v in sorted(idle.items())},
        "longest_gaps": [[gap_phase(trace, *g), (g[1] - g[0]) / 1e6]
                         for g in longest],
    }


def main(argv: list[str]) -> None:
    if argv[:1] == ["record"] and len(argv) == 3:
        Path(argv[2]).write_text(json.dumps(record(Path(argv[1]))))
    elif argv[:1] == ["read"] and len(argv) == 3:
        trace = tr.Trace.from_json(Path(argv[1]))
        data = json.loads(Path(argv[2]).read_text())
        print(json.dumps(readings(trace, data), indent=1))
    elif argv[:1] == ["read"] and len(argv) == 2:
        trace, data = tr.load(Path(argv[1])), record(Path(argv[1]))
        print(json.dumps(readings(trace, data), indent=1))
    else:
        sys.exit(__doc__)


if __name__ == "__main__":
    main(sys.argv[1:])
