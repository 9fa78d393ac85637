"""Reduce a profiler trace to what the per-layer metrics read.

``load`` keeps two event lists from an ``.xplane.pb``: the device's
operations (the TPU plane's op line) and the harness's own host spans
(``TraceAnnotation`` names on the Python thread).  Everything after that
works on the plain lists, so a test can feed a small recorded trace.

* busy: the union of the device-op intervals inside the traced window;
  idle share is one minus busy over the window;
* kernel time: the summed device durations of the ops whose name holds
  a kernel's name;
* breakdown: the ten device ops that took most time, and the ten longest
  idle gaps, each named by the host span that overlapped it most.
"""
from __future__ import annotations

import dataclasses
import json
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "window"          # the host span that brackets the traced window
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    ops: list[tuple[str, int, int]]     # device ops: name, start ns, duration ns
    spans: list[tuple[str, int, int]]   # host spans, same clock

    def window(self) -> tuple[int, int]:
        marks = [(s, s + d) for n, s, d in self.spans if n == WINDOW_SPAN]
        if not marks:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return marks[0]

    def to_json(self, path: Path) -> None:
        Path(path).write_text(json.dumps({"ops": self.ops, "spans": self.spans}))

    @classmethod
    def from_json(cls, path: Path) -> "Trace":
        d = json.loads(Path(path).read_text())
        return cls(ops=[tuple(e) for e in d["ops"]],
                   spans=[tuple(e) for e in d["spans"]])


CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the
    device line names each op by its whole HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(xplane: Path, device_plane: str = "/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(op_name(e.name), int(e.start_ns),
                             int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
    if not ops:
        raise ValueError(f"no {OP_LINE!r} events on {device_plane} in {xplane}")
    return Trace(ops=ops, spans=spans)


def _clip(ev, lo, hi):
    s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
    return (s, e) if e > s else None


def busy_intervals(tr: Trace) -> list[tuple[int, int]]:
    """Union of the device-op intervals inside the window, sorted."""
    lo, hi = tr.window()
    ivs = sorted(iv for iv in (_clip(e, lo, hi) for e in tr.ops) if iv)
    out: list[list[int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_s(tr: Trace) -> float:
    return sum(e - s for s, e in busy_intervals(tr)) / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def kernel_s(tr: Trace, name: str) -> float:
    """Summed device time of the ops whose name holds ``name``."""
    lo, hi = tr.window()
    tot = 0
    for e in tr.ops:
        if name in e[0]:
            iv = _clip(e, lo, hi)
            if iv:
                tot += iv[1] - iv[0]
    return tot / 1e9


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` ops with most device time, by name with the instance
    number dropped; control-flow ops (a layer loop) hold others and are
    left out."""
    lo, hi = tr.window()
    acc: dict[str, int] = defaultdict(int)
    for e in tr.ops:
        base = e[0].split(".", 1)[0]
        if base in CONTAINERS:
            continue
        iv = _clip(e, lo, hi)
        if iv:
            acc[base] += iv[1] - iv[0]
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(tr: Trace, names: tuple[str, ...], n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps in the window, each named by the host
    span among ``names`` that overlaps it most (``other`` if none)."""
    lo, hi = tr.window()
    busy = busy_intervals(tr)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [sp for sp in tr.spans if sp[0] in names]
    out = []
    for g0, g1 in gaps:
        best, cover = "other", 0
        for name, s, d in host:
            ov = min(g1, s + d) - max(g0, s)
            if ov > cover:
                best, cover = name, ov
        out.append([best, (g1 - g0) / 1e9])
    return out
