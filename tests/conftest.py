import dataclasses
import os
import warnings

# keep tests at 1 device — the 512-device override belongs ONLY to dryrun.py
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@dataclasses.dataclass
class TraceEvent:
    """One event of a profiler trace: a host span or, on the CPU, an op."""

    name: str
    start: int          # ns, the profiler's clock
    end: int
    stats: dict

    def inside(self, other: "TraceEvent") -> bool:
        return other.start <= self.start and self.end <= other.end


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn()`` under the JAX profiler and returns
    its result with every event of the trace, in start order."""
    from jax.profiler import ProfileData

    def run(fn):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0            # annotations, not calls
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            out = fn()
        (pb,) = tmp_path.glob("**/*.xplane.pb")
        with warnings.catch_warnings():     # jaxlib's stats type, py3.12
            warnings.simplefilter("ignore", DeprecationWarning)
            events = [TraceEvent(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns), dict(e.stats))
                      for plane in ProfileData.from_file(str(pb)).planes
                      for line in plane.lines for e in line.events]
        return out, sorted(events, key=lambda e: e.start)

    return run
