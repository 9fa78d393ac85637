"""Sampled per-dispatch wall-clock profiler: the measured half of Fig 8.

The step timeline (``timeline.py``) charges every dispatch *analytic*
FLOPs/bytes from the roofline model; nothing there measures what the
hardware actually achieved.  :class:`DispatchProfiler` closes that gap
by timing a **sample** of dispatches between two
``jax.block_until_ready`` fences and joining the measured seconds with
the dispatch's analytic cost:

* ``measured_mfu``  = flops / (seconds * device peak FLOP/s)
* ``measured_mbu``  = bytes / (seconds * device peak HBM B/s)
* ``achieved_gbps`` = bytes / seconds / 1e9

The peaks come from the device the run is on (:func:`device_peaks`,
keyed by ``device_kind``).  A backend with no peaks in the table (the
CPU) keeps the seconds and leaves MFU/MBU unset: not measured.

Sampling contract
-----------------
Fencing a dispatch drains the async dispatch-ahead pipeline (the *pre*
fence waits out all previously dispatched steps so queued work is not
billed to this one; the *post* fence waits for this dispatch alone), so
timing **every** step would serialize the engine back to sync mode.  The
profiler therefore fences only every ``sample_every``-th dispatch —
``sample_every=1`` is the sync mode that times every dispatch — and the
unsampled majority keep full overlap.  The measured interval covers one
step's host-side composition plus its device execution, which is exactly
the per-dispatch cost the paper's utilization figures are about.

The profiler never touches tokens, RNG, or scheduler state: greedy
outputs are bit-identical with it enabled (pinned by
``tests/test_observatory.py``).  Engines default to
:data:`NULL_PROFILER`, whose hooks are no-ops and whose
``enabled = False`` lets the engine skip the per-dispatch bookkeeping
entirely — the same zero-cost contract as :data:`~repro.serving.telemetry.tracer.NULL_TRACER`.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from repro.core.oi import DEVICE_KINDS, DEVICES, Device


def device_peaks(device=None) -> Device | None:
    """Peak FLOP/s and HBM bytes/s of ``device`` (default: the first
    device JAX sees), looked up by its ``device_kind``.  ``None`` off the
    TPU, where no peak applies; a TPU kind missing from
    :data:`~repro.core.oi.DEVICE_KINDS` is an error, never a default."""
    device = jax.devices()[0] if device is None else device
    if device.platform != "tpu":
        return None
    if device.device_kind not in DEVICE_KINDS:
        raise ValueError(
            f"no peaks for TPU kind {device.device_kind!r}: add it to "
            "repro.core.oi.DEVICE_KINDS with its source"
        )
    return DEVICE_KINDS[device.device_kind]


class NullDispatchProfiler:
    """The disabled profiler: every hook is a no-op and ``enabled`` is
    False so engines skip sampling decisions and record joins entirely."""

    enabled = False
    samples: tuple = ()

    def tick(self) -> bool:
        return False

    def begin(self, fence) -> None:
        pass

    def end(self, fence) -> None:
        pass

    def commit(self, record) -> None:
        pass


NULL_PROFILER = NullDispatchProfiler()


@dataclasses.dataclass
class ProfileSample:
    """One fenced dispatch: measured seconds joined with analytic cost."""

    replica: int
    step: int                   # engine-step id of the dispatch
    kind: str                   # decode | fused | solo | spec | ...
    bucket: int | None          # compiled prefill-chunk bucket (None: none)
    decode_batch: int
    seconds: float              # fence-to-fence wall clock
    flops: float                # analytic FLOPs (DispatchCostModel)
    bytes: float                # analytic HBM bytes
    oi: float                   # flops / bytes
    measured_mfu: float | None  # None: no device peaks (not measured)
    measured_mbu: float | None
    achieved_gbps: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class DispatchProfiler:
    """Samples dispatch wall-clock between ``block_until_ready`` fences
    and joins it with the step's analytic FLOPs/bytes — a live Fig 8.

    One profiler instance may be shared by many replicas (the cluster
    passes the same object to every engine); the sampling counter is
    then global across replicas, which only spreads the fence cost.
    """

    enabled = True

    def __init__(self, sample_every: int = 8,
                 device: str | Device | None = None):
        """``device`` names the peaks explicitly (a :data:`DEVICES` key
        or a :class:`Device`); by default they are read from the device
        the run is on (:func:`device_peaks`)."""
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, got {sample_every}")
        self.sample_every = sample_every
        if device is None:
            device = device_peaks()
        self.device = DEVICES[device] if isinstance(device, str) else device
        self.samples: list[ProfileSample] = []
        self._n = 0             # dispatches seen (sampled or not)
        self._t0: float | None = None
        self._dt: float | None = None

    @property
    def sync(self) -> bool:
        """Sync mode: every dispatch is fenced and timed."""
        return self.sample_every == 1

    # ------------------------------------------------------------ sampling
    def tick(self) -> bool:
        """Count one dispatch; True when this one should be fenced."""
        self._n += 1
        return self._n % self.sample_every == 0

    def begin(self, fence) -> None:
        """Pre-dispatch fence: wait out all previously dispatched device
        work so the sampled interval bills only the next dispatch."""
        jax.block_until_ready(fence)
        self._t0 = time.perf_counter()

    def end(self, fence) -> None:
        """Post-dispatch fence: wait for the sampled dispatch itself."""
        jax.block_until_ready(fence)
        self._dt = time.perf_counter() - self._t0
        self._t0 = None

    def commit(self, record) -> None:
        """Join the fenced interval with the dispatch's StepRecord: append
        a :class:`ProfileSample` and annotate the record in place so the
        Perfetto exporter can emit measured counter tracks."""
        dt = self._dt
        self._dt = None
        if dt is None or record is None:
            return
        dt = max(dt, 1e-9)
        mfu = mbu = None
        if self.device is not None:
            mfu = record.flops / (dt * self.device.flops)
            mbu = record.bytes / (dt * self.device.bw)
        gbps = record.bytes / dt / 1e9
        record.measured_s = dt
        record.measured_mfu = mfu
        record.measured_mbu = mbu
        record.achieved_gbps = gbps
        self.samples.append(ProfileSample(
            replica=record.replica, step=record.step, kind=record.kind,
            bucket=record.bucket, decode_batch=record.decode_batch,
            seconds=dt, flops=record.flops, bytes=record.bytes, oi=record.oi,
            measured_mfu=mfu, measured_mbu=mbu, achieved_gbps=gbps,
        ))

    # ----------------------------------------------------------- reporting
    def summary(self) -> dict[tuple, dict[str, float]]:
        """Aggregate per ``(kind, bucket, decode_batch)``: sample count,
        mean seconds, and mean measured MFU/MBU/bandwidth — the measured
        twin of the paper's Fig-8 rows."""
        groups: dict[tuple, list[ProfileSample]] = {}
        for s in self.samples:
            groups.setdefault((s.kind, s.bucket, s.decode_batch), []).append(s)
        out: dict[tuple, dict[str, float]] = {}
        for key in sorted(groups, key=lambda k: (k[0], k[1] or 0, k[2])):
            ss = groups[key]
            n = len(ss)
            out[key] = {
                "n": float(n),
                "seconds": sum(s.seconds for s in ss) / n,
                "oi": sum(s.oi for s in ss) / n,
                "achieved_gbps": sum(s.achieved_gbps for s in ss) / n,
            }
            if self.device is not None:
                out[key]["measured_mfu"] = sum(s.measured_mfu for s in ss) / n
                out[key]["measured_mbu"] = sum(s.measured_mbu for s in ss) / n
        return out

    def register(self, reg) -> None:
        """Publish the measured view into a :class:`MetricsRegistry`:
        overall gauges plus per-dispatch sample histograms."""
        reg.counter("profiled_dispatches").inc(len(self.samples))
        reg.gauge("profile_sample_every").set(self.sample_every)
        if not self.samples:
            return
        n = len(self.samples)
        if self.device is not None:
            reg.gauge("measured_mfu").set(
                sum(s.measured_mfu for s in self.samples) / n
            )
            reg.gauge("measured_mbu").set(
                sum(s.measured_mbu for s in self.samples) / n
            )
        reg.gauge("achieved_gbps").set(
            sum(s.achieved_gbps for s in self.samples) / n
        )
        reg.histogram("dispatch_seconds").extend(
            s.seconds for s in self.samples
        )

    def describe(self) -> str:
        """One-line measured summary for the terminal dashboard."""
        if not self.samples:
            return "measured: no samples yet"
        n = len(self.samples)
        bw = sum(s.achieved_gbps for s in self.samples) / n
        tail = f"bw={bw:.1f}GB/s (n={n}, every {self.sample_every})"
        if self.device is None:
            return f"measured[no device peaks]: mfu/mbu not measured {tail}"
        mfu = sum(s.measured_mfu for s in self.samples) / n
        mbu = sum(s.measured_mbu for s in self.samples) / n
        return (f"measured[{self.device.name}]: mfu={mfu:.4f} mbu={mbu:.4f} "
                f"{tail}")


def make_profiler(sample_every: int) -> DispatchProfiler | NullDispatchProfiler:
    """CLI helper: ``sample_every <= 0`` means disabled (NULL profiler),
    ``1`` is sync mode, ``N`` fences every Nth dispatch; peaks come from
    the device the run is on."""
    if sample_every <= 0:
        return NULL_PROFILER
    return DispatchProfiler(sample_every=sample_every)


__all__ = [
    "NULL_PROFILER",
    "DispatchProfiler",
    "device_peaks",
    "NullDispatchProfiler",
    "ProfileSample",
    "make_profiler",
]
