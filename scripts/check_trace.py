"""CI trace smoke checker: assert a serve run produced a usable trace.

  PYTHONPATH=src python scripts/check_trace.py cluster_trace.json --replicas 2

Parses the Perfetto/Chrome-trace JSON a ``--trace`` serve run wrote,
runs it through :func:`repro.serving.telemetry.validate_trace`, and
asserts every expected replica contributed at least one **complete**
request span (a closed ``decode`` span whose request also has a
``finish`` marker) — the end-to-end guarantee the CI traced-serve smoke
gates on.  Exits nonzero with the problems printed otherwise.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

from repro.serving.telemetry import validate_trace


def check(obj: dict, n_replicas: int, expect_spill_marks: bool = False,
          expect_migrate_marks: bool = False,
          expect_spec_marks: bool = False,
          expect_slo_marks: bool = False,
          expect_measured_counters: bool = False) -> list[str]:
    """Return problem strings (empty = the trace passes the smoke bar)."""
    problems = validate_trace(obj)
    if problems:
        return problems
    events = obj["traceEvents"]
    decodes: dict[int, set[int]] = defaultdict(set)   # replica -> uids
    finishes: dict[int, set[int]] = defaultdict(set)
    n_spills = 0
    n_migrates = 0
    n_proposes = 0
    n_verifies = 0
    n_slo = 0
    measured = {"measured_mfu": 0, "measured_mbu": 0, "achieved_gbps": 0}
    counter_ts: dict[tuple[int, str], float] = {}
    for e in events:
        args = e.get("args", {})
        if e["ph"] == "X" and e["name"].startswith("decode") and e["dur"] >= 0:
            decodes[e["pid"]].add(args.get("uid", -1))
        if e["ph"] == "i" and e["name"] == "finish":
            finishes[e["pid"]].add(args.get("uid", -1))
        if e["ph"] == "i" and e["name"] == "kv_spill":
            n_spills += 1
        if e["ph"] == "i" and e["name"] == "kv_migrate":
            n_migrates += 1
        if e["ph"] == "i" and e["name"] == "spec_propose":
            n_proposes += 1
        if e["ph"] == "i" and e["name"] == "spec_verify":
            n_verifies += 1
        if e["ph"] == "i" and e["name"] == "slo_breach":
            n_slo += 1
        if e["ph"] == "C":
            if e["name"] in measured:
                measured[e["name"]] += 1
            # counter tracks must advance monotonically in ts per
            # (pid, name) series or Perfetto draws garbage graphs
            key = (e["pid"], e["name"])
            prev = counter_ts.get(key)
            if prev is not None and e["ts"] < prev:
                problems.append(
                    f"counter {e['name']} pid={e['pid']}: ts regressed "
                    f"{prev} -> {e['ts']}"
                )
            counter_ts[key] = e["ts"]
    if expect_slo_marks and n_slo == 0:
        problems.append("no slo_breach marks (SLO smoke expected >= 1)")
    if expect_measured_counters:
        # MFU/MBU exist only on a device with known peaks (not the CPU)
        if measured["achieved_gbps"] == 0:
            problems.append(
                "no achieved_gbps counter events (profiler smoke expected >= 1)"
            )
        if measured["measured_mfu"] not in (0, measured["achieved_gbps"]) or (
                measured["measured_mbu"] != measured["measured_mfu"]):
            problems.append(
                f"measured counters disagree: {measured} (MFU/MBU go with "
                "every achieved_gbps sample or with none)"
            )
    if expect_spill_marks and n_spills == 0:
        problems.append("no kv_spill marks (host-tier smoke expected >= 1)")
    if expect_spec_marks and n_proposes == 0:
        problems.append(
            "no spec_propose marks (speculative smoke expected >= 1)"
        )
    if expect_spec_marks and n_verifies == 0:
        problems.append(
            "no spec_verify marks (speculative smoke expected >= 1)"
        )
    if expect_migrate_marks and n_migrates == 0:
        problems.append(
            "no kv_migrate marks (disaggregated smoke expected >= 1)"
        )
    if expect_migrate_marks:
        # disaggregated layout: prefill-role replicas hand every request
        # off before it finishes, so complete spans exist only globally
        all_complete = (set().union(*decodes.values()) if decodes else set()) \
            & (set().union(*finishes.values()) if finishes else set())
        if not all_complete:
            problems.append(
                "no complete request span on any replica "
                f"(decoded uids {sorted(set().union(*decodes.values()) if decodes else set())}, "
                f"finished uids {sorted(set().union(*finishes.values()) if finishes else set())})"
            )
        return problems
    for r in range(n_replicas):
        complete = decodes.get(r, set()) & finishes.get(r, set())
        if not complete:
            problems.append(
                f"replica {r}: no complete request span "
                f"(decoded uids {sorted(decodes.get(r, set()))}, "
                f"finished uids {sorted(finishes.get(r, set()))})"
            )
    return problems


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="Chrome-trace JSON written by --trace")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica count that must each show a complete span")
    ap.add_argument("--expect-spill-marks", action="store_true",
                    help="require at least one kv_spill instant event "
                         "(the host-KV-tier serve smoke)")
    ap.add_argument("--expect-migrate-marks", action="store_true",
                    help="require at least one cluster-row kv_migrate "
                         "event (the disaggregated serve smoke); relaxes "
                         "the complete-span requirement from per-replica "
                         "to global, since prefill-role replicas migrate "
                         "requests away before they finish")
    ap.add_argument("--expect-spec-marks", action="store_true",
                    help="require at least one spec_propose and one "
                         "spec_verify instant event (the speculative "
                         "decoding serve smoke)")
    ap.add_argument("--expect-slo-marks", action="store_true",
                    help="require at least one slo_breach instant event "
                         "(the SLO-monitored workload serve smoke)")
    ap.add_argument("--expect-measured-counters", action="store_true",
                    help="require achieved_gbps counter events, with "
                         "measured_mfu/measured_mbu beside each one on a "
                         "device with known peaks (the sampled-profiler "
                         "serve smoke)")
    args = ap.parse_args(argv)
    try:
        obj = json.loads(open(args.trace).read())
    except (OSError, ValueError) as e:
        print(f"cannot read trace {args.trace}: {e}", file=sys.stderr)
        return 1
    problems = check(obj, args.replicas, args.expect_spill_marks,
                     args.expect_migrate_marks, args.expect_spec_marks,
                     args.expect_slo_marks, args.expect_measured_counters)
    if problems:
        print(f"trace check FAILED for {args.trace}:", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    n_events = len(obj["traceEvents"])
    scope = ("cluster-wide" if args.expect_migrate_marks
             else f"{args.replicas} replica(s)")
    print(f"trace OK: {args.trace} ({n_events} events, "
          f"complete spans on {scope})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
