"""Find the highest open-loop rate a cell sustains, one short run per
rate and seed, all in one process.

    python bench/sweep.py --workload yi-34b.rag-prefill --seconds 30 \\
        --rates 0.4,0.6,0.8 --seeds 1,2,3

A rate is sustained when, on every seed, the requests due in the second
half of the window wait no longer for their first token than those due
in the first half (no growing backlog) and every request due got one.
Each window's arrivals and lengths are one stratified set of about rate x
seconds requests (the mix's ``strata``), so seeds differ only in order.
Each run's TTFT p90, inter-token p95 and output tokens/s go to standard
output and to ``.bench_cache/sweep-<workload>.jsonl``; the cell's traffic
file then records 0.8 of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out_dir = spec.ROOT / ".bench_cache"
    out_dir.mkdir(exist_ok=True)
    path = out_dir / f"sweep-{args.workload}.jsonl"
    runs = [(float(r), int(s)) for r in args.rates.split(",")
            for s in args.seeds.split(",")]
    for rate, seed in runs:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0, keep_trace=None,
                                rehearse=args.rehearse)
        strata = max(1, round(rate * args.seconds))     # one set a window
        rc, res = run.run(ns, {"rate": rate, "strata": strata})
        if res is None:
            return rc
        half = args.seconds / 2
        first = [w for d, w in run.LAST["ttft"] if d < half and w is not None]
        second = [w for d, w in run.LAST["ttft"] if d >= half and w is not None]
        row = {"rate": rate, "seed": seed, "attempted": res["attempted"],
               "failed": res["failed"], "correct": res["correct"],
               "e2e": run.LAST["e2e"],
               "ttft_p50_first_half": float(np.median(first)) if first else None,
               "ttft_p50_second_half": float(np.median(second)) if second else None}
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
