"""Read a cell's correctness numbers over many seeds in one process, the
program's and the control's, for setting the cell's limit.

    python bench/readings.py --workload minicpm-2b.decode-long \\
        --seconds 12 --seeds 11,12,13 --control 3

For each seed it makes one run of the cell (``run.py``'s path, with a
short window at the cell's own load) and keeps the widest gap by which a
served token's logit lies below the float32 reference's best.  For the
first ``--control`` seeds it also reads the control on the same requests:
the reference computed in float8 (e4m3, scaled), at each served position
the gap of the token the control puts first.  One JSON line per seed
goes to ``.bench_cache/readings-<workload>.jsonl`` and to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out_dir = spec.ROOT / ".bench_cache"
    out_dir.mkdir(exist_ok=True)
    path = out_dir / f"readings-{args.workload}.jsonl"
    cell = spec.load_cell(args.workload)
    if args.rehearse:
        cell = run.rehearsal_cell(cell)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0, keep_trace=None,
                                rehearse=args.rehearse)
        rc, res = run.run(ns)
        if res is None:
            return rc
        row = {"seed": seed, "correct": res["correct"],
               "program_gap": res["checks"]["logit_gap_max"]["value"],
               "tokens": res["checks"]["tokens_compared"]["value"],
               "e2e": run.LAST["e2e"],
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        if i < args.control and run.LAST["sample"]:
            gaps = run.logit_gaps(cell, seed, run.LAST["sample"], "fp8")
            row["control_gap"] = float(max(g.max() for g in gaps))
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
