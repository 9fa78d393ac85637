"""Run one benchmark cell once on the chip and print one JSON line.

    python bench/run.py --workload minicpm-2b.decode-long --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic mix and engine settings are found by
name (``bench/lib/spec.py``).  The run makes the weights on the device
from the seed in one jitted call, builds the engine as the serving CLI
does (paged pool, hybrid chunked-prefill schedule, async pipeline; the
Pallas kernels are chosen from the backend), and starts the mix's load
in set-up: the window of ``--seconds`` opens once the load is in its
stride and every program it has used is in memory (``bench/lib/load.py``).
``--trace 1`` traces the middle of the window with the profiler and
prints the per-layer metrics instead of the end-to-end ones.  Besides the
contract's keys, the result line gives ``setup_parts`` (seconds to start
JAX, to make the weights and engine, and to warm the load) and
``compiles_in_window`` (programs compiled or loaded from the persistent
cache inside the window, to be 0).

Once the window has closed and the engine is freed, a sample of the
finished requests drawn from the seed goes through the plain float32
reference named by the configuration, and ``correct`` says whether every
served token lies within the cell's limit of the reference's best logit.

JAX's persistent compilation cache lives at ``.bench_cache/jax`` in the
checkout, so only a cell's first run there compiles.  With no TPU, or
fewer chips than the cell asks for, it exits 2 and prints no result.
``--rehearse`` (tests only) runs the whole path on the CPU at a reduced
size, kernels in interpret mode, and prints no metric; ``--keep-trace``
writes the traced run's reduced events (how ``tests/data`` was recorded).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent / "lib"))
import spec  # noqa: E402

CACHE = spec.ROOT / ".bench_cache"
CHECK_TOKENS = 384          # served tokens the correctness sample reaches
TRACE_S = 4.0               # length of the traced part of the window
SPANS = ("submit", "engine.step", "generator wait")
LAST: dict = {}             # the last run's sample and end-to-end numbers


def rehearsal_cell(cell: spec.Cell) -> spec.Cell:
    """The cell at a size the CPU runs in seconds (tests only): the
    family's rehearsal configuration, short lengths, a small pool."""
    c = cell.family.rehearsal(cell.config)
    t = json.loads(json.dumps(cell.traffic))
    for key, top in (("prompt", 48), ("output", 6)):
        d = t[key]
        d["max"] = min(d["max"], top)
        d["min"] = min(d["min"], d["max"] // 2)
        if "median" in d:
            d["median"] = (d["min"] + d["max"]) / 2
    if t["loop"] == "open":
        t["rate"] = 4.0
    bs = 16
    blocks = -(-(t["prompt"]["max"] + t["output"]["max"] + 1) // bs)
    e = dict(n_slots=4, max_seq=blocks * bs, block_size=bs,
             n_blocks=4 * blocks + 1, prefill_chunk=16,
             check=cell.engine.get("check", {}))
    return spec.Cell(name=cell.name, config=c, traffic=t, engine=e,
                     chips=1, end_to_end=cell.end_to_end,
                     per_layer=cell.per_layer, bench=cell.bench)


def setup_jax(rehearse: bool):
    import jax

    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if rehearse:
        from repro.kernels import ops

        ops.on_tpu = lambda: True           # the TPU path, interpreted
        ops._interpret = lambda: True
    return jax


def build(cell: spec.Cell, seed: int, tracer, async_mode: bool = True):
    from repro.core.placement import Env
    from repro.models.registry import build_model
    from repro.serving.engine import Engine

    family = cell.family
    shape = family.shape(cell.config)
    model = build_model(family.model_config(cell.config), Env())
    params = family.served_params(shape, seed)
    e = cell.engine
    eng = Engine(model, params, n_slots=e["n_slots"], max_seq=e["max_seq"],
                 cache_kind="paged", block_size=e["block_size"],
                 n_blocks=e["n_blocks"], schedule="hybrid",
                 prefill_chunk=e["prefill_chunk"],
                 token_budget=e.get("token_budget"), async_mode=async_mode,
                 tracer=tracer)
    return shape, eng


def steps_from(tracer, lo: float, hi: float) -> list:
    """The program's step timeline in [lo, hi], every field of each
    ``StepRecord``, with each dispatch's prefill chunks
    (``prefill_chunk`` spans end at their dispatch step)."""
    import layer

    chunks: dict[int, list] = {}
    for sp in tracer.spans:
        if sp.name == "prefill_chunk":
            a = sp.attrs
            chunks.setdefault(sp.end, []).append(
                (int(a["pos"]), int(a["n_valid"]), bool(a["last"])))
    return [layer.Step(**r.as_dict(), chunks=chunks.get(r.step, []))
            for r in tracer.steps if r.wall is not None and lo <= r.wall <= hi]


def end_to_end(load, t0: float, t_end: float) -> dict[str, float]:
    import layer

    due = [t for t in load.all if t0 <= t.due <= t_end]
    end = time.perf_counter()
    ttft = [(t.stamps[0] if t.stamps else end) - t.due for t in due]
    gaps, tokens = [], 0
    for t in load.all:
        for i, s in enumerate(t.stamps):
            if t0 <= s <= t_end:
                tokens += 1
                if i:
                    gaps.append(s - t.stamps[i - 1])
    out = {"output_tokens_per_s": tokens / (t_end - t0),
           "setup_s": t0 - T_START}
    if gaps:
        out["itl_p95_s"] = layer.percentile(gaps, 95)
    if ttft:
        out["ttft_p90_s"] = layer.percentile(ttft, 90)
    return out


def check_sample(load, seed: int) -> list:
    """Requests to compare, with the tokens served to them by the end of
    the run (a token once served is final, so a request still decoding
    is compared on what it has): the longest, then others drawn from the
    seed, until ``CHECK_TOKENS`` served tokens are covered."""
    import numpy as np

    served = [(np.asarray(t.req.prompt), np.asarray(t.req.out_tokens))
              for t in load.all if len(t.req.out_tokens) >= 1]
    if not served:
        return []
    served.sort(key=lambda po: len(po[0]) + len(po[1]))
    pick = [served.pop()]
    for i in np.random.default_rng(seed).permutation(len(served)):
        if sum(len(o) for _, o in pick) >= CHECK_TOKENS:
            break
        pick.append(served[i])
    return pick


def logit_gaps(cell: spec.Cell, seed: int, sample, precision="f32"):
    """Per sampled request, the gap by which each served token's logit
    lies below the reference's best (``precision="fp8"``: the gap of the
    token the control puts first instead)."""
    import jax.numpy as jnp
    import numpy as np

    ref = spec.reference_module(cell.config, cell.bench)
    family = cell.family
    s = family.shape(cell.config)
    seqs = [np.concatenate([p, o[:-1]]) for p, o in sample]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o in sample]
    out = []
    if precision == "f32":
        for lg, (_, o) in zip(ref.logits_at(family, s, seed, seqs, rows),
                              sample):
            best = jnp.max(lg, -1)
            got = jnp.take_along_axis(lg, jnp.asarray(o)[:, None], -1)[:, 0]
            out.append(np.asarray(best - got))
        return out
    for lg, lc in zip(ref.logits_at(family, s, seed, seqs, rows),
                      ref.logits_at(family, s, seed, seqs, rows, precision)):
        pick = jnp.argmax(lc, -1)
        out.append(np.asarray(jnp.max(lg, -1)
                              - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]))
    return out


def reduce_trace(trace_dir: Path, ctx) -> tuple[dict, dict]:
    import trace as tr

    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        return {}, {}
    try:
        ctx.trace = tr.load(files[-1])
    except ValueError as e:             # no device plane (the CPU)
        print(f"trace: {e}", file=sys.stderr)
        return {}, {}
    device = {"busy_s": tr.busy_s(ctx.trace), "window_s": tr.window_s(ctx.trace)}
    breakdown = {"device_ops": tr.top_ops(ctx.trace),
                 "idle_gaps": tr.idle_gaps(ctx.trace, SPANS)}
    return device, breakdown


class Traced:
    """Profiles the host-clock interval [lo, hi] of the window: the loop
    calls ``tick`` each iteration; the actual bounds are kept."""

    def __init__(self, jax, out: Path, lo: float, hi: float):
        self.jax, self.out, self.lo, self.hi = jax, out, lo, hi
        self.state = "before"
        self.span = None

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.lo:
            shutil.rmtree(self.out, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # keep annotations, not calls
            self.jax.profiler.start_trace(str(self.out), profiler_options=opts)
            self.span = self.jax.profiler.TraceAnnotation("window")
            self.span.__enter__()
            self.lo, self.state = time.perf_counter(), "on"
        elif self.state == "on" and now >= self.hi:
            self.hi, self.state = time.perf_counter(), "done"
            self.span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()


def run(args, mix: dict | None = None,
        root: Path = spec.ROOT) -> tuple[int, dict | None]:
    """One run of the cell as ``root``'s files define it; ``mix``
    replaces keys of the traffic mix (the rate sweep)."""
    cell = spec.load_cell(args.workload, root)
    if args.rehearse:
        cell = rehearsal_cell(cell)
    cell.traffic.update(mix or {})
    sys.path.insert(0, str(spec.ROOT / "src"))
    jax = setup_jax(args.rehearse)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2, None
    peak = None if args.rehearse else spec.peaks(dev.device_kind)

    import layer
    from compile_log import CompileLog
    from load import Load
    from repro.serving.engine import Request
    from repro.serving.scheduler import chunk_buckets
    from repro.serving.telemetry import Tracer
    from traffic import check_mix

    check_mix(cell.traffic)
    clog = CompileLog()
    t_jax = time.perf_counter()
    tracer = Tracer(wall=True) if args.trace else None
    shape, eng = build(cell, args.seed, tracer)
    clients = cell.engine["n_slots"] if cell.traffic.get("clients") == "slots" \
        else int(cell.traffic.get("clients", 0))
    load = Load(eng, Request, cell.traffic, args.seed, shape.vocab, clients)
    jax.block_until_ready(eng.params)
    t_built = time.perf_counter()
    nb, hb, _ = clog.mark()
    n_steps = load.warm_chunks(chunk_buckets(cell.engine["prefill_chunk"]))
    load.start(time.perf_counter())
    n_steps += load.warm(lambda: clog.mark()[0])
    n0, h0, _ = clog.mark()

    trace_dir = CACHE / "trace"
    t0 = time.perf_counter()
    parts = {"start_s": t_jax - T_START, "build_s": t_built - t_jax,
             "warm_s": t0 - t_built}
    print(f"setup: {parts}; weights and engine: {nb} programs ({hb} from "
          f"the cache); warm-up: {n_steps} steps, {n0 - nb} programs "
          f"({h0 - hb} from the cache)", file=sys.stderr)
    t_end = t0 + args.seconds
    window = Traced(jax, trace_dir, t0 + max(args.seconds - TRACE_S, 0) / 2,
                    min(t0 + max(args.seconds - TRACE_S, 0) / 2 + TRACE_S, t_end))
    load.run(t0, t_end, window.tick if args.trace else None)
    if args.trace:
        window.tick(math.inf)
    n1, h1, _ = clog.mark()
    print(f"bench: {cell.name} seed {args.seed}: {n1 - n0} programs "
          f"compiled or loaded ({h1 - h0} from the cache) in the window "
          f"{clog.names[n0:n1]}; "
          f"generator lag p99 {layer.percentile(load.lag or [0], 99):.4f}s",
          file=sys.stderr)
    e2e = end_to_end(load, t0, t_end)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    due = [t for t in load.all if t0 <= t.due <= t_end]
    served = [t for t in load.all
              if t0 <= t.due <= t_end or any(t0 <= s <= t_end for s in t.stamps)]
    attempted, failed = len(served), sum(1 for t in due if not t.stamps)

    result: dict = {}
    if args.trace:
        ctx = layer.Context(
            family=cell.family, shape=shape, peak=peak,
            steps=steps_from(tracer, t0, t_end),
            trace_window=(window.lo, window.hi))
        dev_extra, breakdown = reduce_trace(trace_dir, ctx)
        if args.keep_trace and ctx.trace is not None:
            ctx.trace.to_json(Path(args.keep_trace))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(dev_extra)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], cell.bench).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if breakdown:
            result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    if args.rehearse:
        metrics = {}

    sample = check_sample(load, args.seed)
    LAST["sample"], LAST["e2e"] = sample, e2e
    LAST["ttft"] = [(t.due - t0, t.stamps[0] - t.due if t.stamps else None)
                    for t in due]
    del load, eng, tracer
    gc.collect()
    gaps = logit_gaps(cell, args.seed, sample) if sample else []
    n_tok = sum(len(g) for g in gaps)
    gap_max = float(max(g.max() for g in gaps)) if gaps else math.inf
    limit = cell.engine["check"]["logit_gap_max"]
    correct = bool(n_tok > 0 and gap_max <= limit)
    checks = {"logit_gap_max": {"value": gap_max if n_tok else None,
                                "limit": limit},
              "tokens_compared": {"value": n_tok, "limit": 1}}
    print(f"check: {len(sample)} requests, {n_tok} served tokens compared with "
          f"the float32 reference", file=sys.stderr)
    print(f"check: logit_gap_max {gap_max:.6f} limit {limit}", file=sys.stderr)
    print(f"check: tokens_compared {n_tok} limit >= 1", file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **result,
              "setup_parts": parts, "compiles_in_window": n1 - n0,
              "checks": checks}
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--keep-trace", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    rc, result = run(args)
    if result is not None:
        print(json.dumps(result))
    return rc


if __name__ == "__main__":
    sys.exit(main())
