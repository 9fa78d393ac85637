"""The model family as files of the benchmark, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

* the dense family makes the weights and counts the work exactly as the
  harness did before the family moved into ``bench/families/dense.py``:
  the expected digests and counts below were read from the earlier
  ``lib/weights.py`` and ``lib/cost.py``;
* a family, configuration, traffic mix, cell, reference and metric
  reader that exist only under another benchmark root are found there,
  and a CPU rehearsal runs through them;
* a dispatch's record reaches the metric readers whole, so a counter the
  program adds later needs no edit of ``run.py``;
* no harness file outside the dense family and its reference names the
  dense block's keys.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import re
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "lib"), str(BENCH.parent / "src")]

import run  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 5
CELL = "minicpm-2b.decode-long"

# sha256 of each leaf's bytes (first 16 hex digits), as the harness made
# them before the dense family was a file of its own
BEFORE_REHEARSAL = {
    ("minicpm-2b", SEED): {
        "['blocks']['ln1']": "24a13973e3e1dd60", "['blocks']['ln2']": "da8008820f8c44b5",
        "['blocks']['w_down']": "085326cc921a9deb", "['blocks']['w_gate']": "048094f10f69c888",
        "['blocks']['w_up']": "16e2ff1133ec05a2", "['blocks']['wk']": "a9f57977d5b60feb",
        "['blocks']['wo']": "3b5d1983fd85c8f3", "['blocks']['wq']": "73f5ea3a20822e49",
        "['blocks']['wv']": "89a9d9da24635ed9", "['embed']": "4b1a5fe22aa477fe",
        "['final_norm']": "0e6548c236a5fe6c"},
    ("minicpm-2b", 7): {
        "['blocks']['ln1']": "34883e129c8af812", "['blocks']['ln2']": "eb71c506108d5968",
        "['blocks']['w_down']": "1c3c41386e3314de", "['blocks']['w_gate']": "da2dd08d22d4608c",
        "['blocks']['w_up']": "7c2ff5d373a102fa", "['blocks']['wk']": "6cad56962263139e",
        "['blocks']['wo']": "0abffaba4194a344", "['blocks']['wq']": "71f5ee8054d5f97e",
        "['blocks']['wv']": "e183759d9b57ec67", "['embed']": "5d897f9c918c1b7c",
        "['final_norm']": "3ac864cf5f8f916f"},
    ("yi-34b", SEED): {
        "['blocks']['ln1']": "24a13973e3e1dd60", "['blocks']['ln2']": "da8008820f8c44b5",
        "['blocks']['w_down']": "085326cc921a9deb", "['blocks']['w_gate']": "048094f10f69c888",
        "['blocks']['w_up']": "16e2ff1133ec05a2", "['blocks']['wk']": "6654431952a4d5f8",
        "['blocks']['wo']": "3b5d1983fd85c8f3", "['blocks']['wq']": "73f5ea3a20822e49",
        "['blocks']['wv']": "dd6609c40b1491c7", "['embed']": "4b1a5fe22aa477fe",
        "['final_norm']": "0e6548c236a5fe6c", "['unembed']": "b8403521d6637e76"},
    ("yi-34b", 7): {
        "['blocks']['ln1']": "34883e129c8af812", "['blocks']['ln2']": "eb71c506108d5968",
        "['blocks']['w_down']": "1c3c41386e3314de", "['blocks']['w_gate']": "da2dd08d22d4608c",
        "['blocks']['w_up']": "7c2ff5d373a102fa", "['blocks']['wk']": "55b55d5b931a4217",
        "['blocks']['wo']": "0abffaba4194a344", "['blocks']['wq']": "71f5ee8054d5f97e",
        "['blocks']['wv']": "860b3c4972b22e31", "['embed']": "5d897f9c918c1b7c",
        "['final_norm']": "3ac864cf5f8f916f", "['unembed']": "2c33f2a06a1061f5"},
}
BEFORE_MINICPM_LAYER0 = {
    "['ln1']": "51d1ee7ab2af1965", "['ln2']": "0c0a359daf8baabe",
    "['w_down']": "1e31e57dffc42c20", "['w_gate']": "cded725153c0ca9e",
    "['w_up']": "2d0daa93554c2a2e", "['wk']": "387fc7c25cec5aee",
    "['wo']": "6f86522962bf3d7d", "['wq']": "d916b101ba390c69",
    "['wv']": "3b2b0c57cb783928"}
BEFORE_MINICPM_TABLES = {"['embed']": "9b20332a8747a662",
                         "['final_norm']": "2f28128cf1c84dcf"}

# (decode_batch, kv_tokens, chunks of (pos, n, last)) and the counts the
# harness gave for them at MiniCPM-2B's widths
STEPS = [(5, 4412, []), (4, 3000, [(0, 512, False)]),
         (0, 0, [(0, 512, False), (512, 300, True)]),
         (1, 1024, [(256, 16, True)]), (5, 7680, [(768, 512, False)])]
BEFORE_STEP_FLOPS = [28873382400.0, 2571792500736.0, 4087844688384.0,
                     86092489728.0, 2723922270720.0]
BEFORE_PAGED_ATTN = [(1626439680.0, 1628282880), (1105920000.0, 1107394560),
                     (0.0, 0), (377487360.0, 377856000),
                     (2831155200.0, 2832998400)]
BEFORE_FLASH_PREFILL = [(48412753920.0, 377487360), (48412753920.0, 377487360),
                        (73267200000.0, 409927680), (1560084480.0, 106168320),
                        (193367900160.0, 660602880)]


def digest(tree) -> dict[str, str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): hashlib.sha256(np.asarray(x).tobytes())
            .hexdigest()[:16] for p, x in flat}


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config,seed", sorted(BEFORE_REHEARSAL))
def test_dense_served_params_are_the_same_bits(config, seed):
    family = spec.family_module(_config(config))
    s = family.shape(family.rehearsal(_config(config)))
    assert digest(family.served_params(s, seed)) == BEFORE_REHEARSAL[config, seed]


def test_dense_layer_and_tables_at_minicpm_widths_are_the_same_bits():
    family = spec.family_module(_config("minicpm-2b"))
    s = family.shape(_config("minicpm-2b"))
    layer0 = jax.jit(lambda k: family.layer_leaves(s, k))(weights.layer_key(SEED, 0))
    assert digest(layer0) == BEFORE_MINICPM_LAYER0
    del layer0
    tables = jax.jit(lambda k: family.table_leaves(s, k))(weights.table_key(SEED))
    assert digest(tables) == BEFORE_MINICPM_TABLES


def test_dense_counts_are_the_same():
    family = spec.family_module(_config("minicpm-2b"))
    s = family.shape(_config("minicpm-2b"))
    assert [family.step_flops(s, b, k, c) for b, k, c in STEPS] == BEFORE_STEP_FLOPS
    assert [family.paged_attn(s, b, k) for b, k, _ in STEPS] == BEFORE_PAGED_ATTN
    assert [family.flash_prefill(s, p, n) for *_, c in STEPS
            for p, n, _ in c] == BEFORE_FLASH_PREFILL
    assert family.layer_params(s) == 61046784
    assert (s.n_params, s.kv_bytes_per_token) == (2725173504, 368640)


ALIAS_FAMILY = '''"""The dense family under another name, found only in this root."""
import spec

_dense = spec.load_module(spec.BENCH / "families" / "dense.py")
globals().update({k: v for k, v in vars(_dense).items()
                  if not k.startswith("__")})
CALLS = []


def rehearsal(config):
    CALLS.append("rehearsal")
    return _dense.rehearsal(config)


def served_params(s, seed):
    CALLS.append("served_params")
    return _dense.served_params(s, seed)
'''

ALIAS_REFERENCE = '''"""The llama block's reference under another name."""
import spec

_ref = spec.load_module(spec.BENCH / "references" / "llama_block.py")
CALLS = []


def logits_at(family, *args, **kw):
    CALLS.append(family.__name__)
    yield from _ref.logits_at(family, *args, **kw)
'''


def _alias_root(tmp: Path) -> Path:
    """A benchmark root whose one cell uses a family, configuration,
    traffic mix, cell file, reference and metric reader of its own."""
    bench = tmp / "bench"
    for d in ("configs", "families", "traffic", "cells", "references", "metrics"):
        (bench / d).mkdir(parents=True)
    cell = spec.load_cell(CELL)
    config = {**cell.config, "name": "alias", "family": "dense_alias",
              "reference": "llama_alias"}
    (bench / "configs" / "alias.json").write_text(json.dumps(config))
    (bench / "families" / "dense_alias.py").write_text(ALIAS_FAMILY)
    (bench / "references" / "llama_alias.py").write_text(ALIAS_REFERENCE)
    (bench / "traffic" / "alias-mix.json").write_text(json.dumps(cell.traffic))
    (bench / "cells" / "alias.alias-mix.json").write_text(json.dumps(cell.engine))
    shutil.copy(BENCH / "metrics" / "decode_batch_mean.py",
                bench / "metrics" / "alias_lanes.py")
    bench_spec = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench_spec["configs"] = [{"name": "alias", "file": "bench/configs/alias.json"}]
    bench_spec["workloads"] = [{"name": "alias.alias-mix", "config": "alias",
                                "traffic": "alias-mix", "chips": 1}]
    for m in bench_spec["end_to_end"] + bench_spec["per_layer"]:
        m.pop("workloads", None)
    bench_spec["per_layer"].append({**bench_spec["per_layer"][0],
                                    "name": "alias_lanes"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench_spec))
    return tmp


def test_a_new_family_is_new_files_only(tmp_path):
    root = _alias_root(tmp_path)
    cell = spec.load_cell("alias.alias-mix", root)
    assert cell.bench == root / "bench"
    assert Path(cell.family.__file__) == root / "bench" / "families" / "dense_alias.py"
    assert cell.engine == spec.load_cell(CELL).engine
    assert "alias_lanes" in [m["name"] for m in cell.per_layer]
    assert hasattr(spec.metric_reader("alias_lanes", cell.bench), "read")
    ns = argparse.Namespace(workload=cell.name, seed=SEED + 1, seconds=2.0,
                            trace=0, rehearse=True, keep_trace=None)
    rc, res = run.run(ns, root=root)
    assert rc == 0 and res["checks"]["tokens_compared"]["value"] > 0
    assert cell.family.CALLS == ["rehearsal", "served_params"]
    reference = spec.reference_module(cell.config, cell.bench)
    assert reference.CALLS == [cell.family.__name__]


@dataclasses.dataclass
class _Span:
    name: str
    end: int
    attrs: dict


def test_steps_carry_every_step_record_field():
    """A counter that a later program adds to its step record reaches the
    readers without an edit of ``steps_from``."""
    from repro.serving.telemetry.timeline import StepRecord

    @dataclasses.dataclass
    class Later(StepRecord):
        routed_here: int = 0

    def record(step, wall, kind, routed):
        return Later(replica=0, step=step, kind=kind, decode_batch=3,
                     prefill_tokens=16 if kind == "fused" else 0,
                     bucket=16 if kind == "fused" else None, bucket2=None,
                     budget=64, fill=0.5, kv_tokens=900, pool_util=0.25,
                     pipeline_depth=1, flops=1.0, bytes=2.0, oi=0.5,
                     wall=wall, routed_here=routed)

    class Tracer:
        steps = [record(1, 0.5, "decode", 7), record(2, 1.5, "fused", 11),
                 record(3, 9.0, "decode", 13)]
        spans = [_Span("prefill_chunk", 2, {"pos": 0, "n_valid": 16,
                                            "last": True})]

    got = run.steps_from(Tracer, 0.0, 2.0)
    assert [s.routed_here for s in got] == [7, 11]
    assert [s.kind for s in got] == ["decode", "fused"]
    assert [s.chunks for s in got] == [[], [(0, 16, True)]]
    for s, r in zip(got, Tracer.steps):
        assert {k: getattr(s, k) for k in r.as_dict()} == r.as_dict()


def test_only_the_dense_family_names_the_dense_block():
    dense_keys = re.compile(r"family=DENSE|intermediate_size|num_key_value_heads")
    allowed = {BENCH / "families" / "dense.py",
               BENCH / "references" / "llama_block.py"}
    named = [p for p in BENCH.rglob("*.py")
             if p not in allowed and "tests" not in p.relative_to(BENCH).parts
             and dense_keys.search(p.read_text())]
    assert named == []
