"""Find a cell's files by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each lives in a file of its own under
``bench/`` and nothing here names a particular one.

    bench/configs/<config>.json    model sizes as run (HF config keys)
    bench/traffic/<traffic>.json   loop kind, rate or clients, lengths
    bench/cells/<cell>.json        engine settings sized for this cell
    bench/metrics/<metric>.py      one reader per per-layer metric
    bench/references/<name>.py     plain float32 forward named by a config
    bench/peaks.json               device peaks keyed by device_kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict[str, Any]
    traffic: dict[str, Any]
    engine: dict[str, Any]
    chips: int
    end_to_end: list[dict[str, Any]]     # the metrics this cell reports
    per_layer: list[dict[str, Any]]


def _read_json(path: Path) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    engine = _read_json(BENCH / "cells" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, config=config, traffic=traffic, engine=engine,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer)


def load_module(path: Path):
    """Import one file of the benchmark by path (metric readers,
    references) without making ``bench`` a package."""
    mod_name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict[str, Any]):
    return load_module(BENCH / "references" / f"{config['reference']}.py")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def peaks(device_kind: str) -> dict[str, Any]:
    table = _read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the benchmark's own code needs (cost model, reference,
    weights), read from the HF-style configuration file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * 2

    @property
    def n_params(self) -> int:
        D, Dh = self.d_model, self.head_dim
        attn = D * (self.n_heads + 2 * self.n_kv_heads) * Dh + self.n_heads * Dh * D
        layer = attn + 3 * D * self.d_ff + 2 * D
        tables = (1 if self.tied else 2) * self.padded_vocab * D
        return self.n_layers * layer + tables + D


def shape(config: dict[str, Any], vocab_multiple: int = 256) -> Shape:
    c = config
    heads = c["num_attention_heads"]
    vocab = c["vocab_size"]
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=heads, n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"], vocab=vocab,
        padded_vocab=-(-vocab // vocab_multiple) * vocab_multiple,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]),
    )


def model_config(config: dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file (imports
    the system under test)."""
    from repro.configs.base import DENSE, ModelConfig

    s = shape(config)
    return ModelConfig(
        name=config["name"], family=DENSE, n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
        d_ff=s.d_ff, vocab=s.vocab, head_dim=s.head_dim,
        max_seq=config["max_position_embeddings"], rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tied,
        dtype=config["torch_dtype"],
    )
