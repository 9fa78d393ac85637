"""Find a cell's files by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each lives in a file of its own under
``bench/`` and nothing here names a particular one.

    bench/configs/<config>.json    model sizes as run (HF config keys), with
                                   the ``family`` and ``reference`` it names
    bench/families/<family>.py     everything that depends on the block
    bench/traffic/<traffic>.json   loop kind, rate or clients, lengths
    bench/cells/<cell>.json        engine settings sized for this cell
    bench/metrics/<metric>.py      one reader per per-layer metric
    bench/references/<name>.py     plain float32 forward named by a config
    bench/peaks.json               device peaks keyed by device_kind

A family module gives the harness:

    shape(config)                  the sizes the benchmark's own code needs;
                                   ``.vocab`` is the id range traffic draws
    model_config(config)           the program's ``ModelConfig``
    served_params(shape, seed)     the seeded weights, made on the device
                                   in one jitted call, as the program lays
                                   them out
    rehearsal(config)              the configuration at a size the CPU runs
    step_flops(shape, decode_batch, kv_tokens, chunks)
                                   one dispatch's FLOPs (``mfu.throughput``)

and the ``(flops, bytes)`` of each of its kernels, for the metric readers
of their rooflines; its reference takes the module and calls the same
per-layer and table generators.  Adding a family is new files only: a
family module, a configuration naming it, a reference, cells, traffic,
metric readers, and entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
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
    bench: Path = BENCH                  # the directory its files are in

    @property
    def family(self):
        return family_module(self.config, self.bench)


def _read_json(path: Path) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json``, with every file
    it names found under ``root``."""
    bench = root / "bench"
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(bench / "traffic" / f"{w['traffic']}.json")
    engine = _read_json(bench / "cells" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, config=config, traffic=traffic, engine=engine,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer,
                bench=bench)


@functools.cache
def load_module(path: Path):
    """Import one file of the benchmark by path (families, metric
    readers, references) without making ``bench`` a package; each file
    once, so that its types stay the same across callers.  It is entered
    in ``sys.modules`` (as ``bench_<dir>_<stem>``), where ``dataclasses``
    looks up a module's names."""
    name = f"bench_{path.parent.name}_{path.stem}"
    mod_name = name.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def family_module(config: dict[str, Any], bench: Path = BENCH):
    return load_module(bench / "families" / f"{config['family']}.py")


def reference_module(config: dict[str, Any], bench: Path = BENCH):
    return load_module(bench / "references" / f"{config['reference']}.py")


def metric_reader(name: str, bench: Path = BENCH):
    return load_module(bench / "metrics" / f"{name}.py")


def peaks(device_kind: str) -> dict[str, Any]:
    table = _read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
