"""Finds everything a cell needs by name: ``BENCHMARK.json`` at the root,
``configs/<config>.json``, ``traffic/<traffic>.json``, ``limits/<cell>.json``,
``metrics/<metric>.py`` and ``peaks.json``. Adding a cell, configuration, mix
or metric is adding files and entries; no file here names one."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: dict | None = None, bench_dir: str = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load(os.path.join(os.path.dirname(bench_dir), conf["file"])),
        traffic=_load(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=_load(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _load(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in peaks.json; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
