"""Find a cell's pieces by name: BENCHMARK.json, its configuration,
traffic mix, correctness limits, the kind of model it serves, the loop
that drives its traffic and its per-layer metric readers."""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reported(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark: Path = REPO / "BENCHMARK.json",
              root: Path = BENCH) -> Cell:
    """Everything a run of cell ``name`` needs; raises when a piece is
    missing, so a run in an incomplete checkout fails before it starts.
    Traffic mixes and limits are read under ``root``."""
    bm = load_json(benchmark)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(REPO / configs[w["config"]]["file"])
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "limits" / f"{name}.json")
    return Cell(name, w["chips"], config, traffic, limits,
                _reported(bm["end_to_end"], name),
                _reported(bm["per_layer"], name))


def metric_reader(name: str):
    """The ``read(run)`` function of bench/metrics/<name>.py."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kind(config: dict):
    """The module of the configuration's kind of model,
    bench/kinds/<kind>.py."""
    return importlib.import_module(f"bench.kinds.{config['kind']}")


def loop(traffic: dict):
    """The ``run(client)`` function of the traffic's arrival process,
    bench/loops/<kind>.py."""
    return importlib.import_module(f"bench.loops.{traffic['kind']}").run
