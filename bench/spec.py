"""Find a cell's data by name: ``BENCHMARK.json``, the configuration, the
traffic mix and the correctness limits.

Everything that belongs to one configuration, traffic mix or cell sits in
a file of its own under ``bench/`` and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   sizes, engine settings, sparsity;
* ``bench/traffic/<traffic>.json``  parameters for ``generator.py``;
* ``bench/limits/<cell>.json``      the limit of each compared number;
* ``bench/metrics/<metric>.py``     one reader per metric.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def engine_kwargs(self) -> dict:
        """``ServeEngine`` keyword arguments: the configuration's engine
        settings, then the traffic's."""
        return {**self.config["engine"], **self.traffic["engine"]}

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics this cell reports in a run with ``--trace``."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    bm = benchmark if benchmark is not None else _load(
        ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(ROOT / conf["file"]),
        traffic_name=w["traffic"],
        traffic=load_traffic(w["traffic"]),
        limits=_load(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=bm["end_to_end"], per_layer=bm["per_layer"])


def load_config(name: str) -> dict:
    return _load(BENCH_DIR / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _load(BENCH_DIR / "traffic" / f"{name}.json")


def model_config(config: dict):
    """The program's ``ModelConfig`` with every field the cell uses taken
    from the configuration file (nothing from ``repro.configs``)."""
    from repro.models.config import BlockCfg, ModelConfig
    m = dict(config["model"])
    m["pattern"] = tuple(BlockCfg(**b) for b in m["pattern"])
    return ModelConfig(**m)


def limits_of(cell: Cell) -> Dict[str, float]:
    return {k: float(v["limit"]) for k, v in cell.limits["numbers"].items()}
