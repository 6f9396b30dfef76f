"""A cell of ``BENCHMARK.json``: its configuration, traffic mix and metrics,
each found by its name in a file of its own under ``bench/``.

* ``bench/configs/<config>.json`` — the deployment: graph generator and its
  parameters and the query.  The layout is the cell's: one worker per chip
  (``Cell.chips``), sequential on one chip and under ``shard_map`` on more.
* ``bench/traffic/<traffic>.json`` — the traffic mix.
* ``bench/graphs/<generator>.py`` — ``generate(config, seed) -> (n, edges)``.
* ``bench/metrics/<metric>.py`` — ``read(ctx) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple   # the BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def substrate(self) -> str:
        """The program's substrate for the workers: one worker runs
        sequentially, several under ``shard_map``, one on each chip."""
        return "sequential" if self.chips == 1 else "shard_map"


def _one(items: list, what: str) -> dict:
    if len(items) != 1:
        raise SystemExit(f"BENCHMARK.json: expected one {what}, found "
                         f"{len(items)}")
    return items[0]


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = _one([w for w in bench["workloads"] if w["name"] == workload],
                f"workload named {workload!r}")
    entry = _one([c for c in bench["configs"] if c["name"] == cell["config"]],
                 f"config named {cell['config']!r}")
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return tuple(m for m in metrics
                     if workload in m.get("workloads", [workload]))

    return Cell(name=workload, chips=int(cell["chips"]), config=config,
                traffic=traffic, end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    if spec is None or not path.is_file():
        raise SystemExit(f"no {kind} named {name!r} ({path})")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def largest_component(n: int, edges: np.ndarray) -> tuple[int, np.ndarray]:
    """The edges of the largest connected component, its vertices renumbered
    0.. in the order of their labels."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    e = np.asarray(edges, np.int64).reshape(-1, 2)
    adj = coo_matrix((np.ones(len(e), np.int8), (e[:, 0], e[:, 1])),
                     shape=(n, n))
    _, label = connected_components(adj, directed=False)
    keep = label == np.argmax(np.bincount(label))
    new = np.cumsum(keep) - 1
    e = e[keep[e[:, 0]]]
    return int(keep.sum()), new[e]


def edges(config: dict) -> tuple[int, np.ndarray]:
    """The configuration's graph as ``(n, edge tuples)``."""
    gen = load_module("graphs", config["generator"])
    n, e = gen.generate(config, int(config["graph_seed"]))
    if config.get("largest_component"):
        n, e = largest_component(n, e)
    return n, e
