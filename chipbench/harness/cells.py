"""Finding a cell's parts by name.

``BENCHMARK.json`` names every cell's configuration and traffic mix and
every metric; the files are found by those names alone:

    chipbench/configs/<config>.json     the configuration (sizes, source)
    chipbench/traffic/<traffic>.json    the traffic mix (client.py reads it)
    chipbench/metrics/<metric>.py       the metric's reader: read(run)

So a new cell is new files plus a ``workloads`` entry, and no edit. A
configuration's ``name`` names its dataset (it seeds the generator, see
``data.dataset_rng``), so a deployment of the same dataset on another mesh
keeps it. The service's mesh is the configuration's ``"mesh": [data,
model]``, or ``(chips, 1)`` where it states none; its size must equal the
workload's ``chips`` and, where the configuration states them, its
``chips``. The key ``rehearsal`` is for the CPU rehearsals alone
(``tests/tinyroot.py``); a run never reads it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent
MESH_AXES = ("data", "model")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    mesh: tuple[int, int]  # the service's (data, model) mesh
    config: dict
    traffic: dict
    end_to_end: list[dict]  # BENCHMARK.json metric entries this cell reports
    per_layer: list[dict]


def load_benchmark(root: pathlib.Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _mesh(name: str, chips: int, config: dict) -> tuple[int, int]:
    """The service's (data, model) mesh: the configuration's ``mesh``, else
    ``(chips, 1)``; its size must be the workload's and the configuration's
    ``chips``."""
    shape = tuple(int(n) for n in config.get("mesh", (chips, 1)))
    stated = int(config.get("chips", chips))
    if len(shape) != len(MESH_AXES) or {math.prod(shape)} != {chips, stated}:
        raise ValueError(
            f"{name}: the mesh {list(shape)} over {MESH_AXES} does not fit the "
            f"workload's {chips} chips and the configuration's {stated}")
    return shape


def find_cell(name: str, bench: dict, bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    config = json.loads((bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    chips = int(w["chips"])
    return Cell(
        name=name, chips=chips, mesh=_mesh(name, chips, config), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
