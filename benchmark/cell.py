"""Finds a cell's files by the names in it. No JAX here: the parent of
the resume cell imports this and must stay off the chip.

``workloads/<name>.json`` names a configuration and a traffic mix;
``configs/<config>.json`` and ``traffic/<traffic>.json`` hold them. A
later PR adds a cell by adding files; nothing here knows a name.

Which cells report a per-layer metric is said by the cells: a file
under ``layer_metrics/`` with ``"restricted": true`` is reported by the
cells whose workload file names it under ``per_layer``, and one
without by every cell. So a new cell joins an accepted metric by
naming it, and no metric file is copied or edited for it.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class CellError(Exception):
    """The cell cannot be run as asked: no result line is printed."""


def _load(kind: str, name: str, root: str) -> dict:
    if not NAME_RE.match(name):
        raise CellError(f"{kind} name {name!r} is not a name")
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = HERE) -> dict:
    """{"name", "workload", "config", "traffic", "chips"}: the three
    files of one cell, as written."""
    w = _load("workloads", workload, root)
    cell = {
        "name": workload,
        "workload": w,
        "config": _load("configs", w["config"], root),
        "traffic": _load("traffic", w["traffic"], root),
        "chips": int(w["chips"]),
    }
    if cell["chips"] not in (1, 4):
        raise CellError(f"{workload}: chips must be 1 or 4")
    cell_metric_specs(cell)  # a name no file has: refused before any run
    return cell


def layer_metric_specs(root: str = HERE) -> list:
    """Every file under ``layer_metrics/``, sorted by name."""
    d = os.path.join(root, "layer_metrics")
    out = []
    for fname in sorted(os.listdir(d)):
        if fname.endswith(".json"):
            with open(os.path.join(d, fname)) as f:
                spec = json.load(f)
            spec.setdefault("name", fname[: -len(".json")])
            out.append(spec)
    return out


def cell_metric_specs(cell: dict) -> list:
    """The per-layer metrics ``cell`` reports, sorted by name: every
    file that is not ``restricted``, and the restricted ones the cell's
    workload file names under ``per_layer``. A name there that no
    restricted file has is a ``CellError``, not a silent gap."""
    specs = layer_metric_specs()
    restricted = {s["name"] for s in specs if s.get("restricted")}
    named = set(cell["workload"].get("per_layer", []))
    if named - restricted:
        raise CellError(
            f"{cell['name']}: per_layer names {sorted(named - restricted)}, "
            "which no restricted file under layer_metrics/ has"
        )
    return [s for s in specs if s["name"] not in restricted or s["name"] in named]


def mesh_shape(traffic: dict, chips: int) -> tuple:
    """``{"fsdp": "chips"}`` -> (("fsdp", chips),); a number is itself."""
    return tuple(
        (axis, chips if size == "chips" else int(size))
        for axis, size in traffic["mesh"].items()
    )
