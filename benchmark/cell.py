"""Finds a cell's files by the names in it. No JAX here: the parent of
the resume cell imports this and must stay off the chip.

``workloads/<name>.json`` names a configuration and a traffic mix;
``configs/<config>.json`` and ``traffic/<traffic>.json`` hold them. A
later PR adds a cell by adding files; nothing here knows a name.
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


def mesh_shape(traffic: dict, chips: int) -> tuple:
    """``{"fsdp": "chips"}`` -> (("fsdp", chips),); a number is itself."""
    return tuple(
        (axis, chips if size == "chips" else int(size))
        for axis, size in traffic["mesh"].items()
    )
