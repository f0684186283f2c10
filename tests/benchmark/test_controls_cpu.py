"""benchmark/controls/: cells whose program is broken on purpose.

A control has to be the benchmark's own cell in everything but the
broken path, or its reading says nothing about the cell: each
control's files are held to the cell's here, and the family's
``build`` is tried on a toy configuration with each control (the loss
still traces, differentiates, and is another loss)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.controls import granite_hybrid as controls
from benchmark.families import granite_hybrid as family

ROOT = os.path.join(cell_files.HERE, "controls")
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONFIG = "granite-4.0-h-micro"
# What a control's files may leave out of the cell's: words, not numbers.
WORDS = ("deployment", "reduced_from", "source")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_is_the_cell_but_for_the_broken_path(name):
    cell = cell_files.load_cell(f"{CONFIG}.steady")
    control = cell_files.load_cell(f"{CONFIG}.{name}", ROOT)
    assert control["traffic"] == cell["traffic"]
    assert control["chips"] == cell["chips"]
    for key in ("micro_batch_per_chip", "steps_per_sample", "traffic"):
        assert control["workload"][key] == cell["workload"][key]
    config = dict(control["config"])
    assert config.pop("control") == name
    assert config.pop("name") == f"{CONFIG}.{name}"
    want = {
        k: v for k, v in cell["config"].items()
        if k not in WORDS + ("name", "assumed")
    }
    assumed = config.pop("assumed")
    assert config == want
    assert assumed == {
        k: v for k, v in cell["config"]["assumed"].items()
        if not k.endswith("_why")
    }


def test_every_control_has_its_cell_and_nothing_else_is_there():
    names = {f"{CONFIG}.{name}.json" for name in controls.NAMES}
    assert set(os.listdir(os.path.join(ROOT, "configs"))) == names
    assert set(os.listdir(os.path.join(ROOT, "workloads"))) == names
    assert os.listdir(os.path.join(ROOT, "traffic")) == ["steady.json"]


@pytest.fixture(scope="module")
def toy():
    config = _json(TOY, "configs", "toy-granite.json")
    # Long memories, so that the carried state weighs in the loss.
    config["assumed"] = dict(config["assumed"], A_scale=1 / 256, dt_max=0.5)
    honest = family.build(config)
    params = jax.jit(honest["init"])(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, honest["seq_len"] + 1), 0, honest["vocab"]
    )
    batch = tok[:, :-1], tok[:, 1:]
    return config, params, batch, float(jax.jit(honest["loss"])(params, *batch))


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_breaks_the_loss_and_still_trains(toy, name):
    config, params, batch, honest = toy
    broken = family.build(dict(config, control=name))["loss"]
    loss, grads = jax.jit(jax.value_and_grad(broken))(params, *batch)
    assert np.isfinite(float(loss)) and float(loss) != honest
    # One path is broken, not the model: the loss stays near.
    assert abs(float(loss) - honest) < 1e-2 * honest
    assert all(
        bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
        for g in jax.tree.leaves(grads)
    )
    # The program is whole again once the broken loss is traced.
    again = family.build(config)["loss"]
    assert float(jax.jit(again)(params, *batch)) == honest


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        controls.broken("no_such_path", lambda *a: None)
