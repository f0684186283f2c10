"""Family ``ouro`` on the CPU: the toy cell rehearsed end to end, the
yardstick's counts for the published configuration by hand, the two
new readers on hand-made tables, the configuration against the
catalog's row, the controls, and the form of what PR 44 added to the
manifest (the rules a driver holds it to before any chip: PR 41 was
refused on a ``why`` of 201 characters that no test here counted)."""

import ast
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark import flops
from benchmark.controls import ouro as controls
from benchmark.families import ouro as family
from benchmark.readers import loop_time, model_flops, scope_time
from tests.benchmark import membership

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONTROLS = os.path.join(cell_files.HERE, "controls", "ouro_cells")
CONFIG = "ouro-2.6b"
CELL = "ouro-2.6b.steady"
METRICS = ("mfu.train", "ut_loop_own_ms_per_step.train",
           "exit_gate_ms_per_step.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What a control's files may leave out of the cell's: words, not numbers.
WORDS = ("deployment", "reduced_from", "source")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name=CONFIG, root=cell_files.HERE):
    return _json(root, "configs", name + ".json")


@pytest.fixture(scope="module")
def manifest():
    return _json(REPO, "BENCHMARK.json")


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cell_files.HERE, "run.py"),
         "--workload", "toy-ouro.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", str(trace), "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=220,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["detail"]["reference"]["rms_rel"] < 3e-4
    return line


def test_toy_ouro_cell_rehearsal_prints_a_correct_line():
    line = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p90"}


def test_traced_rehearsal_reports_no_device_metric():
    """The toy cell names what the cell names; its readers find no
    device plane and no peak off the chip, return nothing and do not
    raise, and the line says which they were."""
    line = _rehearse(1)
    assert line["metrics"]["step_trace_lower_s.setup"]["value"] > 0
    assert not set(METRICS) & set(line["metrics"])
    assert set(METRICS) <= set(line["notes"]["read_nothing"])


# -- the published configuration and its counts, by hand ------------------


def test_published_widths_and_the_cut():
    config = _config()
    for key, value in {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 128,
        "intermediate_size": 5632, "rope_theta": 1000000,
        "rms_norm_eps": 1e-06, "total_ut_steps": 4,
        "max_position_embeddings": 65536, "tie_word_embeddings": False,
        "num_hidden_layers": 8, "vocab_size": 8192,
    }.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["reduced_from"] == {
        "num_hidden_layers": 48, "vocab_size": 49152,
    }
    assert "six" in config["deployment"] and "pipeline" in config["deployment"]
    assumed = config["assumed"]
    assert assumed["sequence_length"] == 4096
    for key in ("sequence_length", "exit_entropy_coef", "initializer_range",
                "init_jitter", "remat"):
        assert assumed[key + "_why"], key
    # The floors of a model_config cut: four layers and an eighth of
    # the vocabulary at least, a whole number of both.
    assert config["num_hidden_layers"] >= 4 and 48 % config["num_hidden_layers"] == 0
    assert 8 * config["vocab_size"] >= 49152 and 49152 % config["vocab_size"] == 0
    cell = cell_files.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["workload"]["micro_batch_per_chip"] == 1
    assert cell["workload"]["steps_per_sample"] == 1
    assert cell["workload"]["traffic"] == "steady"


def test_configuration_is_the_catalogs_row_but_for_the_cut():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    config = _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["reduced_from"][key] == value
        else:
            assert config[key] == value, key


def test_shape_and_parameter_count_by_hand():
    shape = family.shape(_config())
    # wq, wk, wv, wo 2048^2 each; gate, up, down 2048 x 5632 each.
    assert shape["layer_matmul_params"] == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert shape["layer_matmul_params"] == 51_380_224
    assert (shape["layers"], shape["ut_steps"]) == (8, 4)
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) == (16, 16, 128)
    assert (shape["vocab_rows"], shape["seq_len"], shape["window"]) == (
        8192, 4096, None,
    )
    built = family.build(_config())
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # 8 layers' matrices and 4 norms each; two tables; the final norm;
    # the gate's weight and bias.
    assert count == (
        8 * 51_380_224 + 8 * 4 * 2048 + 2 * 8192 * 2048 + 2048 + 2049
    ) == 444_665_857
    assert built["cfg"].ut_steps == 4 and built["cfg"].remat == "full"
    assert built["seq_len"] == 4096 and built["vocab"] == 8192


def test_required_operations_a_token_by_hand():
    """11.9 GFLOP a token at the cut: every matrix four times."""
    shape = family.shape(_config())
    matrices = 8 * 51_380_224 + 8192 * 2048
    attention = 12 * 8 * 16 * 128 * (4096 + 1) / 2
    assert flops.mean_keys(4096) == 2048.5
    assert (matrices, attention) == (427_819_008, 402_751_488)
    want = 4 * (6 * matrices + attention)
    assert family.flops_per_token(shape) == want == 11_878_662_144
    assert f"{want / 1e9:.4g}" == "11.88"
    # The yardstick's count asks the family first (since PR 63: one
    # ``mfu.train``); the dense stack's count is a pass's, a quarter.
    assert flops.train_flops_per_token(_config()) == want
    dense = 6.0 * flops.matmul_params(_config()) + (
        flops.attention_flops_per_token(_config())
    )
    assert dense == want / 4


def test_the_whole_step_s_share_reads_the_rate_and_nothing_without_one():
    cell = cell_files.load_cell(CELL)
    ctx = {
        "cell": cell, "window": {"tokens_per_s": 8820.0},
        "device": {"count": 1}, "peaks": {"bf16_flops_per_s": 197e12},
    }
    want = 100 * 11_878_662_144 * 8820.0 / 197e12
    assert model_flops.read(ctx) == pytest.approx(want, rel=1e-9)
    assert 0 < model_flops.read(ctx) < 100
    assert model_flops.read(dict(ctx, peaks=None)) is None
    assert model_flops.read(dict(ctx, window={})) is None


def test_loop_time_on_a_hand_made_table():
    """The loop's own time: the instructions whose innermost scope is
    the loop, not those of the layers inside it nor the trainer's."""
    reduced = {"steps": 2, "ops": {
        "while.1": {"seconds": 0.004},          # the outer scan itself
        "add.7": {"seconds": 0.010},            # the weights' gradients summed
        "fusion.3": {"seconds": 0.100},         # a layer's product
        "fusion.9": {"seconds": 0.002},         # the gate
        "fusion.11": {"seconds": 0.006},        # the accumulator's add
        "copy.2": {"seconds": 0.001},           # not in the description
    }}
    description = {
        "while.1": {"scope": "accumulate/ut_loop", "pass": "fwd"},
        "add.7": {"scope": "accumulate/ut_loop", "pass": "bwd"},
        "fusion.3": {"scope": "accumulate/ut_loop/layers/mlp", "pass": "fwd"},
        "fusion.9": {"scope": "accumulate/exit_gate", "pass": "fwd"},
        "fusion.11": {"scope": "accumulate", "pass": "fwd"},
        "never_ran.1": {"scope": "accumulate/ut_loop", "pass": "fwd"},
    }
    assert loop_time.own_ms(reduced, description, "ut_loop") == pytest.approx(7.0)
    assert loop_time.own_ms(reduced, description, "exit_gate") == pytest.approx(1.0)
    assert loop_time.own_ms(reduced, description, "layers") is None
    # No device plane: nothing, and no description is asked for.
    assert loop_time.read({"trace": {}}, scope="ut_loop") is None
    assert loop_time.read({}, scope="ut_loop") is None
    assert scope_time.read({}, scope="exit_gate", nested=True) is None


def test_loop_time_joins_the_trace_to_the_programs_description(monkeypatch):
    """Through ``scope_time``'s table: the loop's own time, the gate's
    whole time, nothing for a program that never enters the scope, and
    nothing where the description is not of the program that ran."""
    reduced = {"steps": 1, "device_ops": [], "ops": {
        "while.1": {"seconds": 0.004}, "fusion.3": {"seconds": 0.100},
        "fusion.9": {"seconds": 0.002}, "fusion.10": {"seconds": 0.001},
    }}
    description = {
        "while.1": {"scope": "accumulate/ut_loop", "pass": "fwd"},
        "fusion.3": {"scope": "accumulate/ut_loop/layers/mlp", "pass": "fwd"},
        "fusion.9": {"scope": "accumulate/exit_gate", "pass": "fwd"},
        "fusion.10": {"scope": "accumulate/exit_gate", "pass": "bwd"},
    }
    monkeypatch.setattr(scope_time, "describe", lambda: description)
    ctx = {"trace": reduced}
    assert loop_time.read(ctx, scope="ut_loop") == pytest.approx(4.0)
    assert scope_time.read(ctx, scope="exit_gate", nested=True) == pytest.approx(3.0)
    assert loop_time.read(ctx, scope="ssm") is None
    assert ctx["notes"]["scope_split"]["mlp"]["fwd"] == pytest.approx(100.0)
    # The loop's and the gate's instructions fall to the trainer's scan
    # in scope_time's own partition.
    assert sum(ctx["notes"]["scope_split"]["accumulate"].values()) == (
        pytest.approx(7.0)
    )
    assert ctx["notes"]["scope_inner_ms"]["ut_loop"] == pytest.approx(104.0)
    stranger = {"other.1": {"scope": "accumulate", "pass": "fwd"}}
    monkeypatch.setattr(scope_time, "describe", lambda: stranger)
    assert loop_time.read({"trace": reduced}, scope="ut_loop") is None


def test_shape_stays_off_jax_and_off_the_model():
    code = (
        "import sys, json\n"
        "from benchmark import flops\n"
        "from benchmark import cell\n"
        f"c = cell.load_cell({CELL!r})\n"
        "flops.shape_of(c['config'])\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('dlrover_tpu')]\n"
        "print(json.dumps(bad))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=60, env=dict(os.environ, PYTHONPATH=REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


def test_the_reference_is_plain_and_its_own():
    """Nothing from the program, no scan, no custom rule, no kernel;
    float32 at "highest"; the equations and the departures stated."""
    path = os.path.join(cell_files.HERE, "reference", "ouro.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "jax", "jax.numpy"}
    code = "\n".join(
        line for line in source.split('"""', 2)[2].splitlines()
        if not line.strip().startswith("#")
    )
    for banned in ("dlrover_tpu", "scan", "custom_vjp", "pallas", "lax.map",
                   "while_loop", "fori_loop", "checkpoint"):
        assert banned not in code, banned
    assert 'default_matmul_precision("highest")' in code
    doc = ast.get_docstring(tree)
    for said in ("n2(attn(n1(x)))", "n4(swiglu(n3(a)))", "norm(stack(",
                 "p_last", "beta * H", "Departures"):
        assert said in doc, said


# -- the manifest's form ---------------------------------------------------


def _printable_line(text):
    return (
        isinstance(text, str) and 1 <= len(text) <= 200
        and all(" " <= ch <= "~" for ch in text)
    )


def test_manifest_lists_the_cell(manifest):
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cell = membership.assert_cell_is_listed(manifest, CELL)
    assert cell["config"] == CONFIG
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL not in e2e["save_stall_ms"]["workloads"]


@pytest.mark.parametrize("name", METRICS + (
    "compile_s.setup", "cache_load_s.setup", "boot_s.setup",
))
def test_manifest_lists_the_cell_in_its_metrics(manifest, name):
    spec = membership.assert_cell_reports(manifest, CELL, name)
    if name in METRICS:
        assert spec["moves"] == "tokens_per_s" and spec["layer"] == "model"
    want = {
        "mfu.train": ("model_flops", {}),
        "ut_loop_own_ms_per_step.train": ("loop_time", {"scope": "ut_loop"}),
        "exit_gate_ms_per_step.train": (
            "scope_time", {"scope": "exit_gate", "nested": True}),
    }.get(name)
    if want:
        assert (spec["reader"], spec.get("args", {})) == want


def test_every_line_this_pr_added_to_the_manifest_is_of_the_contracts_form(
    manifest,
):
    """``why``, ``source`` and ``layer``: 1 to 200 printable ASCII
    characters on one line; each entry has just its keys; the cell is
    one-chip. (The manifest's own limits: ``test_cells_cpu.py``.)"""
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert _printable_line(config["why"]) and _printable_line(config["source"])
    assert _printable_line(cell["why"])
    assert len(config["reduced"]) <= 16
    workload = _json(cell_files.HERE, "workloads", CELL + ".json")
    assert _printable_line(workload["why"]) and workload["why"] == cell["why"]
    assert _config()["source"] == config["source"]
    assert cell["chips"] == 1 == workload["chips"]
    for m in manifest["per_layer"]:
        if m["name"] in METRICS:
            assert _printable_line(m["layer"]) and "\t" not in m["layer"]


@pytest.mark.parametrize("text,ok", [
    ("x", True), ("a" * 200, True), ("", False), ("a" * 201, False),
    ("two\nlines", False), ("a\ttab", False), ("café", False),
])
def test_the_form_check_refuses_what_the_driver_refuses(text, ok):
    assert _printable_line(text) is ok


# -- the controls -----------------------------------------------------------


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_is_the_cell_but_for_the_broken_path(name):
    cell = cell_files.load_cell(CELL)
    control = cell_files.load_cell(f"{CONFIG}.{name}", CONTROLS)
    assert control["traffic"] == cell["traffic"]
    assert control["chips"] == cell["chips"]
    for key in ("micro_batch_per_chip", "steps_per_sample", "traffic"):
        assert control["workload"][key] == cell["workload"][key]
    config = dict(control["config"])
    assert config.pop("control") == name
    assert config.pop("name") == f"{CONFIG}.{name}"
    assumed = config.pop("assumed")
    assert config == {
        k: v for k, v in cell["config"].items()
        if k not in WORDS + ("name", "assumed")
    }
    assert assumed == {
        k: v for k, v in cell["config"]["assumed"].items()
        if not k.endswith("_why")
    }


def test_every_control_has_its_cell_and_nothing_else_is_there():
    names = {f"{CONFIG}.{name}.json" for name in controls.NAMES}
    assert set(os.listdir(os.path.join(CONTROLS, "configs"))) == names
    assert set(os.listdir(os.path.join(CONTROLS, "workloads"))) == names
    assert os.listdir(os.path.join(CONTROLS, "traffic")) == ["steady.json"]


@pytest.fixture(scope="module")
def toy():
    config = _config("toy-ouro", TOY)
    honest = family.build(config)
    params = jax.jit(honest["init"])(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, honest["seq_len"] + 1), 0, honest["vocab"]
    )
    batch = tok[:, :-1], tok[:, 1:]
    return config, params, batch, float(jax.jit(honest["loss"])(params, *batch))


def test_the_toy_program_agrees_with_its_reference(toy):
    config, params, batch, honest = toy
    want = float(family.build(config)["reference_loss"](params, *batch))
    assert honest == pytest.approx(want, rel=3e-4)


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_breaks_the_loss_and_still_trains(toy, name):
    config, params, batch, honest = toy
    broken = family.build(dict(config, control=name))["loss"]
    loss, grads = jax.jit(jax.value_and_grad(broken))(params, *batch)
    assert np.isfinite(float(loss))
    # Another loss, by far more than float32 rounds (what the chip's
    # check reads at the published widths is in PERF.md, PR 44).
    assert abs(float(loss) - honest) > 1e-4 * honest
    # One path is broken, not the model: the loss stays near.
    assert abs(float(loss) - honest) < 5e-2 * honest
    assert all(
        bool(np.all(np.isfinite(np.asarray(g, np.float32))))
        for g in jax.tree.leaves(grads)
    )
    # The program is whole again once the broken loss is traced.
    again = family.build(config)["loss"]
    assert float(jax.jit(again)(params, *batch)) == honest


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        controls.broken("no_such_path", family.build(_config("toy-ouro", TOY))["cfg"])
