"""Family ``phi4_flash`` on the CPU: the toy cell rehearsed end to end
with the cell's own ``per_layer`` list, the yardstick's counts for the
published configuration by hand, the configuration against the
catalog's row, the controls, and that the cell and its metrics are in
the manifest and the files (membership, never last or only)."""

import ast
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark import flops, peaks
from benchmark.controls import phi4_flash as controls
from benchmark.families import phi4_flash as family
from benchmark.kernel_work import flash_bwd, flash_fwd
from tests.benchmark import membership

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONTROLS = os.path.join(cell_files.HERE, "controls", "phi4_flash_cells")
CONFIG = "phi-4-mini-flash"
CELL = "phi-4-mini-flash.steady"
# The five this PR brings, each a data file on the accepted reader.
NEW = {
    "selscan_ms_per_step.train": {"scope": "selscan", "nested": True},
    "gmu_ms_per_step.train": {"scope": "gmu", "nested": True},
    "attn_cross_ms_per_step.train": {"scope": "attn_cross", "nested": True},
    "attn_diff_ms_per_step.train": {"scope": "attn_diff", "nested": True},
    "ssm_ms_per_step.train": {"scope": "ssm"},
}
ACCEPTED = (
    "mfu.train", "attn_ms_per_step.train", "attn_window_ms_per_step.train",
    "attn_full_ms_per_step.train", "mlp_ms_per_step.train",
    "head_ms_per_step.train", "embed_ms_per_step.train",
    "optimizer_ms_per_step.train", "accumulate_ms_per_step.train",
    "layer_scan_ms_per_step.train", "unscoped_ms_per_step.train",
    "flash_bwd_ms_per_step.train", "pallas_ms_per_step.train",
    "step_hbm_gb.train", "step_programs.train", "dispatch_ms.train",
    "data_wait_ms.train", "flash_fwd_roofline.train",
    "flash_bwd_roofline.train",
)
# The two the scans' own time reads, which no cell had a copy of.
OWN_OF_THE_SCANS = ("accumulate_ms_per_step.train",
                    "layer_scan_ms_per_step.train")
STAGES = ("step_trace_lower_s", "trace_lower_s", "compile_s", "cache_load_s",
          "compile_requests", "price_step_s")
SETUP_METRICS = tuple(f"{stage}.setup" for stage in STAGES)
# What a run off the chip has to read: the host's clocks and the
# program's own counters and spans.
OFF_CHIP = {
    "step_programs.train", "step_hbm_gb.train",
    "data_wait_ms.train", "dispatch_ms.train",
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What a control's files may leave out of the cell's: words, not numbers.
WORDS = ("deployment", "source")
V5E = "TPU v5 lite"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name=CONFIG, root=cell_files.HERE):
    return _json(root, "configs", name + ".json")


@pytest.fixture(scope="module")
def manifest():
    return _json(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def traced_line():
    """One traced rehearsal: the untraced line's end-to-end metrics are
    in its ``detail.window``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cell_files.HERE, "run.py"),
         "--workload", "toy-phi4.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=260,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_toy_cell_rehearsal_prints_a_correct_line(traced_line):
    line = traced_line
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["detail"]["reference"]["rms_rel"] < 3e-4
    window = line["detail"]["window"]
    assert window["tokens_per_s"] > 0 and window["step_ms_p90"] > 0


def test_traced_rehearsal_runs_the_cell_s_own_readers(traced_line):
    """The toy cell stands for the cell and names its list: one step
    program for eight layers in four runs; the readers of a device
    plane or a peak find none off the chip, return nothing and do not
    raise."""
    line = traced_line
    toy = cell_files.load_cell("toy-phi4.steady", TOY)["workload"]
    cell = cell_files.load_cell(CELL)["workload"]
    assert toy["stands_for"] == CELL and toy["per_layer"] == cell["per_layer"]
    assert line["metrics"]["step_programs.train"]["value"] == 1
    named = set(cell["per_layer"])
    assert not (named - OFF_CHIP - set(SETUP_METRICS)) & set(line["metrics"])
    assert OFF_CHIP | set(SETUP_METRICS) <= set(line["metrics"])
    assert set(NEW) <= set(line["notes"]["read_nothing"])


# -- the published configuration and its counts, by hand ------------------


def test_published_widths_and_the_cut():
    config = _config()
    for key, value in {
        "hidden_size": 2560, "num_attention_heads": 40,
        "num_key_value_heads": 20, "intermediate_size": 10240,
        "sliding_window": 512, "mb_per_layer": 2, "layer_norm_eps": 1e-05,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "hidden_act": "silu", "max_position_embeddings": 262144,
        "num_hidden_layers": 6, "vocab_size": 25008,
    }.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["reduced_from"] == {
        "num_hidden_layers": 32, "vocab_size": 200064,
    }
    assert family.layer_kinds(config) == [
        "mamba", "attn_window", "mamba_memory", "attn_full", "gmu",
        "attn_cross",
    ]
    for said in ("pipeline", "eight chips", "697,094,272 parameters",
                 "633,068,672", "11.15 GB", "9 Mamba : 8 window : 1 full",
                 "one reader each", "9.2%", "13.3%", "host's share"):
        assert said in config["deployment"], said
    assumed = config["assumed"]
    assert (assumed["sequence_length"], assumed["first_layer"]) == (4096, 14)
    assert (assumed["d_state"], assumed["d_conv"], assumed["expand"],
            assumed["dt_rank"], assumed["scan_chunk"]) == (16, 4, 2, 160, 64)
    assert assumed["attention_bias"] and assumed["conv_bias"]
    for key in ("sequence_length", "first_layer", "d_state", "attention_bias",
                "conv_bias", "differential_attention", "lambda_std",
                "subln_gain", "initializer_range", "dt_min", "A_scale",
                "init_jitter", "scan_chunk", "remat"):
        assert assumed[key + "_why"], key
    # The floors of a model_config cut: every kind of layer and at
    # least four of them, an eighth of the vocabulary.
    assert config["num_hidden_layers"] >= 4
    assert 8 * config["vocab_size"] >= 200064
    cell = cell_files.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["workload"]["micro_batch_per_chip"] == 1
    assert cell["workload"]["traffic"] == "steady"
    # The accepted traffic file, not a copy with other numbers.
    assert cell["traffic"] == cell_files.load_cell("mistral-7b.steady")["traffic"]


def test_configuration_is_the_catalogs_row_but_for_the_cut():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "Phi-4-mini-flash-reasoning"
        )
    config = _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["reduced_from"][key] == value
        else:
            assert config[key] == value, key


def test_shape_and_parameter_count_by_hand():
    config = _config()
    shape = family.shape(config)
    e, inner, mlp = 2560, 5120, 3 * 2560 * 10240
    mamba = e * 2 * inner + inner * (160 + 32) + 160 * inner + inner * e + mlp
    attention = e * (2560 + 1280 + 1280) + 2560 * e + mlp
    gmu, cross = 2 * e * inner + mlp, 2 * e * 2560 + mlp
    assert (mamba, attention, gmu, cross) == (
        119_767_040, 98_304_000, 104_857_600, 91_750_400,
    )
    assert shape["matmul_params_by_kind"] == {
        "mamba": mamba, "mamba_memory": mamba, "attn_window": attention,
        "attn_full": attention, "gmu": gmu, "attn_cross": cross,
    }
    assert shape["layers"] * shape["layer_matmul_params"] == pytest.approx(
        2 * mamba + 2 * attention + gmu + cross
    )
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"],
            shape["v_head_dim"]) == (20, 10, 64, 128)
    assert (shape["sliding_layers"], shape["full_layers"]) == (1, 2)
    assert (shape["sliding_window"], shape["full_window"]) == (512, None)
    assert shape["flash_calls_per_layer"] == 2
    assert (shape["vocab_rows"], shape["seq_len"], shape["window"]) == (
        25008, 4096, None,
    )
    assert (shape["mamba_layers"], shape["gmu_layers"], shape["scan_channels"],
            shape["scan_states"], shape["scan_chunk"]) == (2, 1, 5120, 16, 64)
    built = family.build(config)
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = lambda tree: sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree)
    )
    # With the vectors: norms, biases, the convolution, A, D, lambdas.
    by_kind = {
        kind: count(tree)
        for run in shapes["runs"].values() for kind, tree in run.items()
    }
    assert by_kind == {
        "mamba": 119_895_040, "mamba_memory": 119_895_040,
        "attn_window": 98_322_304, "attn_full": 98_322_304,
        "gmu": 104_867_840, "attn_cross": 91_766_144,
    }
    assert sum(by_kind.values()) == 633_068_672
    assert count(shapes["wte"]) == 64_020_480
    assert count(shapes) == 697_094_272
    assert f"{count(shapes) * 16 / 1e9:.2f}" == "11.15"
    cfg = built["cfg"]
    assert cfg.remat == "full" and cfg.first_layer == 14
    assert [(count, index) for _, _, count, index in cfg.runs] == [
        (1, 14), (1, 16), (1, 17), (1, 18),
    ]
    assert built["seq_len"] == 4096 and built["vocab"] == 25008


def test_required_operations_by_hand():
    """4.40 GFLOP a token, 1.80e13 a step: 6 x the matrix parameters a
    token passes, both maps' causal products in each attention layer
    over its own mean keys (480.06 under the window, 2,048.5 without),
    three times the scan's forward a Mamba layer."""
    shape = family.shape(_config())
    assert flops.mean_keys(4096, 512) == 480.0625
    assert flops.mean_keys(4096, None) == 2048.5
    matrices = 632_750_080 + 25008 * 2560
    assert matrices == 696_770_560
    # Two calls a layer, forward and twice that backward, each 2 x 20
    # heads x (64 + 128) columns x keys.
    attention = 3 * 2 * 2 * 20 * 192 * (480.0625 + 2 * 2048.5)
    scan = 3 * 2 * (7 * 5120 * 16 + 3 * 5120)
    want = 6 * matrices + attention + scan
    assert family.flops_per_token(shape) == pytest.approx(want)
    assert flops.train_flops_per_token(_config()) == family.flops_per_token(shape)
    assert f"{want / 1e9:.3g}" == "4.4"
    assert f"{want * 4096:.3g}" == "1.8e+13"
    assert round(attention / 1e6) == 211 and round(scan / 1e6, 1) == 3.5
    # The head is 9.2% of the matrix parameters a token passes here.
    assert round(1000 * 25008 * 2560 / matrices) == 92
    # The whole step's required operations take 91 ms at the peak.
    chip = peaks.chip_peaks(V5E)
    assert round(1e3 * want * 4096 / chip["bf16_flops_per_s"]) == 91


def test_the_flash_counts_reach_from_the_shape_s_keys():
    """Differential attention is the accepted modules' reading of
    ``heads`` 20, ``head_dim`` 64, ``v_head_dim`` 128 and one sliding
    and two full layers: ``trace_events`` multiplies one call's work by
    the six calls it finds a step, so the mean over a period's three
    kinds of call times six is the step's sum."""
    shape = family.shape(_config())
    for kernel, passes in ((flash_fwd, 1), (flash_bwd, 2)):
        sliding = kernel.one_call(dict(shape, window=512), 1)
        full = kernel.one_call(dict(shape, window=None), 1)
        mean = kernel.work(shape, 1)
        for key in ("flops", "bytes"):
            assert 3 * mean[key] == pytest.approx(sliding[key] + 2 * full[key])
        assert sliding["flops"] == passes * 2.0 * 20 * 192 * 4096 * 480.0625
        assert full["flops"] == passes * 2.0 * 20 * 192 * 4096 * 2048.5
    # Forward and backward of the six calls are the family's count of
    # attention, to the last digit.
    calls = 2 * 3
    step = calls * (flash_fwd.work(shape, 1)["flops"] + flash_bwd.work(shape, 1)["flops"])
    assert step == pytest.approx(
        4096 * 3 * 2 * 2 * 20 * 192 * (480.0625 + 2 * 2048.5)
    )
    chip = peaks.chip_peaks(V5E)
    for kernel in ("flash_fwd", "flash_bwd"):
        work = flops.kernel_work(kernel, _config(), 1)
        least = flops.roofline_seconds(work, chip)["seconds"]
        assert least == pytest.approx(max(
            work["flops"] / chip["bf16_flops_per_s"],
            work["bytes"] / chip["hbm_bytes_per_s"],
        ))


def test_shape_stays_off_jax_and_off_the_model():
    code = (
        "import sys, json\n"
        "from benchmark import flops\n"
        "from benchmark import cell\n"
        f"c = cell.load_cell({CELL!r})\n"
        "flops.shape_of(c['config'])\n"
        "flops.train_flops_per_token(c['config'])\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('dlrover_tpu')]\n"
        "print(json.dumps(bad))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=60, env=dict(os.environ, PYTHONPATH=REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


def test_a_cell_of_another_family_imports_nothing_this_pr_added():
    code = (
        "import sys, json\n"
        "from benchmark import cell\n"
        "from benchmark.families import gpt\n"
        "gpt.build(cell.load_cell('gpt2-124m.steady')['config'])\n"
        "bad = [m for m in sys.modules if 'phi4' in m or 'selective_scan' in m]\n"
        "print(json.dumps(bad))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


def test_the_reference_is_plain_and_its_own():
    """Nothing from the program, no custom rule, no kernel, no chunk;
    float32 at "highest"; the equations and the departures stated."""
    path = os.path.join(cell_files.HERE, "reference", "phi4_flash.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {
        "__future__", "math", "jax", "jax.numpy", "benchmark.reference",
        "benchmark.families.phi4_flash",
    }
    code = "\n".join(
        line for line in source.split('"""', 2)[2].splitlines()
        if not line.strip().startswith("#")
    )
    for banned in ("dlrover_tpu", "custom_vjp", "pallas", "checkpoint",
                   "associative_scan", "chunk", "flash_attention"):
        assert banned not in code, banned
    assert 'default_matmul_precision("highest")' in code
    doc = ast.get_docstring(tree)
    for said in ("ONE TOKEN AT A TIME", "lam0 = 0.8 - 0.6 exp(-0.3 l)",
                 "(t - sliding_window, t]", "m = y", "i //", "Departures"):
        assert said in doc, said


# -- the manifest and the files: membership --------------------------------


def test_manifest_lists_the_cell(manifest):
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cell = membership.assert_cell_is_listed(manifest, CELL)
    assert cell["config"] == CONFIG
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == _config()["reduced"]
    assert config["source"] == _config()["source"]
    workload = _json(cell_files.HERE, "workloads", CELL + ".json")
    assert workload["why"] == cell["why"] and len(cell["why"]) <= 200
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL not in e2e["save_stall_ms"]["workloads"]


def _named_here():
    return set(cell_files.load_cell(CELL)["workload"]["per_layer"])


@pytest.mark.parametrize("name", ACCEPTED + SETUP_METRICS)
def test_manifest_lists_the_cell_in_the_accepted_metrics_it_names(
    manifest, name
):
    """A member of each list it names, wherever a later cell stands;
    a flash roofline is named only while the chip reads it under 100%
    (PERF.md section 6, PR 64), so those two may be absent from both."""
    if name not in _named_here():
        assert name.endswith("_roofline.train"), name
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
        return
    spec = membership.assert_cell_reports(manifest, CELL, name)
    assert spec["moves"] == (
        "setup_s" if name in SETUP_METRICS else "tokens_per_s")
    if name not in OWN_OF_THE_SCANS:
        membership.assert_reads_as_its_copy_did(spec)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_new_metrics_are_data_on_the_accepted_reader(manifest, name):
    spec = membership.assert_cell_reports(manifest, CELL, name)
    assert (spec["reader"], spec["args"]) == ("scope_time", NEW[name])
    assert (spec["unit"], spec["better"], spec["moves"], spec["source"],
            spec["layer"]) == ("ms", "lower", "tokens_per_s", "device_trace",
                               "model")
    assert spec["restricted"] is True
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


# -- the controls -----------------------------------------------------------


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_is_the_cell_but_for_the_broken_path(name):
    cell = cell_files.load_cell(CELL)
    control = cell_files.load_cell(f"{CONFIG}.{name}", CONTROLS)
    assert control["traffic"] == cell["traffic"]
    assert control["chips"] == cell["chips"]
    for key in ("micro_batch_per_chip", "traffic", "steps_per_sample"):
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
    """The toy family in float32 with weights large enough that every
    path weighs in the loss (at bf16 and toy widths the rounding of
    two short sequences is as large as a broken path): (the loss as a
    partial on its configuration, parameters, a batch, the honest
    loss, the reference's)."""
    import dataclasses
    import functools

    import jax.numpy as jnp

    from dlrover_tpu.models import phi4_flash as model

    config = _config("toy-phi4", TOY)
    config["assumed"] = dict(config["assumed"], initializer_range=0.2)
    built = family.build(config)
    cfg = dataclasses.replace(built["cfg"], dtype=jnp.float32)
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    params = jax.jit(functools.partial(model.init_params, cfg=cfg))(
        jax.random.PRNGKey(3)
    )
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, built["seq_len"] + 1), 0, built["vocab"]
    )
    batch = tok[:, :-1], tok[:, 1:]
    return (
        loss, params, batch, float(jax.jit(loss)(params, *batch)),
        float(built["reference_loss"](params, *batch)),
    )


def test_the_toy_program_agrees_with_its_reference(toy):
    _, _, _, honest, want = toy
    assert honest == pytest.approx(want, rel=2e-6)


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_is_refused_at_toy_widths_and_still_trains(toy, name):
    loss, params, batch, honest, want = toy
    broken = controls.broken(name, loss)
    value, grads = jax.jit(jax.value_and_grad(broken))(params, *batch)
    assert np.isfinite(float(value))
    # Refused: further from the reference than the one tolerance every
    # cell shares, 3e-4 (6.9e-4 to 1.2e-2 here; what the chip's check
    # reads at the published widths is in PERF.md, PR 64).
    assert abs(float(value) - want) > 3e-4 * want
    # One path is broken, not the model: the loss stays near.
    assert abs(float(value) - honest) < 0.2 * honest
    assert all(
        bool(np.all(np.isfinite(np.asarray(g, np.float32))))
        for g in jax.tree.leaves(grads)
    )
    # The program is whole again once the broken loss is traced.
    assert float(jax.jit(loss)(params, *batch)) == honest


def test_a_control_reaches_the_harness_through_the_configuration():
    """``"control": <name>`` in a configuration file is what the
    family's ``build`` hands to this module."""
    config = _config(f"{CONFIG}.no_carry", CONTROLS)
    built = family.build(config)
    assert built["loss"].__name__ == "traced_broken"
    assert family.build(_config())["loss"].func.__name__ == "loss_fn_fused"


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        controls.broken("no_such_path", lambda *a: None)
