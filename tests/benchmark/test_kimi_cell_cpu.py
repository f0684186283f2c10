"""Family ``kimi_linear`` on the CPU: the toy cell rehearsed end to
end, the yardstick's counts for the published configuration by hand,
the new readers on hand-made tables, the configuration against the
catalog's row, the controls, and the form of what PR 53 added to the
manifest (the rules a driver holds it to before any chip)."""

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
from benchmark.controls import kimi_linear as controls
from benchmark.families import kimi_linear as family
from benchmark.kernel_work import flash_bwd, flash_fwd, kda_fwd
from benchmark.readers import model_flops
from tests.benchmark import membership

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONTROLS = os.path.join(cell_files.HERE, "controls", "kimi_cells")
CONFIG = "kimi-linear-48b-a3b"
CELL = "kimi-linear-48b-a3b.steady"
METRICS = (
    "mfu.train", "kda_ms_per_step.train", "kda_scan_ms_per_step.train",
    "mla_ms_per_step.train", "moe_shared_ms_per_step.train",
    "moe_routed_ms_per_step.train", "attn_ms_per_step.train",
    "mlp_ms_per_step.train", "head_ms_per_step.train",
    "optimizer_ms_per_step.train", "step_hbm_gb.train",
    "step_programs.train", "unscoped_ms_per_step.train",
    "embed_ms_per_step.train", "moe_route_ms_per_step.train",
    "moe_experts_ms_per_step.train", "moe_combine_ms_per_step.train",
    "moe_gmm_ms_per_step.train", "flash_fwd_roofline.train",
    "flash_bwd_roofline.train",
    # The start's stages, left out for want of room until PR 63.
    "step_trace_lower_s.setup", "trace_lower_s.setup", "compile_s.setup",
    "cache_load_s.setup", "compile_requests.setup", "price_step_s.setup",
)
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
         "--workload", "toy-kimi.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", str(trace), "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=260,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["detail"]["reference"]["rms_rel"] < 3e-4
    return line


def test_toy_kimi_cell_rehearsal_prints_a_correct_line():
    line = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p90"}


def test_traced_rehearsal_reports_no_device_metric():
    """One step program; the readers of a device plane or a peak find
    none off the chip, return nothing and do not raise."""
    line = _rehearse(1)
    assert line["metrics"]["step_programs.train"]["value"] == 1
    device = {m for m in METRICS if m.endswith(".train")} - {
        "step_programs.train", "step_hbm_gb.train"}
    assert not device & set(line["metrics"])


# -- the published configuration and its counts, by hand ------------------


def test_published_widths_and_the_cut():
    config = _config()
    for key, value in {
        "hidden_size": 2304, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 72, "intermediate_size": 9216,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "q_lora_rank": None, "mla_use_nope": True,
        "moe_intermediate_size": 1024, "num_experts_per_token": 8,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": False, "model_max_length": 1048576,
        "num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480,
    }.items():
        assert config[key] == value, key
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"]) == (32, 128)
    assert linear["short_conv_kernel_size"] == 4
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["reduced_from"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
    }
    for said in ("pipeline", "32 chips", "expert parallel"):
        assert said in config["deployment"], said
    assumed = config["assumed"]
    assert assumed["sequence_length"] == 8192
    assert assumed["router_num_experts"] == 256 and assumed["first_expert"] == 0
    for key in ("sequence_length", "router_num_experts", "first_expert",
                "gate_rank", "initializer_range", "A_min", "init_jitter",
                "router_bias_std", "balancing_step", "remat"):
        assert assumed[key + "_why"], key
    # The floors of a model_config cut: the leading dense layer and a
    # whole period of four behind it, 8 routed experts, an eighth of
    # the vocabulary.
    assert family.layer_kinds(config) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"),
    ]
    assert config["num_experts"] >= 8 and 8 * config["vocab_size"] >= 163840
    cell = cell_files.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["workload"]["micro_batch_per_chip"] == 1
    assert cell["workload"]["traffic"] == "steady"


def test_configuration_is_the_catalogs_row_but_for_the_cut():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "Kimi-Linear-48B-A3B-Instruct"
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
    shape = family.shape(_config())
    e, inner = 2304, 32 * 128
    kda = 3 * e * inner + 2 * (e * 128 + 128 * inner) + e * 32 + inner * e
    mla = e * 32 * 192 + e * 576 + 512 * 32 * 256 + 32 * 128 * e
    assert shape["kda_matmul_params"] == kda == 39_460_864
    assert shape["mla_matmul_params"] == mla == 29_114_368
    assert shape["dense_matmul_params"] == 3 * e * 9216
    # The router's 256 outputs, the shared expert, a quarter of an
    # expert a token (8 x 8 / 256).
    assert shape["moe_matmul_params"] == e * 256 + 1.25 * 3 * e * 1024
    assert (shape["layers"], shape["kda_layers"], shape["mla_layers"]) == (5, 4, 1)
    assert (shape["dense_layers"], shape["moe_layers"]) == (1, 4)
    assert (shape["heads"], shape["head_dim"], shape["v_head_dim"]) == (32, 192, 128)
    assert (shape["vocab_rows"], shape["seq_len"], shape["window"]) == (
        20480, 8192, None,
    )
    assert shape["layers"] * shape["layer_matmul_params"] == pytest.approx(
        4 * kda + mla + 3 * e * 9216 + 4 * shape["moe_matmul_params"]
    )
    built = family.build(_config())
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 602_434_432
    assert built["cfg"].remat == "full" and built["cfg"].held == 8
    assert built["seq_len"] == 8192 and built["vocab"] == 20480


def test_required_operations_by_hand():
    """2.32 GFLOP a token at the cut; the rule is 2% of it."""
    shape = family.shape(_config())
    rule = kda_fwd.work(shape, 1)
    per_token_head = 5 * 64 * 128 + 6 * 128 * 128
    assert rule["flops"] == 8192 * 32 * per_token_head
    assert rule["bytes"] == (
        8192 * 32 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 2)
        + 128 * 32 * 128 * 128 * 4
    )
    matrices = 5 * shape["layer_matmul_params"] + 20480 * 2304
    attention = 6 * 32 * (192 + 128) * flops.mean_keys(8192)
    want = 6 * matrices + attention + 3 * 4 * 32 * per_token_head
    assert family.flops_per_token(shape) == pytest.approx(want)
    assert flops.train_flops_per_token(_config()) == family.flops_per_token(shape)
    assert f"{want / 1e9:.3g}" == "2.32"


def test_flash_work_at_two_head_sizes_by_hand():
    """Queries and keys 192 wide, values 128: the causal half of QK^T
    and PV each at its own size, read from the shape's ``v_head_dim``;
    with one size the count is what it was before the fold."""
    shape = family.shape(_config())
    keys = flops.mean_keys(8192)
    fwd, bwd = flash_fwd.work(shape, 1), flash_bwd.work(shape, 1)
    assert fwd["flops"] == 2.0 * 32 * (192 + 128) * 8192 * keys
    assert fwd["bytes"] == 2.0 * 8192 * 32 * (192 + 128) * 2 + 32 * 8192 * 4.0
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == 4.0 * 8192 * 32 * (192 + 128) * 2 + 2 * 32 * 8192 * 4.0
    # The forward and the backward of the latent layers' attention in
    # the family's ``flops_per_token`` are these three products.
    assert fwd["flops"] + bwd["flops"] == pytest.approx(
        8192 * 6.0 * 32 * (192 + 128) * keys
    )
    for name in ("gpt2-124m.steady", "mistral-7b.steady"):
        one = flops.shape_of(cell_files.load_cell(name)["config"])
        b, t, h, d = 2, one["seq_len"], one["heads"], one["head_dim"]
        keys = flops.mean_keys(t, one["window"])
        assert flash_fwd.work(one, 2) == {
            "flops": 4.0 * b * h * d * t * keys,
            "bytes": 4.0 * b * t * h * d * 2 + b * h * t * 4.0,
        }, name
        assert flash_bwd.work(one, 2) == {
            "flops": 8.0 * b * h * d * t * keys,
            "bytes": 8.0 * b * t * h * d * 2 + 2.0 * b * h * t * 4.0,
        }, name


def test_the_whole_step_s_share_reads_the_rate_and_nothing_without_one():
    cell = cell_files.load_cell(CELL)
    ctx = {
        "cell": cell, "window": {"tokens_per_s": 30000.0},
        "device": {"count": 1}, "peaks": {"bf16_flops_per_s": 197e12},
    }
    shape = family.shape(_config())
    want = 100 * family.flops_per_token(shape) * 30000.0 / 197e12
    assert model_flops.read(ctx) == pytest.approx(want, rel=1e-9)
    assert 0 < model_flops.read(ctx) < 100
    assert model_flops.read(dict(ctx, peaks=None)) is None
    assert model_flops.read(dict(ctx, window={})) is None


def test_scope_readers_on_a_hand_made_table(monkeypatch):
    """``scope_time`` reads one name of its partition, and nested a
    scope inside one; both nothing off the chip and in a program that
    never enters the scope."""
    from benchmark.readers import scope_time

    reduced = {"steps": 2, "device_ops": [], "ops": {
        "fusion.1": {"seconds": 0.020}, "fusion.2": {"seconds": 0.006},
        "fusion.3": {"seconds": 0.004}, "moe_gmm.1": {"seconds": 0.002},
        "fusion.4": {"seconds": 0.008},
    }}
    description = {
        "fusion.1": {"scope": "accumulate/layers/attn/kda/kda_scan", "pass": "fwd"},
        "fusion.2": {"scope": "accumulate/layers/attn/kda/kda_gate", "pass": "bwd"},
        "fusion.3": {"scope": "accumulate/layers/attn/mla", "pass": "fwd"},
        "moe_gmm.1": {"scope": "accumulate/layers/mlp/moe_routed/moe_experts",
                      "pass": "fwd"},
        "fusion.4": {"scope": "accumulate/layers/mlp/moe_shared", "pass": "fwd"},
    }
    monkeypatch.setattr(scope_time, "describe", lambda: description)
    ctx = {"trace": reduced}
    assert scope_time.read(ctx, scope="attn") == pytest.approx(15.0)
    assert scope_time.read(ctx, scope="mlp") == pytest.approx(5.0)
    assert scope_time.read(ctx, scope="ssm") is None
    assert scope_time.read(ctx, scope="kda", nested=True) == pytest.approx(13.0)
    assert scope_time.read(ctx, scope="kda_scan", nested=True) == pytest.approx(10.0)
    assert scope_time.read(ctx, scope="mla", nested=True) == pytest.approx(2.0)
    assert scope_time.read(ctx, scope="moe_routed", nested=True) == pytest.approx(1.0)
    assert scope_time.read(ctx, scope="moe_shared", nested=True) == pytest.approx(4.0)
    assert scope_time.read({"trace": {}}, scope="attn") is None
    assert scope_time.read({}, scope="attn") is None


def test_the_programs_scopes_know_the_new_names():
    from dlrover_tpu.obs import profiling

    for name in ("kda", "kda_conv", "kda_scan", "kda_gate", "mla",
                 "moe_routed", "moe_shared"):
        assert name in profiling.SCOPES
    assert profiling.scope_of(
        "jit(train_step)/accumulate/layers/attn/kda/kda_scan/dot_general"
    )["scope"] == "accumulate/layers/attn/kda/kda_scan"


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


def test_a_cell_of_another_family_imports_nothing_this_pr_added():
    """Start-up: the GPT-2 family's build loads neither the new model,
    nor the rule, nor the new reference."""
    code = (
        "import sys, json\n"
        "from benchmark import cell\n"
        "from benchmark.families import gpt\n"
        "gpt.build(cell.load_cell('gpt2-124m.steady')['config'])\n"
        "bad = [m for m in sys.modules if 'kimi' in m or m.endswith('ops.kda')]\n"
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
    path = os.path.join(cell_files.HERE, "reference", "kimi_linear.py")
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
        "__future__", "jax", "jax.numpy", "benchmark.reference",
        "benchmark.reference.llama", "benchmark.families.kimi_linear",
    }
    code = "\n".join(
        line for line in source.split('"""', 2)[2].splitlines()
        if not line.strip().startswith("#")
    )
    for banned in ("dlrover_tpu", "custom_vjp", "pallas", "checkpoint",
                   "cumsum", "sort", "gmm"):
        assert banned not in code, banned
    assert 'default_matmul_precision("highest")' in code
    doc = ast.get_docstring(tree)
    for said in ("S' = diag(exp(g_t)) S_{t-1}", "sigmoid(x W_r)",
                 "mla_use_nope", "routed_scaling_factor", "Departures"):
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
    assert config["reduced"] == _config()["reduced"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL not in e2e["save_stall_ms"]["workloads"]
    # The grouped products' time, and no share of a roofline for them:
    # their rows are the step's held pairs, which no count from shapes
    # knows (3 to 7,897 a layer by the seed), and a share at the mean
    # load would pass 100% in a step with fewer.
    assert not [n for n in cell_files.load_cell(CELL)["workload"]["per_layer"]
                if "gmm_roofline" in n]


@pytest.mark.parametrize("name", METRICS)
def test_manifest_lists_the_cell_in_its_metrics(manifest, name):
    """A member of each list, wherever a later cell stands, and read
    as the cell's own copy of the metric was before PR 63 folded it."""
    spec = membership.assert_cell_reports(manifest, CELL, name)
    assert spec["moves"] == (
        "setup_s" if name.endswith(".setup") else "tokens_per_s")
    membership.assert_reads_as_its_copy_did(spec)


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
    ("two\nlines", False), ("a\ttab", False),
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
    for key in ("micro_batch_per_chip", "traffic"):
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


def test_no_control_breaks_the_router_and_the_cell_says_so(manifest):
    """``correct`` cannot see the router at this share (both router
    controls read under the tolerance on the chip: PERF.md section 6,
    PR 53): none ships, and the cell's ``why`` says what its check
    leaves to the CPU tests."""
    assert controls.NAMES == ("no_carry", "no_delta", "no_shared", "rope_on_mla")
    for name in ("softmax_router", "bias_in_weight"):
        with pytest.raises(ValueError, match="no control"):
            controls.broken(name, lambda *a: None)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert "correct cannot see the router" in cell["why"]
    assert "no kernel yet" in cell["why"]
    assert "router" in _config()["assumed"]["initializer_range_why"]


def test_every_control_has_its_cell_and_nothing_else_is_there():
    names = {f"{CONFIG}.{name}.json" for name in controls.NAMES}
    assert set(os.listdir(os.path.join(CONTROLS, "configs"))) == names
    assert set(os.listdir(os.path.join(CONTROLS, "workloads"))) == names
    assert os.listdir(os.path.join(CONTROLS, "traffic")) == ["steady.json"]


@pytest.fixture(scope="module")
def toy():
    config = _config("toy-kimi", TOY)
    # Weights large enough that every path weighs in the loss, and two
    # chunks of the rule, so that a state crosses a boundary.
    config["assumed"] = dict(config["assumed"], initializer_range=0.1,
                             router_bias_std=1.0, sequence_length=128)
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
    # check reads at the published widths is in PERF.md, PR 53).
    assert abs(float(loss) - honest) > 1e-5 * honest
    # One path is broken, not the model: the loss stays near.
    assert abs(float(loss) - honest) < 0.2 * honest
    assert all(
        bool(np.all(np.isfinite(np.asarray(g, np.float32))))
        for g in jax.tree.leaves(grads)
    )
    # The program is whole again once the broken loss is traced.
    again = family.build(config)["loss"]
    assert float(jax.jit(again)(params, *batch)) == honest


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        controls.broken("no_such_path", lambda *a: None)
