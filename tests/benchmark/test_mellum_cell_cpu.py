"""Family ``mellum`` on the CPU: the toy cell rehearsed end to end, the
yardstick's counts for the published configuration by hand, the new
reader and kernel counts, the configuration against the catalog's row,
the controls, and the form of what PR 57 added to the manifest (the
rules a driver holds it to before any chip)."""

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
from benchmark.controls import mellum as controls
from benchmark.families import mellum as family
from benchmark.kernel_work import (
    flash_bwd, flash_fwd, moe_gmm, moe_tgmm,
)
from benchmark.readers import model_flops
from tests.benchmark import membership

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONTROLS = os.path.join(cell_files.HERE, "controls", "mellum_cells")
CONFIG = "mellum2-12b-a2.5b"
CELL = "mellum2-12b-a2.5b.steady"
METRICS = (
    "mfu.train", "attn_window_ms_per_step.train",
    "attn_full_ms_per_step.train", "flash_fwd_roofline.train",
    "flash_bwd_roofline.train", "moe_gmm_roofline.train",
    "attn_ms_per_step.train", "embed_ms_per_step.train",
    "head_ms_per_step.train", "mlp_ms_per_step.train",
    "moe_combine_ms_per_step.train",
    "moe_experts_ms_per_step.train", "moe_gmm_ms_per_step.train",
    "moe_route_ms_per_step.train", "moe_routed_ms_per_step.train",
    "optimizer_ms_per_step.train", "step_hbm_gb.train",
    "step_programs.train", "unscoped_ms_per_step.train",
    "moe_tgmm_roofline.train", "moe_tgmm_ms_per_step.train",
    "flash_bwd_ms_per_step.train", "pallas_ms_per_step.train",
    "data_wait_ms.train", "dispatch_ms.train",
)
# The start-up stages of the trainer's process, which move ``setup_s``.
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
WORDS = ("deployment", "reduced_from", "source")
V5E = "TPU v5 lite"


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
         "--workload", "toy-mellum.steady", "--seed", "3000000019",
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


def test_toy_mellum_cell_rehearsal_prints_a_correct_line():
    line = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p90"}


def test_traced_rehearsal_reports_no_device_metric():
    """One step program for two scanned periods; the readers of a
    device plane or a peak find none off the chip, return nothing and
    do not raise."""
    line = _rehearse(1)
    assert line["metrics"]["step_programs.train"]["value"] == 1
    assert not (set(METRICS) - OFF_CHIP) & set(line["metrics"])
    assert OFF_CHIP | set(SETUP_METRICS) <= set(line["metrics"])
    for stage in STAGES:
        assert line["metrics"][f"{stage}.setup"]["value"] >= 0


# -- the published configuration and its counts, by hand ------------------


def test_published_widths_and_the_cut():
    config = _config()
    for key, value in {
        "hidden_size": 2304, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 7168,
        "moe_intermediate_size": 896, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "sliding_window": 1024,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "attention_bias": False, "max_position_embeddings": 131072,
        "num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576,
    }.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["reduced_from"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304,
    }
    # The two published lists stand whole; the layers held are their
    # first ``num_hidden_layers`` entries, one whole period.
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 28
    assert family.layer_kinds(config) == ["sliding_attention"] * 3 + [
        "full_attention"
    ]
    for said in ("pipeline", "4 chips", "expert parallelism 4",
                 "595,153,152 parameters", "8.33 GB", "9.52 GB"):
        assert said in config["deployment"], said
    assumed = config["assumed"]
    assert assumed["sequence_length"] == 8192
    assert assumed["router_num_experts"] == 64 and assumed["first_expert"] == 0
    assert assumed["qk_norm"] == "none" and assumed["mtp"] == "left out"
    assert assumed["router_aux_loss_coef"] == 0.001
    for key in ("sequence_length", "router_num_experts", "first_expert",
                "qk_norm", "router_aux_loss_coef", "mtp", "initializer_range",
                "init_jitter", "remat"):
        assert assumed[key + "_why"], key
    # The floors of a model_config cut: a whole period and four layers,
    # 8 routed experts, an eighth of the vocabulary.
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert 8 * config["vocab_size"] >= 98304
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
            if r["name"] == "Mellum2-12B-A2.5B-Instruct"
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
    e = 2304
    attention = 2 * e * 4096 + 2 * e * 512
    assert family.attention_matmul_params(_config()) == attention == 21_233_664
    # The router's 64 outputs and two experts a token (8 x 16 / 64).
    experts = e * 64 + 2 * 3 * e * 896
    assert family.expert_matmul_params(_config()) == experts == 12_533_760
    assert shape["layer_matmul_params"] == attention + experts
    assert (shape["layers"], shape["sliding_layers"], shape["full_layers"]) == (
        4, 3, 1,
    )
    assert (shape["sliding_window"], shape["full_window"]) == (1024, None)
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) == (32, 4, 128)
    assert (shape["vocab_rows"], shape["seq_len"], shape["window"]) == (
        24576, 8192, None,
    )
    assert (shape["experts_held"], shape["router_experts"]) == (16, 64)
    assert (shape["experts_per_token"], shape["expert_width"]) == (8, 896)
    built = family.build(_config())
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 595_153_152
    assert f"{count * 14 / 1e9:.2f} {count * 16 / 1e9:.2f}" == "8.33 9.52"
    cfg = built["cfg"]
    assert cfg.remat == "full" and cfg.held == 16 and cfg.periods == 1
    assert cfg.rope_full.rope_type == "yarn" and cfg.rope_full.factor == 16
    assert cfg.rope_sliding.rope_type == "default"
    assert built["seq_len"] == 8192 and built["vocab"] == 24576


def test_required_operations_by_hand():
    """1.49 GFLOP a token, 1.22e13 a step: 6 x the matrix parameters a
    token passes, and each layer's causal products over its own kind's
    mean keys (960.06 under the window, 4,096.5 without)."""
    shape = family.shape(_config())
    assert flops.mean_keys(8192, 1024) == 960.0625
    assert flops.mean_keys(8192, None) == 4096.5
    matrices = 4 * (21_233_664 + 12_533_760) + 24576 * 2304
    attention = 12 * 32 * 128 * (3 * 960.0625 + 4096.5)
    want = 6 * matrices + attention
    assert family.flops_per_token(shape) == pytest.approx(want)
    assert flops.train_flops_per_token(_config()) == family.flops_per_token(shape)
    assert f"{want / 1e9:.3g}" == "1.49"
    assert f"{want * 8192:.3g}" == "1.22e+13"
    # The head is 30% of the matrix parameters a token passes here.
    assert round(100 * 24576 * 2304 / matrices) == 30


def test_pattern_work_is_the_mean_over_the_period_s_calls():
    """``trace_events`` multiplies one call's work by the calls it
    finds: three sliding and one full a period, so the mean over the
    four times four calls is the period's sum, and since both kinds
    are compute-bound the mean's least time is the mean of theirs."""
    shape = family.shape(_config())
    chip = peaks.chip_peaks(V5E)
    for kernel in (flash_fwd, flash_bwd):
        kinds = kernel.by_kind(shape, 1)
        assert [n for n, _ in kinds] == [3, 1]
        sliding = kernel.one_call(dict(shape, window=1024), 1)
        full = kernel.one_call(dict(shape, window=None), 1)
        assert [w for _, w in kinds] == [sliding, full]
        mean = kernel.work(shape, 1)
        for key in ("flops", "bytes"):
            assert 4 * mean[key] == pytest.approx(3 * sliding[key] + full[key])
        least = [flops.roofline_seconds(w, chip) for w in (sliding, full)]
        assert [r["bound"] for r in least] == ["compute", "compute"]
        of_mean = flops.roofline_seconds(mean, chip)
        assert of_mean["bound"] == "compute"
        assert 4 * of_mean["seconds"] == pytest.approx(
            3 * least[0]["seconds"] + least[1]["seconds"]
        )
    fwd = flash_fwd.work(shape, 1)
    assert fwd["flops"] == 4.0 * 32 * 128 * 8192 * (3 * 960.0625 + 4096.5) / 4
    assert flash_bwd.work(shape, 1)["flops"] == 2 * fwd["flops"]
    # With one kind of layer, or no pattern in the shape, the count is
    # one call's.
    one_kind = dict(shape, sliding_layers=0, full_layers=4)
    assert flash_fwd.work(one_kind, 2) == flash_fwd.one_call(shape, 2)
    plain = {k: v for k, v in shape.items() if not k.startswith(("sliding", "full"))}
    assert flash_fwd.work(plain, 2) == flash_fwd.one_call(shape, 2)


def test_held_products_count_the_held_pairs_and_not_the_buffer():
    shape = family.shape(_config())
    held = moe_gmm.work(shape, 1)
    rows = 8192 * 8 * 16 / 64
    assert rows == 16384
    assert held["flops"] == 2.0 * rows * 2304 * 896
    assert held["bytes"] == 2.0 * (rows * 2304 + rows * 896 + 16 * 2304 * 896)
    # A quarter of the whole layer's pairs, which is what the buffer
    # holds: a product that walks all of it reads a quarter at most.
    uncut = {k: v for k, v in shape.items()
             if k not in ("experts_held", "router_experts")}
    whole = moe_gmm.work(dict(uncut, experts=64), 1)
    assert 4 * held["flops"] == whole["flops"]
    # The weight-gradient product is the same count the other way round.
    assert moe_tgmm.work(shape, 1) == held


def test_no_count_is_over_its_kernel_s_peak():
    """Each kernel's least time at the chip's peaks, against the least
    time of the operations alone: a share cannot pass 100% unless a
    count exceeds what the kernel must do."""
    config = _config()
    chip = peaks.chip_peaks(V5E)
    for kernel in ("flash_fwd", "flash_bwd", "moe_gmm", "moe_tgmm"):
        work = flops.kernel_work(kernel, config, 1)
        least = flops.roofline_seconds(work, chip)["seconds"]
        assert least == pytest.approx(
            max(work["flops"] / chip["bf16_flops_per_s"],
                work["bytes"] / chip["hbm_bytes_per_s"])
        )
    # The whole step's required operations take 62 ms at the peak.
    step = flops.train_flops_per_token(config) * 8192
    assert round(1e3 * step / chip["bf16_flops_per_s"]) == 62


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
    """The two kinds' scopes stand beneath ``attn``: ``attn`` still
    reads the whole, the nested reading each kind."""
    from benchmark.readers import scope_time

    reduced = {"steps": 2, "device_ops": [], "ops": {
        "fusion.1": {"seconds": 0.006}, "fusion.2": {"seconds": 0.020},
        "fusion.3": {"seconds": 0.002}, "moe_gmm.1": {"seconds": 0.008},
    }}
    description = {
        "fusion.1": {"scope": "accumulate/layers/attn/attn_window", "pass": "fwd"},
        "fusion.2": {"scope": "accumulate/layers/attn/attn_full", "pass": "bwd"},
        "fusion.3": {"scope": "accumulate/layers/attn", "pass": "fwd"},
        "moe_gmm.1": {"scope": "accumulate/layers/mlp/moe_routed/moe_experts",
                      "pass": "fwd"},
    }
    monkeypatch.setattr(scope_time, "describe", lambda: description)
    ctx = {"trace": reduced}
    assert scope_time.read(ctx, scope="attn") == pytest.approx(14.0)
    assert scope_time.read(ctx, scope="attn_window", nested=True) == pytest.approx(3.0)
    assert scope_time.read(ctx, scope="attn_full", nested=True) == pytest.approx(10.0)
    assert scope_time.read(ctx, scope="moe_routed", nested=True) == pytest.approx(4.0)
    assert scope_time.read({"trace": {}}, scope="attn_full", nested=True) is None


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
    code = (
        "import sys, json\n"
        "from benchmark import cell\n"
        "from benchmark.families import gpt\n"
        "gpt.build(cell.load_cell('gpt2-124m.steady')['config'])\n"
        "bad = [m for m in sys.modules if 'mellum' in m]\n"
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
    """Nothing from the program, no custom rule, no kernel, no sort;
    float32 at "highest"; the equations and the departures stated."""
    path = os.path.join(cell_files.HERE, "reference", "mellum.py")
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
        "benchmark.reference.llama", "benchmark.families.mellum",
    }
    code = "\n".join(
        line for line in source.split('"""', 2)[2].splitlines()
        if not line.strip().startswith("#")
    )
    for banned in ("dlrover_tpu", "custom_vjp", "pallas", "checkpoint",
                   "sort", "gmm", "rope_table", "apply_rope"):
        assert banned not in code, banned
    assert 'default_matmul_precision("highest")' in code
    doc = ast.get_docstring(tree)
    for said in ("i - j < sliding_window", "attention_factor", "ramp_i",
                 "norm_topk_prob", "h // (H / G)", "Departures"):
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


@pytest.mark.parametrize("name", METRICS + SETUP_METRICS)
def test_manifest_lists_the_cell_in_its_metrics(manifest, name):
    """A member of each list, wherever a later cell stands, and read
    as the cell's own copy of the metric was before PR 63 folded it."""
    spec = membership.assert_cell_reports(manifest, CELL, name)
    assert spec["moves"] == (
        "setup_s" if name in SETUP_METRICS else "tokens_per_s")
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
        if m["name"] in METRICS + SETUP_METRICS:
            assert _printable_line(m["layer"]) and "\t" not in m["layer"]


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


def test_every_control_has_its_cell_and_nothing_else_is_there():
    names = {f"{CONFIG}.{name}.json" for name in controls.NAMES}
    assert set(os.listdir(os.path.join(CONTROLS, "configs"))) == names
    assert set(os.listdir(os.path.join(CONTROLS, "workloads"))) == names
    assert os.listdir(os.path.join(CONTROLS, "traffic")) == ["steady.json"]


@pytest.fixture(scope="module")
def toy():
    config = _config("toy-mellum", TOY)
    # Weights large enough that every path weighs in the loss.
    config["assumed"] = dict(config["assumed"], initializer_range=0.1)
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
    # check reads at the published widths is in PERF.md, PR 57).
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
