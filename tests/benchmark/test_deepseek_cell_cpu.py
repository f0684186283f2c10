"""Family ``deepseek_v2`` on the CPU: the toy cell rehearsed end to
end, the yardstick's counts for the published configuration by hand,
the new reader and the kernel counts it shares, the configuration
against the catalog's row, the controls, and the form of what PR 59
added to the manifest (the rules a driver holds it to before any
chip)."""

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
from benchmark.controls import deepseek_v2 as controls
from benchmark.families import deepseek_v2 as family
from benchmark.kernel_work import flash_bwd, flash_fwd, moe_gmm, moe_tgmm
from benchmark.readers import model_flops
from tests.benchmark import membership

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CONTROLS = os.path.join(cell_files.HERE, "controls", "deepseek_v2_cells")
CONFIG = "deepseek-v2-lite"
CELL = "deepseek-v2-lite.steady"
# The generic step metrics the cell joins by naming them (its own
# copies of them until PR 63), the last four left out for want of room
# until then.
GENERIC = (
    "attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
    "optimizer_ms_per_step", "unscoped_ms_per_step", "moe_route_ms_per_step",
    "moe_experts_ms_per_step", "moe_combine_ms_per_step",
    "moe_routed_ms_per_step", "moe_gmm_ms_per_step", "step_hbm_gb",
    "step_programs", "data_wait_ms", "dispatch_ms", "moe_gmm_roofline",
    "moe_tgmm_roofline", "flash_fwd_roofline", "flash_bwd_roofline",
    "embed_ms_per_step", "moe_tgmm_ms_per_step", "flash_bwd_ms_per_step",
    "pallas_ms_per_step",
)
METRICS = ("mfu.train", "mla_rope_ms_per_step.train") + tuple(
    f"{name}.train" for name in GENERIC
)
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


@pytest.fixture(scope="module")
def rehearsal():
    """The toy cell through ``run.py`` once, traced: the line's
    ``detail.window`` holds what an untraced line's metrics are made
    of."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cell_files.HERE, "run.py"),
         "--workload", "toy-deepseek.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=260,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_toy_deepseek_cell_rehearsal_prints_a_correct_line(rehearsal):
    line = rehearsal
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["detail"]["reference"]["rms_rel"] < 3e-4
    window = line["detail"]["window"]
    assert window["tokens_per_s"] > 0 and window["step_ms_p90"] > 0


def test_traced_rehearsal_reports_no_device_metric(rehearsal):
    """One step program for three layers in line; the readers of a
    device plane or a peak find none off the chip, return nothing and
    do not raise."""
    metrics = rehearsal["metrics"]
    assert metrics["step_programs.train"]["value"] == 1
    assert not (set(METRICS) - OFF_CHIP) & set(metrics)
    assert OFF_CHIP | set(SETUP_METRICS) <= set(metrics)
    for stage in STAGES:
        assert metrics[f"{stage}.setup"]["value"] >= 0


# -- the published configuration and its counts, by hand ------------------


def test_published_widths_and_the_cut():
    config = _config()
    for key, value in {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 10944, "moe_intermediate_size": 1408,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "norm_topk_prob": False, "routed_scaling_factor": 1,
        "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
        "topk_group": 1, "seq_aux": True, "rope_theta": 10000,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "attention_bias": False, "max_position_embeddings": 163840,
        "num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 12800,
    }.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn",
    }
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    assert config["reduced_from"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 102400,
    }
    assert family.layer_kinds(config) == ["dense"] + ["moe"] * 5
    for said in ("pipeline", "8 chips", "expert parallelism 8",
                 "635,466,752 parameters", "8.90 GB", "10.17 GB",
                 "768 rows", "8.9%"):
        assert said in config["deployment"], said
    assumed = config["assumed"]
    assert assumed["sequence_length"] == 8192
    assert assumed["router_num_experts"] == 64 and assumed["first_expert"] == 0
    assert assumed["aux_loss_alpha"] == 0.001 and assumed["remat"] == "full"
    for key in ("sequence_length", "router_num_experts", "first_expert",
                "aux_loss_alpha", "initializer_range", "init_jitter", "remat"):
        assert assumed[key + "_why"], key
    # The floors of a model_config cut: the leading dense layer and
    # four layers behind it at least, 8 routed experts, an eighth of
    # the vocabulary.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert 8 * config["vocab_size"] >= 102400
    cell = cell_files.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["workload"]["micro_batch_per_chip"] == 1
    assert cell["workload"]["steps_per_sample"] == 1
    assert cell["workload"]["traffic"] == "steady"
    # The accepted traffic file, not a copy with other numbers.
    assert cell["traffic"] == cell_files.load_cell("mistral-7b.steady")["traffic"]


def test_configuration_is_the_catalogs_row_but_for_the_cut():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2-Lite"
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
    e = 2048
    mixer = e * 16 * 192 + e * 576 + 512 * 16 * 256 + 16 * 128 * e
    assert family.mla_matmul_params(config) == mixer == 13_762_560
    # The router's 64 outputs, two shared experts and three quarters
    # of a routed one a token (6 x 8 / 64).
    experts = e * 64 + (2 + 0.75) * 3 * e * 1408
    assert family.expert_matmul_params(config) == experts == 23_920_640
    dense = 3 * e * 10944
    assert shape["dense_matmul_params"] == dense == 67_239_936
    assert shape["layers"] * shape["layer_matmul_params"] == pytest.approx(
        6 * mixer + dense + 5 * experts
    )
    assert (shape["layers"], shape["mla_layers"]) == (6, 6)
    assert (shape["dense_layers"], shape["moe_layers"]) == (1, 5)
    assert shape["layer_kinds"] == ["mla+dense"] + ["mla+moe"] * 5
    assert (shape["heads"], shape["kv_heads"]) == (16, 16)
    assert (shape["head_dim"], shape["v_head_dim"]) == (192, 128)
    assert (shape["vocab_rows"], shape["seq_len"], shape["window"]) == (
        12800, 8192, None,
    )
    assert (shape["embd"], shape["expert_width"]) == (2048, 1408)
    assert (shape["experts_held"], shape["router_experts"]) == (8, 64)
    assert shape["experts_per_token"] == 6 and shape["mla_rope_dim"] == 64
    built = family.build(config)
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 635_466_752
    assert f"{count * 14 / 1e9:.2f} {count * 16 / 1e9:.2f}" == "8.90 10.17"
    cfg = built["cfg"]
    assert cfg.remat == "full" and cfg.held == 8 and cfg.n_layer == 6
    assert cfg.first_dense == 1 and cfg.shared_hidden == 2816
    assert (cfg.rope_factor, cfg.rope_original) == (40.0, 4096)
    assert (cfg.mscale, cfg.mscale_all_dim) == (0.707, 0.707)
    assert not cfg.renorm_top_k and cfg.routed_scale == 1.0
    assert built["seq_len"] == 8192 and built["vocab"] == 12800
    for key, value in (("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
                       ("topk_method", "group_limited_greedy"),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="family deepseek_v2"):
            family.build(dict(config, **{key: value}))


def test_required_operations_by_hand():
    """2.53 GFLOP a token, 2.07e13 a step: 6 x the 295.6M matrix
    parameters a token passes, and the six layers' causal products at
    192 and 128 over 4,096.5 keys."""
    shape = family.shape(_config())
    assert flops.mean_keys(8192, None) == 4096.5
    matrices = 6 * 13_762_560 + 67_239_936 + 5 * 23_920_640 + 12800 * 2048
    assert matrices == 295_632_896
    attention = 6 * 6 * 16 * (192 + 128) * 4096.5
    want = 6 * matrices + attention
    assert family.flops_per_token(shape) == pytest.approx(want)
    assert flops.train_flops_per_token(_config()) == family.flops_per_token(shape)
    assert f"{want / 1e9:.3g}" == "2.53"
    assert f"{want * 8192:.3g}" == "2.07e+13"
    # The head is 8.9% of the matrix parameters a token passes here.
    assert round(1000 * 12800 * 2048 / matrices) == 89
    # The six flash calls' forward operations are a tenth of the step.
    fwd = flash_fwd.work(shape, 1)["flops"]
    assert fwd == 2.0 * 16 * 320 * 8192 * 4096.5
    assert 18 * fwd == pytest.approx(attention * 8192)


def test_the_kernel_counts_this_cell_reads():
    shape = family.shape(_config())
    # Queries and keys of 192 columns, values of 128.
    fwd = flash_fwd.work(shape, 1)
    assert fwd["bytes"] == 2.0 * 8192 * 16 * 320 * 2 + 16 * 8192 * 4.0
    assert flash_bwd.work(shape, 1)["flops"] == 2 * fwd["flops"]
    one_size = flash_fwd.work(dict(shape, head_dim=160, v_head_dim=160), 1)
    assert fwd["flops"] == one_size["flops"]
    # The held pairs at even load, 6,144 a product, not the buffer's
    # 16,384 rows: the share these read against is three eighths at most.
    held = moe_gmm.work(shape, 1)
    rows = 8192 * 6 * 8 / 64
    assert rows == 6144
    assert held["flops"] == 2.0 * rows * 2048 * 1408
    assert held["bytes"] == 2.0 * (rows * 2048 + rows * 1408 + 8 * 2048 * 1408)
    uncut = {k: v for k, v in shape.items()
             if k not in ("experts_held", "router_experts")}
    whole = moe_gmm.work(dict(uncut, experts=64), 1)
    assert 8 * held["flops"] == whole["flops"]
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
    # The whole step's required operations take 105 ms at the peak.
    step = flops.train_flops_per_token(config) * 8192
    assert round(1e3 * step / chip["bf16_flops_per_s"]) == 105


def test_the_whole_step_s_share_reads_the_rate_and_nothing_without_one():
    cell = cell_files.load_cell(CELL)
    ctx = {
        "cell": cell, "window": {"tokens_per_s": 28000.0},
        "device": {"count": 1}, "peaks": {"bf16_flops_per_s": 197e12},
    }
    shape = family.shape(_config())
    want = 100 * family.flops_per_token(shape) * 28000.0 / 197e12
    assert model_flops.read(ctx) == pytest.approx(want, rel=1e-9)
    assert 0 < model_flops.read(ctx) < 100
    assert model_flops.read(dict(ctx, peaks=None)) is None
    assert model_flops.read(dict(ctx, window={})) is None


def test_scope_readers_on_a_hand_made_table(monkeypatch):
    """``mla_rope`` stands beneath ``attn`` > ``mla``: ``attn`` still
    reads the whole, the nested reading the rotation."""
    from benchmark.readers import scope_time

    reduced = {"steps": 2, "device_ops": [], "ops": {
        "fusion.1": {"seconds": 0.006}, "fusion.2": {"seconds": 0.020},
        "fusion.3": {"seconds": 0.002}, "moe_gmm.1": {"seconds": 0.008},
    }}
    description = {
        "fusion.1": {"scope": "accumulate/layers/attn/mla/mla_rope", "pass": "fwd"},
        "fusion.2": {"scope": "accumulate/layers/attn/mla", "pass": "bwd"},
        "fusion.3": {"scope": "accumulate/layers/attn", "pass": "fwd"},
        "moe_gmm.1": {"scope": "accumulate/layers/mlp/moe_routed/moe_experts",
                      "pass": "fwd"},
    }
    monkeypatch.setattr(scope_time, "describe", lambda: description)
    ctx = {"trace": reduced}
    assert scope_time.read(ctx, scope="attn") == pytest.approx(14.0)
    assert scope_time.read(ctx, scope="mla_rope", nested=True) == pytest.approx(3.0)
    assert scope_time.read(ctx, scope="mla", nested=True) == pytest.approx(13.0)
    assert scope_time.read(ctx, scope="moe_routed", nested=True) == pytest.approx(4.0)
    assert scope_time.read({"trace": {}}, scope="mla_rope", nested=True) is None


def test_shape_stays_off_jax_and_a_cell_of_another_family_off_this_one():
    code = (
        "import sys, json\n"
        "from benchmark import flops\n"
        "from benchmark import cell\n"
        f"c = cell.load_cell({CELL!r})\n"
        "flops.shape_of(c['config'])\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('dlrover_tpu')]\n"
        "from benchmark.families import gpt\n"
        "gpt.build(cell.load_cell('gpt2-124m.steady')['config'])\n"
        "bad += [m for m in sys.modules if m.endswith('models.deepseek_v2')"
        " or m.endswith('reference.deepseek_v2')]\n"
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
    float32 at "highest"; its own frequencies; the equations and the
    departures stated."""
    path = os.path.join(cell_files.HERE, "reference", "deepseek_v2.py")
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
        "benchmark.reference.llama", "benchmark.families.deepseek_v2",
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
    for said in ("(2i, 2i + 1)", "mscale_all_dim", "ramp_i", "norm_topk_prob",
                 "k_r is not normed", "f_be", "Departures"):
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
    assert len(controls.NAMES) >= 2 and "rope_off" in controls.NAMES
    assert set(os.listdir(os.path.join(CONTROLS, "configs"))) == names
    assert set(os.listdir(os.path.join(CONTROLS, "workloads"))) == names
    assert os.listdir(os.path.join(CONTROLS, "traffic")) == ["steady.json"]
    # Each shipped control's count of twelve is written down.
    doc = controls.__doc__
    for name in controls.NAMES:
        assert f"``{name}``" in doc, name
    assert "of twelve" in doc


@pytest.fixture(scope="module")
def toy():
    config = _config("toy-deepseek", TOY)
    # Weights large enough that every path weighs in the loss.
    config["assumed"] = dict(config["assumed"], initializer_range=0.1)
    honest = family.build(config)
    params = jax.jit(honest["init"])(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, honest["seq_len"] + 1), 0, honest["vocab"]
    )
    batch = tok[:, :-1], tok[:, 1:]
    return (
        config, params, batch, float(jax.jit(honest["loss"])(params, *batch)),
        float(honest["reference_loss"](params, *batch)),
    )


@pytest.mark.parametrize("name", controls.NAMES)
def test_a_control_breaks_the_loss_and_is_refused(toy, name):
    """Each shipped control is refused at the toy's size: against the
    program's own reference its loss is off by more than the
    tolerance, where the honest program is within it."""
    config, params, batch, honest, want = toy
    assert honest == pytest.approx(want, rel=3e-4)
    broken = family.build(dict(config, control=name))["loss"]
    loss = float(jax.jit(broken)(params, *batch))
    assert np.isfinite(loss) and abs(loss - want) > 3e-4 * want
    # One path is broken, not the model: the loss stays near.
    assert abs(loss - honest) < 0.2 * honest
    # The program is whole again once the broken loss is traced.
    again = family.build(config)["loss"]
    assert float(jax.jit(again)(params, *batch)) == honest


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        controls.broken("no_such_path", lambda *a: None)
