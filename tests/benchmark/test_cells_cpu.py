"""Every cell rehearsed end to end on the CPU, at a two-layer toy
width (``benchmark/testdata/cells``, never in BENCHMARK.json): the
steady cells in a process each, the four-chip one on four virtual
devices, the resume one through a real SIGKILL of a real trainer under
``elastic_run --standalone``. Also: the plain references against the
program's models, the manifest against the contract's characters and
against the benchmark's own files.

Each rehearsal is a child with its own time limit; the resume one makes
its own work directory, job name and socket directory (see
``kinds/save_kill_resume.py``), so six workers can run it side by side.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.reference import gpt as gpt_reference
from benchmark.reference import llama as llama_reference

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(workload, devices, trace=0, seconds=2, allow_cpu=True, timeout=240):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, os.path.join(cell_files.HERE, "run.py"),
           "--workload", workload, "--seed", "3000000019",
           "--seconds", str(seconds), "--trace", str(trace),
           "--cells-root", TOY, "--deadline-s", str(timeout - 20)]
    if allow_cpu:
        cmd.append("--allow-cpu")
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["why_incorrect"]
    # A rehearsal names the CPU: never mistaken for a device number.
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for name, m in line["metrics"].items():
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert isinstance(m["value"], (int, float))
    return line


@pytest.mark.parametrize("workload,devices,metrics", [
    ("toy-gpt.steady", 1, {"setup_s", "tokens_per_s", "step_ms_p90"}),
    ("toy-mistral.steady", 1, {"setup_s", "tokens_per_s", "step_ms_p90"}),
    ("toy-mistral.fsdp4", 4, {"setup_s", "tokens_per_s"}),
])
def test_steady_cell_rehearsal(workload, devices, metrics):
    line = _last_line(_run(workload, devices))
    assert set(line["metrics"]) == metrics
    assert line["device"]["count"] == devices
    ref = line["detail"]["reference"]
    assert len(ref["system_loss"]) == len(ref["reference_loss"]) >= 1
    assert ref["mean_rel"] <= ref["rms_rel"] < 3e-4


def test_traced_rehearsal_reports_layer_metrics():
    line = _last_line(_run("toy-gpt.steady", 1, trace=1))
    assert {"data_wait_ms.train", "dispatch_ms.train",
            "step_programs.train", "step_hbm_gb.train"} <= set(line["metrics"])
    assert line["metrics"]["step_programs.train"]["value"] == 1
    # No device plane in a CPU trace: no device metric, no busy_s. The
    # line says which of the cell's metrics found nothing to read.
    assert "mfu.train" not in line["metrics"]
    assert "busy_s" not in line["device"]
    nothing = line["notes"]["read_nothing"]
    assert {"mfu.train", "attn_ms_per_step.train"} <= set(nothing)
    assert not set(nothing) & set(line["metrics"])


# -- which cells report a metric is said by the cells --------------------


def _names(workload):
    cell = cell_files.load_cell(workload, TOY)
    return {s["name"] for s in cell_files.cell_metric_specs(cell)}


def test_a_cell_reports_the_restricted_metrics_it_names_and_no_other():
    specs = cell_files.layer_metric_specs()
    restricted = {s["name"] for s in specs if s.get("restricted")}
    for_all = {s["name"] for s in specs} - restricted
    assert {"boot_s.setup", "first_step_s.setup"} <= for_all
    assert {"attn_ms_per_step.train", "mfu.train"} <= restricted
    assert not any("workloads" in s for s in specs)
    # toy-gpt.steady names some; toy-gpt.bare is the same cell and
    # names none; each reports what every cell reports. Held to the
    # cell's own list and never to the directory: the next PR's metric
    # is a file more there, and no cell here names it.
    named = set(cell_files.load_cell("toy-gpt.steady", TOY)["workload"]["per_layer"])
    assert {"attn_ms_per_step.train", "mfu.train"} <= named <= restricted
    assert _names("toy-gpt.steady") == for_all | named
    assert _names("toy-gpt.bare") == for_all


def _stand_ins():
    """(toy cell, its workload file) for every toy cell that stands for
    a cell under ``workloads/``."""
    d = os.path.join(TOY, "workloads")
    out = []
    for fname in sorted(os.listdir(d)):
        with open(os.path.join(d, fname)) as f:
            workload = json.load(f)
        if "stands_for" in workload:
            out.append(pytest.param(workload, id=fname[: -len(".json")]))
    return out


@pytest.mark.parametrize("workload", _stand_ins())
def test_a_toy_cell_rehearses_the_line_of_the_cell_it_stands_for(workload):
    """``stands_for`` in a toy workload file is the cell under
    ``workloads/`` whose traced line it rehearses off the chip: the
    same names on the same kind of traffic, so that each reader runs
    on the CPU where it will run on the chip. A new cell brings its
    toy cell with it, a file each."""
    real = cell_files.load_cell(workload["stands_for"])
    assert workload["per_layer"] == real["workload"].get("per_layer", [])
    assert workload["chips"] == real["chips"]
    with open(os.path.join(TOY, "traffic", workload["traffic"] + ".json")) as f:
        assert json.load(f)["kind"] == real["traffic"]["kind"]


def test_a_traced_rehearsal_leaves_out_what_its_cell_does_not_name():
    line = _last_line(_run("toy-gpt.bare", 1, trace=1))
    assert "boot_s.setup" in line["metrics"]
    for name in ("data_wait_ms.train", "step_programs.train"):
        # read off the chip too, where the cell names them (above)
        assert name not in line["metrics"]
        assert name not in line["notes"]["read_nothing"]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cell_that_names_a_metric_no_file_has_is_refused(trace):
    with pytest.raises(cell_files.CellError, match="no_such_metric.train"):
        cell_files.load_cell("toy-gpt.typo", TOY)
    proc = _run("toy-gpt.typo", 1, trace=trace, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no_such_metric.train" in proc.stderr


def test_resume_cell_rehearsal_kills_a_real_trainer():
    line = _last_line(_run("toy-gpt.resume", 1, seconds=3, timeout=280))
    assert set(line["metrics"]) == {"setup_s", "save_stall_ms"}
    assert line["detail"]["window"]["tokens_per_s"] > 0
    assert line["detail"]["resume_s"] > 0
    d = line["detail"]
    assert d["restored_step"] > 0
    assert d["restored_step"] <= d["killed_after_step"]
    assert d["marks"]["kill"] < d["marks"]["proc_start"] < d["marks"]["built"]
    assert d["marks"]["built"] <= d["marks"]["restore_done"]
    assert d["resume_cache"][1] == 0  # nothing compiled again
    # The kill is counted from a save to memory behind which every save
    # to disk stood committed, and the restore is no older than those.
    by_step = {s["save_step"]: s for s in d["saves"]}
    anchor = by_step[d["kill_counted_from_save"]]
    assert anchor["save_ok"] and not anchor["to_disk"]
    assert d["killed_after_step"] >= d["kill_counted_from_save"] + 3
    assert d["restored_step"] >= d["committed_on_disk_at_kill"] > 0
    assert d["saves_dropped"] == sum(not s["save_ok"] for s in d["saves"])
    assert set(d["spans"]) == {"relaunch_s", "bootstrap_s", "restore_s",
                               "first_step_s"}
    assert all(v > 0 for v in d["spans"].values())


def test_no_tpu_means_no_result_line():
    proc = _run("toy-gpt.steady", 1, allow_cpu=False, timeout=120)
    assert proc.returncode != 0
    assert not any(
        ln.lstrip().startswith("{") for ln in proc.stdout.splitlines()
    )
    assert "no TPU" in proc.stderr


def test_unknown_cell_means_no_result_line():
    proc = _run("no-such-cell", 1, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- the plain references against the program's models, toy width ------


def _toy(name):
    with open(os.path.join(TOY, "configs", name + ".json")) as f:
        return json.load(f)


def _batch(vocab, rows=2, t=64):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, vocab, (rows, t + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def test_gpt_reference_agrees_with_models_gpt():
    from dlrover_tpu.models import gpt

    config = _toy("toy-gpt")
    cfg = gpt.GPTConfig(
        vocab_size=256, block_size=64, n_layer=2, n_head=2, n_embd=64,
        dtype=jnp.float32, remat=False, use_flash_attention=False,
    )
    params = gpt.init_params(jax.random.PRNGKey(1), cfg)
    # Biases and gains off their initial 0 and 1, so that they count.
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
        params,
    )
    tok, tgt = _batch(250)
    with jax.default_matmul_precision("highest"):
        want = float(gpt.loss_fn(params, tok, tgt, cfg))
    got = float(gpt_reference.loss(params, tok, tgt, config))
    assert got == pytest.approx(want, rel=2e-5)


def test_llama_reference_agrees_with_models_llama_window_and_gqa():
    from dlrover_tpu.models import llama

    config = _toy("toy-mistral")
    cfg = llama.LlamaConfig(
        vocab_size=256, block_size=64, n_layer=2, n_head=4, n_kv_head=2,
        n_embd=64, intermediate=128, sliding_window=32,
        dtype=jnp.float32, remat=False, use_flash_attention=False,
    )
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
        params,
    )
    tok, tgt = _batch(256)
    with jax.default_matmul_precision("highest"):
        want = float(llama.loss_fn(params, tok, tgt, cfg))
    got = float(llama_reference.loss(params, tok, tgt, config))
    assert got == pytest.approx(want, rel=2e-5)
    # The window is in the reference: without it the loss differs.
    no_window = dict(config, sliding_window=None)
    assert float(llama_reference.loss(params, tok, tgt, no_window)) != pytest.approx(
        want, rel=2e-5
    )


# -- the manifest -------------------------------------------------------


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


MFU_CELLS_METRIC = "tokens_per_s"
# A metric exists once: no cell's or family's name in a metric's own.
CELL_WORDS = (".kimi.", ".mellum.", ".deepseek.", "_hybrid", "_looped",
              "_kimi", "_mellum", "_deepseek")


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_manifest_names_units_and_limits(manifest):
    """The contract's limits, asserted here once and not once a cell."""
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(word) for word in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]  # 64 at most
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for m in manifest["per_layer"]:
        assert {"name", "unit", "better", "source", "layer", "moves"} <= set(m)
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])
        assert not any(word in m["name"] for word in CELL_WORDS), m["name"]
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert _line(c["source"]) and _line(c["why"])
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) < 64 * 1024


def test_one_whole_step_share_bounds_every_training_cell(manifest):
    """Exactly one metric with ``mfu`` in its name, and every cell that
    reports ``tokens_per_s`` reports it."""
    (mfu,) = [m for m in manifest["per_layer"] if "mfu" in m["name"]]
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == MFU_CELLS_METRIC]
    assert mfu["moves"] == MFU_CELLS_METRIC
    assert sorted(mfu["workloads"]) == sorted(rate["workloads"])


def test_manifest_agrees_with_the_benchmarks_files(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    named = {}  # restricted metric -> the cells that name it, in order
    for w in manifest["workloads"]:
        cell = cell_files.load_cell(w["name"])
        assert cell["workload"]["config"] == w["config"]
        assert cell["workload"]["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert cell["workload"]["why"] == w["why"]
        # What the cell's traffic reports is what the manifest lists.
        reports = set(cell["traffic"]["end_to_end"])
        listed = {n for n, m in e2e.items()
                  if w["name"] in m.get("workloads", cells)}
        assert reports == listed, (w["name"], reports, listed)
        for n, unit in cell["traffic"]["end_to_end"].items():
            assert e2e[n]["unit"] == unit
        per_layer = cell["workload"].get("per_layer", [])
        assert len(per_layer) == len(set(per_layer)), w["name"]
        for name in per_layer:
            named.setdefault(name, []).append(w["name"])
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
    specs = {s["name"]: s for s in cell_files.layer_metric_specs()}
    # Every file has its entry and every entry its file.
    assert set(specs) == {m["name"] for m in manifest["per_layer"]}
    for m in manifest["per_layer"]:
        spec = specs[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert "workloads" not in spec, m["name"]
        # Both ways: a restricted metric's list in the manifest is the
        # cells whose workload files name it, in the manifest's order;
        # one that every cell reports has no list.
        if spec.get("restricted"):
            assert m["workloads"] == named[m["name"]], m["name"]
        else:
            assert "workloads" not in m and m["name"] not in named
        where = m.get("workloads", sorted(cells))
        # The metric it moves is reported wherever it is.
        for c in where:
            assert c in e2e[m["moves"]].get("workloads", cells)
        assert os.path.isfile(
            os.path.join(cell_files.HERE, "readers", spec["reader"] + ".py")
        )
