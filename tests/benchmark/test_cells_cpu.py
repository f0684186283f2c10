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
    # No device plane in a CPU trace: no device metric, no busy_s.
    assert "mfu.train" not in line["metrics"]
    assert "busy_s" not in line["device"]


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


def test_manifest_names_units_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
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
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(json.dumps(manifest)) < 64 * 1024


def test_manifest_agrees_with_the_benchmarks_files(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
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
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
    specs = {s["name"]: s for s in cell_files.layer_metric_specs()}
    for m in manifest["per_layer"]:
        spec = specs[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        where = [c for c in m.get("workloads", cells) if c in cells]
        assert where, m["name"]
        # The metric it moves is reported wherever it is.
        for c in where:
            assert c in e2e[m["moves"]].get("workloads", cells)
        assert os.path.isfile(
            os.path.join(cell_files.HERE, "readers", spec["reader"] + ".py")
        )
