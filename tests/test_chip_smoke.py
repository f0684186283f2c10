"""Rehearsal of chip_smoke.py off the chip.

The script itself must refuse to pass here: run as the driver runs it,
it finds no TPU and exits non-zero before any phase. What can be
rehearsed is the control flow of its phases, called as functions at
the 2-layer smoke width on the CPU — without ``require_tpu``, the one
platform check, which no option of the script bypasses.
"""

import os
import shutil
import subprocess
import sys
import tempfile

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run_script(script, *args, env=None):
    return subprocess.run(
        [sys.executable, script, *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_refuses_to_pass_without_a_tpu(args):
    out = _run_script(os.path.join(REPO, "chip_smoke.py"), *args)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    # It stopped before any phase, and printed no result.
    assert "train_launch" not in out.stdout + out.stderr
    assert '"ok"' not in out.stdout


def test_script_alone_is_not_the_program(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the
    repo: there is nothing to drive, so it fails, even where
    dlrover_tpu could be imported from somewhere else."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_script(
        str(tmp_path / "chip_smoke.py"), env={"PYTHONPATH": REPO}
    )
    assert out.returncode != 0
    assert "holds none" in out.stderr
    assert '"ok"' not in out.stdout


def test_require_tpu_is_the_platform_check():
    chip_smoke.require_tpu(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.require_tpu(CPU, 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="4 chip"):
        chip_smoke.require_tpu(
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4
        )


def test_failing_or_hanging_child_fails_the_run():
    with pytest.raises(chip_smoke.SmokeFailure, match="exit code 3"):
        chip_smoke.run_child(
            "boom", [sys.executable, "-c", "import sys; sys.exit(3)"], 60
        )
    with pytest.raises(chip_smoke.SmokeFailure, match="timed out"):
        chip_smoke.run_child(
            "hang", [sys.executable, "-c", "import time; time.sleep(60)"], 1
        )


def test_train_phase_saves_exits_and_resumes(tmp_path, monkeypatch):
    """Both launches through elastic_run --standalone with no
    --nproc_per_node: six steps and a checkpoint, then a fresh
    process that restores step 6, takes two more, and loads its step
    program from the compile cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    # The CPU compiles this small step in under the cache's default
    # one-second floor.
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    monkeypatch.setenv("XLA_FLAGS", "")  # one device, as on one chip
    chip_smoke.phase_train(str(tmp_path), "smoke", CPU)
    assert os.listdir(tmp_path / "jax")
    # What the job keeps per host (sockets, metrics, beacon, the
    # agent's checkpoint staging) went under the work directory and
    # nowhere else.
    job = f"smoke{os.getpid()}"
    assert any(job in name for name in os.listdir(tmp_path))
    tmp = tempfile.gettempdir()
    leaked = [
        os.path.join(d, name)
        for d in (tmp, os.path.join(tmp, "dlrover_tpu_sock"))
        if os.path.isdir(d)
        for name in os.listdir(d)
        if job in name
    ]
    assert not leaked


def test_train_phase_fails_when_a_check_fails(tmp_path, monkeypatch):
    """Reports that say the second launch started over are refused."""
    good = {
        "device": CPU, "start_step": 0, "last_step": 6, "saved_step": 6,
        "losses": [5.0, 4.9, 4.8, 4.7, 4.6, 4.5], "first_step_s": 1.0,
        "step_cache_hits": 0, "step_cache_misses": 1, "steps_per_s": 1.0,
        "peak_bytes_in_use": None, "tpu_custom_calls": 0,
        "step_compiles": 1, "compile_cache_dir": "x",
    }
    second = {**good, "losses": [4.4, 4.3], "step_cache_hits": 1,
              "step_cache_misses": 0, "last_step": 2}
    reports = iter([good, second])
    monkeypatch.setattr(
        chip_smoke, "launch_trainer", lambda *a, **k: next(reports)
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="restored step 0"):
        chip_smoke.phase_train(str(tmp_path), "smoke", CPU)


def test_kernel_phase_checks_every_kernel():
    table = chip_smoke.run_kernels("smoke")
    names = {row["kernel"] for row in table}
    assert {"flash_fwd_bwd", "flash_rect_fwd",
            "quantize_4bit_roundtrip",
            "ssd_fwd_bwd_f32", "ssd_fwd_bwd_bf16"} <= names
    assert all(row.get("ok", True) for row in table)


def test_multichip_phase_on_four_virtual_devices():
    """data=4 and fsdp=4 against one device, the flash kernel
    (interpreted here) inside shard_map over the mesh."""
    assert len(jax.devices()) >= 4
    runs = chip_smoke.run_multichip("smoke")
    assert runs["data"]["batch_devices"] == 4
    assert runs["fsdp"]["param_devices"] == 4
    assert not runs["fsdp"]["param_replicated"]
    for name in ("data", "fsdp"):
        assert runs[name]["step_compiles"] == 1
        for got, want in zip(runs[name]["losses"], runs["one"]["losses"]):
            assert abs(got - want) < 1e-2 * want  # bf16, as on the chip
