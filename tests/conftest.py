"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Must set the env vars before jax initializes its backends, so this
executes at conftest import time (pytest loads conftest before test
modules import jax).
"""

import os
import tempfile

# Tests always run on the virtual 8-device CPU mesh, whatever the
# environment asks for.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent autotune cache defaults to TUNE_CACHE.jsonl at the
# repo root; tests must never write there. Suites that exercise the
# cache pass an explicit tmp path (which bypasses this) or monkeypatch
# the env themselves.
os.environ.setdefault("DLROVER_TPU_TUNE_CACHE", "0")

# Likewise the bench ledger (tools/bench_ledger.py): it defaults to the
# committed BENCH_LEDGER.jsonl, and suites that fake a bench failure
# (test_perf_obs, test_beacon_stall) would append a record to it on
# every run. Suites that read a ledger back set their own path.
os.environ.setdefault(
    "DLROVER_TPU_BENCH_LEDGER",
    os.path.join(
        tempfile.mkdtemp(prefix="dlrover_tpu_test_ledger_"),
        "BENCH_LEDGER.jsonl",
    ),
)

# The remediation engine's background thread must never act mid-test
# on a JobMaster a suite built for something else (its first tick at
# the default 15 s cadence could cordon a deliberately-degraded drill
# host and change later assertions). Suites that exercise remediation
# pass an explicit config (which beats the env) and tick manually.
os.environ.setdefault("DLROVER_TPU_REMEDIATION_INTERVAL_S", "9999")

import pytest  # noqa: E402


@pytest.fixture()
def fresh_context():
    """Reset the global Context singleton around a test."""
    from dlrover_tpu.common.config import Context

    Context.reset()
    yield Context.singleton()
    Context.reset()


@pytest.fixture(autouse=True)
def _teardown_ckpt_saver_singleton():
    """The agent-hosted AsyncCheckpointSaver is a process singleton;
    a test that started one (agent run paths) must not pin its
    checkpoint dir for later tests' standalone Checkpointers."""
    yield
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

    inst = AsyncCheckpointSaver._instance
    if inst is not None:
        try:
            inst.close()
        except Exception:  # noqa: BLE001
            pass
