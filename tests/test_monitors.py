"""Monitors, metric collector, profiler."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.monitor import (
    ResourceMonitor,
    TrainingMonitor,
    current_resource_stats,
)
from dlrover_tpu.master.job_manager import JobManager
from dlrover_tpu.master.metrics import (
    JobMetricCollector,
    JsonFileReporter,
)
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.utils.profiler import (
    profile_fn,
    summarize,
    transformer_component_flops,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClient:
    def __init__(self):
        self.resources = []
        self.steps = []

    def report_resource(self, **kw):
        self.resources.append(kw)

    def report_step(self, step, tokens=0):
        self.steps.append((step, tokens))


def test_resource_stats_sampled():
    stats = current_resource_stats()
    assert stats["memory_mb"] > 0  # psutil is available here


def test_resource_monitor_reports():
    client = FakeClient()
    mon = ResourceMonitor(client, interval=999)
    out = mon.report_once()
    assert client.resources and client.resources[0] == out


def test_hbm_gauge_comes_from_the_trainers_metrics_file(tmp_path):
    """The agent never asks JAX for the chips' memory: the trainer,
    which owns them, writes it to the metrics file it writes anyway."""
    path = str(tmp_path / "metrics.json")
    client = FakeClient()
    mon = ResourceMonitor(client, interval=999, metrics_file=path)
    assert mon.report_once()["hbm_used_gb"] == 0.0  # no trainer yet
    TrainingMonitor.write_metrics(3, tokens=10, path=path)
    data = json.load(open(path))
    # This process's backend is the CPU, which reports no memory.
    assert "hbm_used_gb" not in data
    json.dump({**data, "hbm_used_gb": 1.75}, open(path, "w"))
    assert mon.report_once()["hbm_used_gb"] == 1.75
    assert client.resources[-1]["hbm_used_gb"] == 1.75
    assert mon.build_snapshot()["resource"]["hbm_used_gb"] == 1.75


def test_trainer_samples_hbm_where_the_backend_reports_it(
    tmp_path, monkeypatch
):
    import types

    from dlrover_tpu.agent import monitor

    devices = [
        types.SimpleNamespace(
            memory_stats=lambda: {"bytes_in_use": 3 << 29}
        )
    ] * 4
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    jax.devices()  # the trainer has its backend up by its first step
    assert monitor._local_hbm_used_gb() == 6.0
    path = str(tmp_path / "metrics.json")
    TrainingMonitor.write_metrics(1, path=path)
    assert json.load(open(path))["hbm_used_gb"] == 6.0


_NO_BACKEND = (
    "import sys\n"
    "jax = sys.modules.get('jax')\n"
    "state = ('absent' if jax is None else 'initialized' "
    "if jax._src.xla_bridge.backends_are_initialized() else 'imported')\n"
)


def test_agent_side_monitors_never_initialise_a_backend():
    """ResourceMonitor + TrainingMonitor ticking in a process of
    their own leave JAX without a backend (a process that has one
    holds the chip against the trainer)."""
    import subprocess
    import sys

    code = (
        "from dlrover_tpu.agent.monitor import *\n"
        "class C:\n"
        "    def report_resource(self, **kw): pass\n"
        "    def report_step(self, *a): pass\n"
        "ResourceMonitor(C(), interval=999).report_once()\n"
        "TrainingMonitor(C(), interval=999).report_once()\n"
        + _NO_BACKEND + "print('JAX', state)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] in (
        ["JAX", "absent"], ["JAX", "imported"]
    )


def test_launcher_and_agent_never_initialise_a_backend(tmp_path):
    """elastic_run --standalone with NO --nproc_per_node: the chip
    count comes from a child that has exited, the agent supervises a
    trainer that uses JAX, and the launcher/agent process itself ends
    with no backend of its own."""
    import subprocess
    import sys

    script = tmp_path / "train.py"
    script.write_text(
        "import jax, jax.numpy as jnp\n"
        "from dlrover_tpu.agent.monitor import TrainingMonitor\n"
        "from dlrover_tpu.trainer import jax_env\n"
        "jax_env.setup_distributed()\n"
        "x = (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()\n"
        "TrainingMonitor.write_metrics(1, tokens=64)\n"
        "print('TRAINER_DEVICES', len(jax.devices()))\n"
    )
    code = (
        "import sys\n"
        "from dlrover_tpu.trainer import elastic_run\n"
        "from dlrover_tpu.agent.agent import ElasticAgent\n"
        "seen = []\n"
        "spawn = ElasticAgent._spawn\n"
        "def spy(self, spec):\n"
        "    seen.append(self.config.local_world_size)\n"
        "    return spawn(self, spec)\n"
        "ElasticAgent._spawn = spy\n"
        f"rc = elastic_run.main(['--standalone', {str(script)!r}])\n"
        + _NO_BACKEND + "print('AGENT', rc, seen, state)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240,
        env={
            **os.environ, "PYTHONPATH": REPO_ROOT,
            "DLROVER_TPU_JOB_NAME": f"nojax{os.getpid()}",
            "DLROVER_TPU_METRICS_FILE": str(tmp_path / "m.json"),
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax"),
        },
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRAINER_DEVICES 8" in out.stdout
    agent_line = [
        line for line in out.stdout.splitlines() if line.startswith("AGENT")
    ][-1]
    # rc 0, the child counted conftest's 8 virtual devices, and no
    # backend was ever brought up in this process.
    assert agent_line in (
        "AGENT 0 [8] absent", "AGENT 0 [8] imported"
    ), agent_line


def test_training_monitor_relays_new_steps(tmp_path):
    path = str(tmp_path / "metrics.json")
    client = FakeClient()
    mon = TrainingMonitor(client, metrics_file=path, interval=999)
    assert mon.report_once() is None  # no file yet
    TrainingMonitor.write_metrics(5, tokens=1000, path=path)
    assert mon.report_once() == 5
    assert client.steps == [(5, 1000)]
    # same step again: not re-reported
    assert mon.report_once() is None
    TrainingMonitor.write_metrics(6, tokens=2000, path=path)
    assert mon.report_once() == 6


def test_metric_collector_snapshot(tmp_path):
    jm = JobManager()
    jm.register_node(node_id=0)
    jm.register_node(node_id=1)
    jm.handle_failure_report(1, "CUDA out of memory", "process_error", 0)
    sm = SpeedMonitor()
    path = str(tmp_path / "metrics.jsonl")
    coll = JobMetricCollector(
        "jobZ", jm, sm, reporters=[JsonFileReporter(path)], interval=999
    )
    snap = coll.collect_once()
    assert snap.workers_alive == 1
    assert snap.workers_pending == 1  # OOM replacement
    assert snap.failure_counts.get("oom") == 1
    with open(path) as f:
        on_disk = json.loads(f.readline())
    assert on_disk["job_name"] == "jobZ"


def test_profile_fn_costs_and_timing():
    def fn(x):
        return x @ x

    x = jnp.ones((256, 256), jnp.float32)
    prof = profile_fn(fn, x, iters=3)
    # 2*M*N*K flops for the matmul
    assert prof.flops == pytest.approx(2 * 256**3, rel=0.1)
    assert prof.wall_time_s > 0
    assert prof.arithmetic_intensity > 0
    assert "GFLOP" in summarize(prof, "matmul")


def test_transformer_component_flops_sums_to_model():
    from dlrover_tpu.models import gpt

    cfg = gpt.GPTConfig.nano()
    comp = transformer_component_flops(
        cfg.n_layer, cfg.n_embd, cfg.block_size, cfg.vocab_size
    )
    total_per_token = sum(comp.values()) / cfg.block_size
    model_estimate = gpt.flops_per_token(cfg)
    assert total_per_token == pytest.approx(model_estimate, rel=0.05)


def test_training_monitor_reports_token_deltas_and_restarts(tmp_path):
    """Cumulative token counts become per-report deltas; a restart at
    a lower step re-baselines instead of going silent."""
    path = str(tmp_path / "metrics.json")
    client = FakeClient()
    mon = TrainingMonitor(client, metrics_file=path, interval=999)
    TrainingMonitor.write_metrics(1, tokens=1000, path=path)
    mon.report_once()
    TrainingMonitor.write_metrics(2, tokens=2500, path=path)
    mon.report_once()
    assert client.steps == [(1, 1000), (2, 1500)]  # deltas
    # restart: resume at step 1 with fresh cumulative counter
    TrainingMonitor.write_metrics(1, tokens=800, path=path)
    assert mon.report_once() == 1
    assert client.steps[-1] == (1, 800)


def test_json_file_reporter_appends_and_failure_is_contained(tmp_path):
    """A JsonFileReporter writing to a dead path raises from report();
    collect_once must contain it (warn + keep going) and still feed
    every other reporter."""
    good_path = str(tmp_path / "metrics.jsonl")
    bad = JsonFileReporter(str(tmp_path / "no_such_dir" / "m.jsonl"))
    good = JsonFileReporter(good_path)
    jm = JobManager()
    jm.register_node(node_id=0)
    coll = JobMetricCollector(
        "jobF", jm, SpeedMonitor(),
        reporters=[bad, good], interval=999,
    )
    with pytest.raises(OSError):
        bad.report(coll.snapshot())  # the reporter itself raises...
    snap = coll.collect_once()  # ...but the collector survives it
    assert snap.workers_alive == 1
    # and the healthy reporter appended one line per collect
    coll.collect_once()
    with open(good_path) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == 2
    assert all(rec["job_name"] == "jobF" for rec in lines)


def test_recovery_seconds_uses_crossing_time_not_poll_time():
    """A late recovery_seconds() poll must report when the throughput
    window first regained 90% of pre-failure speed (the crossing
    sample's timestamp), not how long ago the poll happened."""
    import time as _time

    sm = SpeedMonitor(window=4)
    sm.add_running_node(0)
    sm.add_running_node(1)
    t0 = _time.time()
    for i in range(4):  # healthy: 100 tokens/s
        sm.collect_global_step(i, t0 + i, tokens=100)
    sm.remove_running_node(1)  # failure: snapshots 100 tok/s baseline
    assert sm._pre_failure_tput == pytest.approx(100.0)
    t_fail = sm._last_failure_time
    # Recovery happens "in the future" relative to the poll: samples
    # are stamped ~100s after the failure, crossing on the last one.
    base = t_fail + 100.0
    for i in range(4):  # limp along at 10 tokens/s
        sm.collect_global_step(10 + i, base + i, tokens=10)
    assert sm.recovery_seconds() is None  # not recovered yet
    for i in range(4):  # back to full speed
        sm.collect_global_step(20 + i, base + 4 + i, tokens=100)
    rec = sm.recovery_seconds()
    assert rec is not None
    # The crossing was recorded at a sample timestamp ~104-108s after
    # the failure; a poll-time answer would be ~0s here.
    assert 100.0 <= rec <= 110.0
    assert sm.recovery_seconds() == pytest.approx(rec)  # sticky


def test_remove_running_node_snapshot_is_single_lock():
    """The pre-failure throughput snapshot happens in the same lock
    acquisition as the failure bookkeeping, so it reflects the window
    at the failure instant (here: the healthy 100 tok/s window)."""
    sm = SpeedMonitor(window=4)
    sm.add_running_node(0)
    t = 1000.0
    for i in range(4):
        sm.collect_global_step(i, t + i, tokens=100)
    sm.remove_running_node(0)
    assert sm._pre_failure_tput == pytest.approx(100.0)
    # A node never marked running must not re-arm failure tracking.
    sm.reset_failure_tracking()
    sm.remove_running_node(99)
    assert sm._pre_failure_tput is None


def test_recovery_not_vouched_by_pre_failure_window():
    """A window still dominated by healthy pre-failure samples must
    not claim recovery the moment the first post-failure report
    lands — only post-failure samples vouch for the crossing."""
    import time as _time

    sm = SpeedMonitor(window=6)
    sm.add_running_node(0)
    t0 = _time.time()
    for i in range(6):  # full healthy window at 100 tok/s
        sm.collect_global_step(i, t0 + i, tokens=100)
    sm.remove_running_node(0)
    fail_t = sm._last_failure_time
    # One slow post-failure sample: the healthy samples still in the
    # deque would put the full-window tput way above 90%.
    sm.collect_global_step(20, fail_t + 30.0, tokens=10 * 30)
    assert sm.recovery_seconds() is None
    sm.collect_global_step(21, fail_t + 60.0, tokens=10 * 30)
    assert sm.recovery_seconds() is None  # post tput = 10/s, not 90
    # Ramp back up: not recovered until the post-failure window
    # itself sustains >= 90 tok/s (the slow samples must age out).
    for k, ts in enumerate((90.0, 120.0, 150.0, 180.0)):
        sm.collect_global_step(22 + k, fail_t + ts, tokens=100 * 30)
    assert sm.recovery_seconds() is None  # window still 82 tok/s
    sm.collect_global_step(26, fail_t + 210.0, tokens=100 * 30)
    rec = sm.recovery_seconds()
    assert rec == pytest.approx(210.0, abs=1.0)


def test_resource_monitor_trace_tail_defers_past_event_cap(
    tmp_path, monkeypatch
):
    """A burst larger than the per-snapshot cap is split across
    snapshots, never dropped."""
    trace = tmp_path / "trace.jsonl"
    monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", str(trace))
    client = SnapshotFakeClient()
    mon = ResourceMonitor(
        client, interval=999, metrics_file=str(tmp_path / "m.json")
    )
    mon.MAX_EVENTS_PER_SNAPSHOT = 3
    with open(trace, "w") as f:
        for i in range(5):
            f.write(json.dumps({"name": f"e{i}", "ts": float(i)}) + "\n")
    mon.report_once()
    mon.report_once()
    got = [
        [e["name"] for e in s["events"]] for s in client.snapshots
    ]
    assert got == [["e0", "e1", "e2"], ["e3", "e4"]]


def test_resource_monitor_skips_pre_restart_trace_history(
    tmp_path, monkeypatch
):
    """A restarted agent must not re-ship (and double-count) the
    trace lines its previous incarnation already sent."""
    trace = tmp_path / "trace.jsonl"
    trace.write_text(
        json.dumps({"name": "old.event", "ts": 1.0}) + "\n"
    )
    monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", str(trace))
    client = SnapshotFakeClient()
    mon = ResourceMonitor(
        client, interval=999, metrics_file=str(tmp_path / "m.json")
    )
    with open(trace, "a") as f:
        f.write(json.dumps({"name": "new.event", "ts": 2.0}) + "\n")
    mon.report_once()
    names = [e["name"] for e in client.snapshots[0]["events"]]
    assert names == ["new.event"]


def test_hang_detector_emits_obs(tmp_path):
    from dlrover_tpu import obs
    from dlrover_tpu.agent.hang_detector import HangDetector

    tracer = obs.configure_tracer()
    try:
        path = str(tmp_path / "metrics.json")
        det = HangDetector(
            hang_timeout=0.01, startup_grace=999.0, metrics_file=path
        )
        TrainingMonitor.write_metrics(1, path=path)
        assert det.check() is False  # first step = progress
        counter = obs.get_registry().get("dlrover_hang_detect_total")
        before = counter.value()
        import time as _time

        _time.sleep(0.05)
        assert det.check() is True
        assert det.check() is True  # still hung
        assert counter.value() == before + 1  # one hang, one count
        hangs = [
            e for e in tracer.events()
            if e["name"] == "agent.hang_detected"
        ]
        assert len(hangs) == 1
        assert hangs[0]["seconds_since_progress"] >= 0.01
        assert hangs[0]["last_step"] == 1
        # Progress re-arms the detector for the next hang.
        TrainingMonitor.write_metrics(2, path=path)
        assert det.check() is False
        _time.sleep(0.05)
        assert det.check() is True
        assert counter.value() == before + 2
    finally:
        obs.disable_tracer()


def test_write_metrics_records_recent_step_times(tmp_path):
    path = str(tmp_path / "metrics.json")
    TrainingMonitor.write_metrics(1, tokens=100, path=path,
                                  step_time=0.2)
    TrainingMonitor.write_metrics(2, tokens=220, path=path,
                                  step_time=0.3)
    with open(path) as f:
        data = json.load(f)
    assert data["recent_step_times"] == [0.2, 0.3]


class SnapshotFakeClient(FakeClient):
    def __init__(self):
        super().__init__()
        self.snapshots = []

    def report_metrics_snapshot(self, **kw):
        self.snapshots.append(kw)


def test_resource_monitor_ships_deduped_snapshots(tmp_path):
    """Each step time is shipped exactly once across snapshots; the
    tokens/s rate appears once two reads bracket a token delta."""
    path = str(tmp_path / "metrics.json")
    client = SnapshotFakeClient()
    mon = ResourceMonitor(client, interval=999, metrics_file=path)
    TrainingMonitor.write_metrics(1, tokens=100, path=path,
                                  step_time=0.2)
    TrainingMonitor.write_metrics(2, tokens=300, path=path,
                                  step_time=0.3)
    mon.report_once()
    assert len(client.snapshots) == 1
    snap = client.snapshots[0]
    assert snap["step_times"] == [0.2, 0.3]
    assert snap["host"] == mon.host
    assert "dlrover_hang_detect_total" in snap["registry"]
    assert "tokens_per_s" not in snap["resource"]  # no prior read
    TrainingMonitor.write_metrics(3, tokens=500, path=path,
                                  step_time=0.4)
    mon.report_once()
    snap = client.snapshots[1]
    assert snap["step_times"] == [0.4]  # only the new one
    assert snap["resource"]["tokens_per_s"] > 0
    mon.report_once()  # no trainer progress
    assert client.snapshots[2]["step_times"] == []


def test_resource_monitor_snapshot_includes_ring_events_once(tmp_path):
    from dlrover_tpu import obs

    obs.configure_tracer()
    try:
        client = SnapshotFakeClient()
        mon = ResourceMonitor(
            client, interval=999,
            metrics_file=str(tmp_path / "m.json"),
        )
        with obs.span("agent.some_span"):
            obs.event("agent.some_event")
        mon.report_once()
        names = [
            e["name"] for e in client.snapshots[0]["events"]
        ]
        # Arrival order delivers the span even though its mono stamp
        # (span start) predates the inner event's.
        assert "agent.some_span" in names
        assert "agent.some_event" in names
        mon.report_once()
        assert client.snapshots[1]["events"] == []  # exactly once
    finally:
        obs.disable_tracer()


def test_resource_monitor_tails_shared_trace_file(
    tmp_path, monkeypatch
):
    """With DLROVER_TPU_TRACE_FILE set, the snapshot events come from
    the host's shared trace file — the trainer process appends there
    too, which is how its spans reach the master."""
    trace = tmp_path / "trace.jsonl"
    monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", str(trace))
    client = SnapshotFakeClient()
    mon = ResourceMonitor(
        client, interval=999, metrics_file=str(tmp_path / "m.json")
    )
    # "Trainer process" writes two events + one torn line.
    with open(trace, "w") as f:
        f.write(json.dumps({"name": "trainer.step", "ts": 1.0}) + "\n")
        f.write(json.dumps(
            {"name": "ckpt.save_memory", "ts": 2.0, "dur_s": 0.5}
        ) + "\n")
        f.write('{"name": "torn')
    mon.report_once()
    names = [e["name"] for e in client.snapshots[0]["events"]]
    assert names == ["trainer.step", "ckpt.save_memory"]
    # The torn line completes later and ships exactly once.
    with open(trace, "a") as f:
        f.write('_done", "ts": 3.0}\n')
    mon.report_once()
    names = [e["name"] for e in client.snapshots[1]["events"]]
    assert names == ["torn_done"]
    mon.report_once()
    assert client.snapshots[2]["events"] == []


def test_mark_phase_mirrors_to_obs_tracer(tmp_path, monkeypatch):
    """Phase marks feed the recovery-timeline reconstructor through
    the obs tracer, independent of the phases file."""
    from dlrover_tpu import obs
    from dlrover_tpu.obs.timeline import reconstruct_recovery_timeline

    monkeypatch.delenv("DLROVER_TPU_PHASES_FILE", raising=False)
    tracer = obs.configure_tracer()
    try:
        for mark in ("proc_start", "dist_ready", "built",
                     "restore_done", "first_step_done"):
            TrainingMonitor.mark_phase(mark)
        events = tracer.events()
        t_fail = events[0]["ts"] - 1.0
        tl = reconstruct_recovery_timeline(events, t_failure=t_fail)
        assert tl is not None and tl.complete
        assert tl.phases["failure-detect"] >= 1.0
    finally:
        obs.disable_tracer()
