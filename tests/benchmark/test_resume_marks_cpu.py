"""The resume cell rehearsed on the CPU with a trace: the program's own
marks split the bootstrap in three, and the agent's marks of the
relaunch stand between the benchmark's."""

import pytest

from tests.benchmark.test_cells_cpu import _last_line, _run


def test_traced_resume_rehearsal_splits_the_bootstrap_in_three():
    line = _last_line(
        _run("toy-gpt.resume", 1, trace=1, seconds=3, timeout=280)
    )
    marks = line["detail"]["marks"]
    parts = [line["metrics"][name]["value"] for name in (
        "runtime_init_s.resume", "accelerate_s.resume", "state_init_s.resume")]
    assert all(v >= 0 for v in parts)
    assert sum(parts) == pytest.approx(
        marks["built"] - marks["dist_ready"], abs=0.1
    )
    boot = line["metrics"]["bootstrap_s.resume"]["value"]
    assert sum(parts) == pytest.approx(
        boot - (marks["dist_ready"] - marks["proc_start"]), abs=0.1
    )
    # The agent's marks of the relaunch outlive the new trainer's
    # proc_start, and the program's own stand between the script's.
    order = ["kill", "agent.exit_seen", "agent.spawned", "proc_start",
             "dist_ready", "devices_ready", "accelerate_done", "built",
             "restore_read_done", "restore_done", "first_step_done"]
    times = [marks[name] for name in order]
    assert times == sorted(times), dict(zip(order, times))
