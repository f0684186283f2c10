"""Flapping-host chaos: a host that dies and is relaunched IMMEDIATELY
— and then dies AGAIN mid-recovery — must still converge to a steady
2-host world. The soak drill waits for stability between kills; this
test deliberately doesn't, covering the rendezvous/agent races that
rapid churn exposes (a node registering while its previous
incarnation's death is still being processed; a kill landing mid-
rendezvous). Reuses the preemption drill's real-process helpers
(master + agents + jax.distributed trainers)."""

import os
import re
import signal
import time

import pytest

from examples.chaos.host_preemption_drill import (
    start_agent,
    start_master,
    wait_stepping,
)


# Outside tier-1 since PR 56: one case of 502 s in a run that took
# 1,351 s of its 1,470, and it fails on the tree as it stands
# (ROADMAP D2), so the count of passes does not fall.
@pytest.mark.slow
def test_double_flap_converges(tmp_path):
    tmp = str(tmp_path)
    m0 = os.path.join(tmp, "metrics_n0.json")
    m1 = os.path.join(tmp, "metrics_n1.json")
    master, addr = start_master(tmp)
    agents = {}
    # One shared wall-clock budget (the sibling drill test bounds its
    # subprocess at 900 s): every wait below draws from it, so a hung
    # rendezvous — the regime this test provokes — fails in ~15 min,
    # not the sum of all per-wait deadlines.
    deadline = time.time() + 900

    def budget():
        return max(5.0, deadline - time.time())

    try:
        agents[0] = start_agent(0, addr, tmp, 2000)
        agents[1] = start_agent(1, addr, tmp, 2000)
        t0 = time.time()
        assert wait_stepping(m0, t0 - 1, budget(), min_step=3), tmp
        assert wait_stepping(m1, t0 - 1, budget(), min_step=3), tmp

        # Flap 1: kill and relaunch host 1 with NO stabilization wait.
        os.killpg(agents[1].pid, signal.SIGKILL)
        agents[1].wait()
        agents[1] = start_agent(1, addr, tmp, 2000)

        # Flap 2: re-kill a few seconds later — mid-recovery — and
        # relaunch again.
        time.sleep(4)
        os.killpg(agents[1].pid, signal.SIGKILL)
        agents[1].wait()
        time.sleep(2)
        agents[1] = start_agent(1, addr, tmp, 2000)

        # Both hosts stepping within the remaining shared budget
        # (capped — convergence itself should take well under this).
        t_conv = time.time()
        c0 = wait_stepping(m0, t_conv, min(240, budget()), min_step=1)
        c1 = wait_stepping(m1, t_conv, min(240, budget()), min_step=1)
        assert c0 and c1, f"flap did not converge: {c0} {c1}; see {tmp}"
        # Stepping alone is not convergence — a split brain (each host
        # alone in its own world=1) would also step. The final spawn
        # on BOTH agents must be in the re-formed 2-host world.
        for rank in (0, 1):
            with open(os.path.join(tmp, f"agent_n{rank}.log")) as f:
                spawns = re.findall(r"rank=\d+/(\d+)", f.read())
            assert spawns and spawns[-1] == "2", (
                f"agent {rank}'s final world is /{spawns[-1:]}, "
                f"not the re-formed 2-host world; see {tmp}"
            )
    finally:
        for a in agents.values():
            if a.poll() is None:
                try:
                    os.killpg(a.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        master.terminate()
        try:
            master.wait(10)
        except Exception:  # noqa: BLE001
            master.kill()
