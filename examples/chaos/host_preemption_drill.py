"""Two-host elasticity drill: kill one host (agent + trainer), verify
the survivor re-rendezvouses into the shrunken world, resumes from the
flash checkpoint, and the killed host later rejoins to re-grow the
world (ref: torch elastic's membership-change restart,
elastic_agent/torch/training.py:564-619; BASELINE north star: recover
to >=90% throughput within 120 s of a host preemption).

Topology: one master (tight failure-detection knobs), two agents as
separate OS processes, each spawning a trainer that does a REAL
jax.distributed init over a 2-process CPU world (2 virtual devices
per process). The kill is a SIGKILL of host 1's whole process group —
no orderly shutdown, no checkpoint flush, exactly a preempted VM.

Recovery chain exercised end to end:
  master heartbeat watchdog -> node DELETED -> rendezvous alive-set
  shrink + RESTART_TRAINING pushed to survivors -> survivor agent
  kills its (blocked) trainer -> re-rendezvous (world 2 -> 1) ->
  jax.distributed re-init -> flash-checkpoint restore -> stepping.
Then host 1 relaunches: join -> num_nodes_waiting>0 on the survivor
-> restart -> world 1 -> 2 -> both stepping again.

Run: python examples/chaos/host_preemption_drill.py
     [--steps 400] [--output RECOVERY_2HOST.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)

from dlrover_tpu.obs.timeline import (  # noqa: E402
    load_events,
    reconstruct_recovery_timeline,
)


def read_step(path: str):
    try:
        with open(path) as f:
            d = json.load(f)
        return int(d.get("step", -1)), float(d.get("ts", 0.0))
    except (OSError, ValueError):
        return -1, 0.0


def recovery_phases(phases_path: str, t_event: float):
    """Split a recovery interval into explainable segments from the
    trainer's phase marks (TrainingMonitor.mark_phase). Marks describe
    the trainer attempt STARTED AFTER ``t_event``; returns None when
    the file predates the event (e.g. a restart that never got to
    proc_start)."""
    try:
        with open(phases_path) as f:
            marks = json.load(f)
    except (OSError, ValueError):
        return None
    order = (
        "proc_start", "dist_ready", "built", "restore_done",
        "first_step_done",
    )
    if any(k not in marks for k in order):
        return None
    if marks["proc_start"] < t_event:
        return None  # stale file from the pre-event attempt
    seg = {
        # master watchdog detection + restart push + agent respawn
        "detect_respawn_s": marks["proc_start"] - t_event,
        # master re-rendezvous + jax.distributed re-init
        "rendezvous_init_s": marks["dist_ready"] - marks["proc_start"],
        # strategy build + sharded param init (compile #1)
        "build_s": marks["built"] - marks["dist_ready"],
        # flash-checkpoint streaming restore
        "restore_s": marks["restore_done"] - marks["built"],
        # first train step (compile #2)
        "first_step_s": (
            marks["first_step_done"] - marks["restore_done"]
        ),
    }
    return {k: round(v, 2) for k, v in seg.items()}


def start_master(tmp: str):
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.master.main",
            "--node_num", "2", "--min_nodes", "1",
            "--rdzv_timeout", "5",
            "--heartbeat_timeout", "6",
            "--monitor_interval", "2",
        ],
        stdout=subprocess.PIPE,
        stderr=open(os.path.join(tmp, "master.log"), "w"),
        text=True,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 30
    port = None
    while time.time() < deadline and port is None:
        line = proc.stdout.readline()
        if line.startswith("DLROVER_TPU_MASTER_PORT="):
            port = int(line.strip().split("=")[1])
    if port is None:
        raise RuntimeError("master never printed its port")
    return proc, f"127.0.0.1:{port}"


def start_agent(
    rank: int, master_addr: str, tmp: str, steps: int
):
    """One 'host': agent + its trainer, own process group, own
    per-host job name (separate /dev/shm staging, like a real host)."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "DLROVER_TPU_JOB_NAME": f"host_drill_n{rank}",
        "DLROVER_TPU_METRICS_FILE": os.path.join(
            tmp, f"metrics_n{rank}.json"
        ),
        "DLROVER_TPU_PHASES_FILE": os.path.join(
            tmp, f"phases_n{rank}.json"
        ),
        # Obs event trace (appended across restarts): the recovery
        # timeline is reconstructed from these trainer.* marks.
        "DLROVER_TPU_TRACE_FILE": os.path.join(
            tmp, f"trace_n{rank}.jsonl"
        ),
        # The compile cache itself sits where the environment's
        # JAX_COMPILATION_CACHE_DIR says, else at the fixed path
        # jax_env.setup_distributed gives every trainer, so each
        # restarted process hits what the first one wrote.
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }
    return subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
            "--nnodes", "1:2",
            "--node_rank", str(rank),
            "--nproc_per_node", "2",
            "--master", master_addr,
            "--heartbeat_interval", "2",
            "--max_restarts", "6",
            "--rdzv_timeout", "120",
            "examples/nanogpt/train.py", "--",
            "--smoke",
            "--steps", str(steps),
            "--checkpoint-dir", os.path.join(tmp, "ckpt"),
            "--checkpoint-every", "5",
            "--global-batch-size", "8",
            "--micro-batch-size", "2",
        ],
        stdout=open(os.path.join(tmp, f"agent_n{rank}.log"), "w"),
        stderr=subprocess.STDOUT,
        cwd=REPO,
        env=env,
        start_new_session=True,  # own group: SIGKILL takes trainer too
    )


def wait_stepping(metrics: str, after_ts: float, deadline_s: float,
                  min_step: int = 1):
    """Block until the metrics file shows progress past after_ts;
    returns (step, ts) or None on timeout."""
    deadline = time.time() + deadline_s
    prev = -1
    while time.time() < deadline:
        time.sleep(1.0)
        step, ts = read_step(metrics)
        if ts > after_ts and step >= min_step and step > prev >= 0:
            return step, ts
        if ts > after_ts and step >= min_step:
            prev = step
    return None


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=400)
    p.add_argument("--recovery-budget", type=float, default=120.0)
    p.add_argument("--output", default="")
    p.add_argument(
        "--cycles", type=int, default=1,
        help="soak mode: repeat the kill/rejoin cycle N times, "
        "alternating the victim host — production elasticity means "
        "surviving REPEATED failures, not one",
    )
    args = p.parse_args()
    if args.cycles < 1:
        p.error(f"--cycles must be >= 1, got {args.cycles}")

    tmp = tempfile.mkdtemp(prefix="host_drill_")
    m0 = os.path.join(tmp, "metrics_n0.json")
    m1 = os.path.join(tmp, "metrics_n1.json")
    metrics = {0: m0, 1: m1}

    master, addr = start_master(tmp)
    agents = {}
    try:
        agents[0] = start_agent(0, addr, tmp, args.steps)
        agents[1] = start_agent(1, addr, tmp, args.steps)

        # Phase 0: both hosts stepping in the 2-node world.
        t0 = time.time()
        ok0 = wait_stepping(m0, t0 - 1, 600, min_step=3)
        ok1 = wait_stepping(m1, t0 - 1, 600, min_step=3)
        if not (ok0 and ok1):
            print("DRILL FAIL: 2-host world never reached steady "
                  "stepping; see", tmp)
            return 1
        pre_kill_step = max(ok0[0], ok1[0])
        print(f"steady 2-host stepping at step ~{pre_kill_step}")

        cycles = []
        for cyc in range(args.cycles):
            # Alternate the victim so both hosts' kill AND rejoin
            # paths get exercised across a soak.
            victim = 1 if cyc % 2 == 0 else 0
            survivor = 1 - victim

            # Kill the victim's whole process group — no orderly
            # shutdown, exactly a preempted VM.
            t_kill = time.time()
            os.killpg(agents[victim].pid, signal.SIGKILL)
            agents[victim].wait()
            print(f"[cycle {cyc}] host {victim} preempted "
                  "(SIGKILL of agent+trainer)")

            resumed = wait_stepping(
                metrics[survivor], t_kill, args.recovery_budget,
                min_step=1,
            )
            if resumed is None:
                print(f"DRILL FAIL: survivor {survivor} never "
                      f"resumed in cycle {cyc}; see", tmp)
                return 1
            c_shrink = resumed[1] - t_kill
            c_resumed_step = resumed[0]
            print(
                f"[cycle {cyc}] survivor {survivor} resumed at step "
                f"{c_resumed_step} {c_shrink:.1f}s after the kill "
                "(world 2 -> 1)"
            )
            with open(
                os.path.join(tmp, f"agent_n{survivor}.log")
            ) as f:
                c_shrank = "rank=0/1" in f.read()
            # Snapshot NOW: the regrow restarts the survivor's
            # trainer again and would overwrite these marks.
            c_phases = recovery_phases(
                os.path.join(tmp, f"phases_n{survivor}.json"), t_kill
            )
            # Canonical recovery timeline from the survivor's obs
            # event trace (failure-detect -> rendezvous -> build ->
            # restore -> first-step). Snapshot for the same reason:
            # the regrow appends another attempt's marks.
            # throughput_recovered_ts is deliberately NOT supplied:
            # the drill observes "stepping again" through a 1 s
            # metrics poll, which is not a 90%-of-baseline throughput
            # measurement — the throughput-90 phase stays None rather
            # than carrying a mislabeled number.
            tl = reconstruct_recovery_timeline(
                load_events(
                    os.path.join(tmp, f"trace_n{survivor}.jsonl")
                ),
                t_failure=t_kill,
            )
            c_timeline = (
                tl.to_dict() if tl is not None and tl.complete
                else None
            )

            # The victim comes back and the world re-grows.
            t_rejoin = time.time()
            agents[victim] = start_agent(
                victim, addr, tmp, args.steps
            )
            regrown = wait_stepping(
                metrics[victim], t_rejoin, args.recovery_budget * 2,
                min_step=1,
            )
            c_rejoin = regrown[1] - t_rejoin if regrown else None
            # Snapshot the rejoiner's phase marks now, same reason as
            # the shrink marks above.
            c_rejoin_phases = (
                recovery_phases(
                    os.path.join(tmp, f"phases_n{victim}.json"),
                    t_rejoin,
                )
                if regrown else None
            )
            if regrown:
                print(
                    f"[cycle {cyc}] host {victim} rejoined and is "
                    f"stepping again {c_rejoin:.1f}s after relaunch "
                    "(world 1 -> 2)"
                )
                # Both trainers restart on the membership change;
                # before the NEXT kill, the survivor must be stepping
                # again — killing mid-rendezvous would attribute the
                # confusion to the wrong cycle.
                if cyc < args.cycles - 1:
                    stable = wait_stepping(
                        metrics[survivor], t_rejoin,
                        args.recovery_budget, min_step=1,
                    )
                    if stable is None:
                        print(
                            f"DRILL FAIL: survivor {survivor} never "
                            f"re-stabilized after cycle {cyc}'s "
                            "regrow; see", tmp,
                        )
                        return 1
            cycles.append({
                "cycle": cyc,
                "victim": victim,
                "shrink_recovery_s": round(c_shrink, 1),
                "shrink_phases": c_phases,
                "recovery_timeline": c_timeline,
                "rejoin_recovery_s": (
                    round(c_rejoin, 1) if regrown else None
                ),
                "rejoin_phases": c_rejoin_phases,
                "resumed_step": c_resumed_step,
                "world_shrank_to_one": c_shrank,
                "regrew": bool(regrown),
                "within_budget": (
                    c_shrink <= args.recovery_budget
                    and bool(regrown)
                ),
            })

        first = cycles[0]
        result = {
            "drill": "host_preemption_2host",
            # Top-level fields are ALL cycle 0's (the one-shot drill
            # contract, tests/test_two_host_drill.py); aggregates and
            # the per-cycle records carry the rest of a soak.
            "shrink_recovery_s": first["shrink_recovery_s"],
            "shrink_phases": first["shrink_phases"],
            "recovery_timeline": first["recovery_timeline"],
            "rejoin_recovery_s": first["rejoin_recovery_s"],
            "rejoin_phases": first["rejoin_phases"],
            "pre_kill_step": pre_kill_step,
            "resumed_step": first["resumed_step"],
            "world_shrank_to_one": all(
                c["world_shrank_to_one"] for c in cycles
            ),
            "world_regrew": all(c["regrew"] for c in cycles),
            "within_budget": all(
                c["within_budget"] for c in cycles
            ),
            "recovery_budget_s": args.recovery_budget,
        }
        if args.cycles > 1:
            shrinks = [c["shrink_recovery_s"] for c in cycles]
            result["cycles"] = cycles
            result["n_cycles"] = len(cycles)
            result["max_shrink_recovery_s"] = max(shrinks)
            result["mean_shrink_recovery_s"] = round(
                sum(shrinks) / len(shrinks), 1
            )
        print(json.dumps(result))
        if args.output:
            with open(args.output, "w") as f:
                json.dump(result, f, indent=1)
        return 0 if (
            result["within_budget"] and result["world_shrank_to_one"]
        ) else 1
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
        except subprocess.TimeoutExpired:
            master.kill()


if __name__ == "__main__":
    sys.exit(main())
