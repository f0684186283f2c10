"""Preemption drill: kill the training process mid-run, measure
recovery (ref: docs/tutorial/fault_tolerations.md chaosblade drills;
BASELINE north star: >=90% of pre-failure throughput within 120s).

Launches `elastic_run --standalone` on the nanoGPT example, waits for
steady-state stepping, SIGKILLs the *training process* (not the
agent), and measures:

* detection + restart latency (agent monitor loop),
* steps lost (checkpoint-resume distance),
* time until the post-restart step rate reaches 90% of pre-kill.

Run: python examples/chaos/preemption_drill.py [--kill-signal TERM]
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


def read_step(path: str):
    try:
        with open(path) as f:
            d = json.load(f)
        return int(d.get("step", -1)), float(d.get("ts", 0))
    except (OSError, ValueError):
        return -1, 0.0


def find_training_pid(agent_pid: int):
    """The training process is the grandchild running train.py."""
    out = subprocess.run(
        ["ps", "-eo", "pid,ppid,args"], capture_output=True, text=True
    ).stdout
    procs = {}
    for line in out.splitlines()[1:]:
        parts = line.split(None, 2)
        if len(parts) < 3:
            continue
        pid, ppid, args = int(parts[0]), int(parts[1]), parts[2]
        procs[pid] = (ppid, args)
    for pid, (ppid, args) in procs.items():
        if "train.py" in args and "elastic_run" not in args:
            # walk ancestry to confirm it belongs to our launcher
            cur = ppid
            for _ in range(5):
                if cur == agent_pid:
                    return pid
                cur = procs.get(cur, (0, ""))[0]
    return None


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--kill-signal", default="KILL")
    p.add_argument("--recovery-budget", type=float, default=120.0)
    p.add_argument(
        "--output", default="",
        help="also write the result JSON to this path",
    )
    args = p.parse_args()

    job = f"drill{os.getpid()}"
    tmp = tempfile.mkdtemp(prefix="drill_")
    metrics = os.path.join(tmp, "metrics.json")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        DLROVER_TPU_JOB_NAME=job,
        DLROVER_TPU_METRICS_FILE=metrics,
        # Persistent compilation cache: the restarted process must not
        # pay the cold compile again — same mechanism production TPU
        # jobs rely on for fast recovery. Its directory is the
        # environment's JAX_COMPILATION_CACHE_DIR where set, else the
        # fixed one jax_env.setup_distributed gives every trainer.
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--standalone", "examples/nanogpt/train.py", "--",
        "--smoke", "--steps", str(args.steps),
        "--checkpoint-dir", os.path.join(tmp, "ckpt"),
        "--checkpoint-every", "5",
    ]
    launcher = subprocess.Popen(cmd, env=env)
    try:
        # wait for steady stepping (cold compile on 1 CPU core is slow)
        deadline = time.time() + 600
        last = (-1, 0.0)
        rates = []
        while time.time() < deadline:
            time.sleep(1.0)
            step, ts = read_step(metrics)
            if step > 5 and last[0] > 0 and step > last[0]:
                rates.append((step - last[0]) / max(ts - last[1], 1e-9))
            last = (step, ts)
            if len(rates) >= 3:
                break
        if len(rates) < 3:
            print("DRILL FAIL: never reached steady state")
            return 1
        base_rate = sorted(rates)[len(rates) // 2]
        pre_kill_step = last[0]

        pid = find_training_pid(launcher.pid)
        if pid is None:
            print("DRILL FAIL: training pid not found")
            return 1
        sig = getattr(signal, f"SIG{args.kill_signal}")
        t_kill = time.time()
        os.kill(pid, sig)
        print(
            f"killed training pid {pid} at step {pre_kill_step} "
            f"(base rate {base_rate:.2f} steps/s)"
        )

        # measure recovery: step rate back to >= 90% of base
        recovered_at = None
        resumed_step = None
        last = (-1, 0.0)
        while time.time() - t_kill < args.recovery_budget:
            time.sleep(1.0)
            step, ts = read_step(metrics)
            if step >= 0 and ts > t_kill:
                if resumed_step is None:
                    resumed_step = step
                if last[0] > 0 and step > last[0]:
                    rate = (step - last[0]) / max(ts - last[1], 1e-9)
                    if rate >= 0.9 * base_rate:
                        recovered_at = time.time() - t_kill
                        break
                last = (step, ts)
        result = {
            "metric": "preemption_recovery_seconds",
            "value": round(recovered_at, 1) if recovered_at else None,
            "unit": "s",
            "base_rate_steps_per_s": round(base_rate, 2),
            "pre_kill_step": pre_kill_step,
            "resumed_step": resumed_step,
            "steps_lost": (
                max(pre_kill_step - resumed_step, 0)
                if resumed_step is not None
                else None
            ),
            "within_budget": recovered_at is not None,
        }
        print(json.dumps(result))
        if args.output:
            with open(args.output, "w") as f:
                json.dump(result, f, indent=1)
        return 0 if recovered_at is not None else 1
    finally:
        launcher.terminate()
        try:
            launcher.wait(10)
        except subprocess.TimeoutExpired:
            launcher.kill()


if __name__ == "__main__":
    sys.exit(main())
