"""Traffic kind ``save_kill_resume``: the trainer script under the
launcher, flash checkpoints in the window, a SIGKILL at its end, the
agent's restart, the restore, the first step after it.

The kill is fixed to the save schedule and not to the clock: once
``--seconds`` have passed, it waits for the next save to memory behind
which every save to disk stands committed in the checkpoint
directory, and lands ``kill_after_save_steps`` steps after that one. A faster or slower step moves the kill in time and leaves
it where it was in the schedule: never in a save, never in a persist.

This parent never touches JAX: the chip belongs to the trainer the
agent spawns. Everything is timed on ``time.time()``, which the
trainer's step records and phase marks share.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

from benchmark import cell as cell_files
from benchmark import step_metrics
from benchmark.kinds import common

POLL_S = 0.02


class RunFailed(Exception):
    pass


def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _wait(what: str, cond, proc, deadline: float):
    """Poll ``cond()`` until it returns something; the launcher dying
    or the deadline passing first is a failure."""
    while True:
        got = cond()
        if got:
            return got
        if proc.poll() is not None:
            raise RunFailed(f"{what}: the launcher exited with {proc.returncode}")
        if time.time() > deadline:
            raise RunFailed(f"{what}: timed out")
        time.sleep(POLL_S)


def run(cell: dict, opts: dict) -> dict:
    work = tempfile.mkdtemp(prefix="bk_")
    steps_file = os.path.join(work, "steps.jsonl")
    phases_file = os.path.join(work, "phases.json")
    spec_file = os.path.join(work, "spec.json")
    report_prefix = os.path.join(work, "report_")
    trace_dir = os.path.join(work, "trace")
    with open(spec_file, "w") as f:
        json.dump({
            "cell": cell, "seed": opts["seed"], "seconds": opts["seconds"],
            "trace": opts["trace"], "trace_dir": trace_dir,
            "allow_cpu": opts["allow_cpu"], "steps_file": steps_file,
            "ckpt_dir": os.path.join(work, "ckpt"),
            "report_prefix": report_prefix,
        }, f)
    env = dict(
        os.environ,
        TMPDIR=work,
        TPU_LOG_DIR=os.path.join(work, "tpu_logs"),
        DLROVER_TPU_JOB_NAME=f"bk{os.getpid()}",
        # AF_UNIX paths end at 107 bytes: a short directory of its own.
        DLROVER_TPU_SOCK_DIR=os.path.join(work, "s"),
        DLROVER_TPU_PHASES_FILE=phases_file,
        PYTHONPATH=cell_files.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    log_path = os.path.join(work, "launch.log")
    deadline = time.time() + float(opts["deadline_s"])
    proc = None
    try:
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
                 "--standalone", "--max_restarts", "1",
                 os.path.join(cell_files.HERE, "trainer_loop.py"),
                 "--", "--spec", spec_file],
                cwd=cell_files.REPO, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        result = _drive(cell, opts, proc, deadline, steps_file, phases_file,
                        report_prefix, trace_dir, os.path.join(work, "ckpt"))
        try:
            proc.wait(timeout=max(deadline - time.time(), 1.0))
        except subprocess.TimeoutExpired:
            raise RunFailed("the launcher did not exit after the resumed run")
        if proc.returncode != 0:
            raise RunFailed(f"the launcher exited with {proc.returncode}")
        return result
    except RunFailed:
        with open(log_path) as f:
            sys.stderr.write(f.read()[-6000:])
        raise
    finally:
        if proc is not None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
        if opts.get("keep_work"):
            print(f"[bench] work directory kept: {work}", file=sys.stderr)
        else:
            shutil.rmtree(work, ignore_errors=True)


def committed_step(ckpt_dir: str) -> int:
    """The newest step the checkpoint directory shows as committed:
    the tracker file the program's saver writes after the rename that
    publishes a step (flash_checkpoint/engine.py ``TRACKER_FILE``; the
    name is written out here because this parent imports nothing that
    imports JAX). -1 before the first."""
    try:
        with open(os.path.join(ckpt_dir, "latest_checkpointed_step")) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return -1


def _span(marks: dict, a: str, b: str):
    return marks[b] - marks[a] if a in marks and b in marks else None


def _drive(cell, opts, proc, deadline, steps_file, phases_file,
           report_prefix, trace_dir, ckpt_dir) -> dict:
    traffic = cell["traffic"]
    kill_after = int(traffic["kill_after_save_steps"])

    def incarnations():
        if not os.path.exists(steps_file):
            return []
        return step_metrics.split_incarnations(
            step_metrics.read_records(steps_file)
        )

    first = _wait("window open", lambda: incarnations()[:1], proc, deadline)[0]
    setup_s = first["open"] - opts["t_start"]
    t_end = first["open"] + float(opts["seconds"])
    save_every = int(traffic["save_every"])
    anchor = {}  # the save the kill is counted from, once one qualifies

    def ready_to_kill():
        if time.time() < t_end:
            return None
        steps = incarnations()[0]["steps"]
        saves = [s["save"] for s in steps if "save" in s]
        if not saves:
            return None
        newest, last = steps[-1]["step"], saves[-1]
        if anchor.get("step") != last["save_step"]:
            anchor.clear()
            to_disk = [sv["save_step"] for sv in saves
                       if sv["to_disk"] and sv["save_ok"]]
            if (
                last["save_ok"] and not last["to_disk"]
                and last["t_issued"] >= t_end
                and (not to_disk or committed_step(ckpt_dir) >= to_disk[-1])
            ):
                anchor["step"] = last["save_step"]
        # Seen too late to land before the next slot: take the next.
        if "step" in anchor and kill_after <= newest - anchor["step"] < save_every - 1:
            return steps
        return None

    _wait("kill point", ready_to_kill, proc, deadline)
    pid = first["pid"]
    committed = committed_step(ckpt_dir)
    os.kill(pid, signal.SIGKILL)
    t_kill = time.time()

    def resumed_done():
        rep = _read_json(report_prefix + "1.json")
        return rep if rep and "last_step" in rep else None

    rep1 = _wait("resumed run", resumed_done, proc, deadline)
    # Written before the window opened, and again when a trace ended.
    rep0 = _read_json(report_prefix + "0.json") or {}
    marks = _read_json(phases_file) or {}
    marks["kill"] = t_kill
    incs = incarnations()
    before = [s for s in incs[0]["steps"] if s["t_done"] <= t_kill]
    after_steps = incs[1]["steps"] if len(incs) > 1 else []

    reduced = (
        common.reduce_trace(trace_dir, opts.get("dump_events", ""))
        if opts["trace"] else {}
    )

    workload = cell["workload"]
    trace_t1 = (rep0.get("trace_window") or {}).get("t1")
    window = step_metrics.window_metrics(
        first["open"], before, rep0["tokens_per_step"],
        int(workload.get("steps_per_sample", 1)), after=trace_t1,
    )
    why = []
    failed = common.check_losses(before, why)
    if not common.reference_ok(rep0.get("reference")):
        why.append(f"reference check: {rep0.get('reference')}")
    tried = [s["save"] for s in before if "save" in s]
    # A save the program dropped (its agent was still persisting the
    # last one) is its documented behaviour, not a failed step. Every
    # slot of the schedule is tried and the drops are counted.
    saves = [s for s in tried if s["save_ok"]]
    if not saves:
        why.append("no save in the window")
    else:
        newest = saves[-1]
        # The floor is the newest checkpoint the directory showed as
        # committed when the trainer died. The newest one in host
        # memory is what a flash checkpoint promises; whether the
        # restore reached it is reported (``restored_newest``), not
        # demanded, because today it cannot: PERF.md, Findings, PR 23.
        if rep1["start_step"] < committed:
            why.append(
                f"restored step {rep1['start_step']} is older than the "
                f"newest checkpoint committed on disk, {committed}"
            )
        by_step = {s["save_step"]: s for s in saves}
        want = by_step.get(rep1["start_step"], {}).get("checksum")
        if rep1["restored_checksum"] != want:
            why.append(
                f"restored parameters' checksum {rep1['restored_checksum']} "
                f"is not the saved one {want}"
            )
        if newest["step_programs"] != 1 or rep1["step_programs"] != 1:
            why.append("a process compiled its step more than once")
        if newest["compile_events"] != rep0.get("compile_events_before_window"):
            if not opts["trace"]:
                why.append("something compiled inside the window")
    # The first loss after the resume against the ten losses the first
    # process read around the restored step (other batches, the same
    # point of training): restored weights that were not the saved
    # ones would sit far outside.
    r = rep1["start_step"]
    near = [s["loss"] for s in before if r - 5 < s["step"] <= r + 5]
    if near:
        spread = max(near) - min(near)
        lo, hi = min(near) - spread, max(near) + spread
        if not lo <= rep1["first_loss"] <= hi:
            why.append(
                f"first loss after the resume {rep1['first_loss']} outside "
                f"[{lo}, {hi}]"
            )
    else:
        why.append(f"no loss recorded around the restored step {r}")
    restore_failed = 0 if rep1.get("resumed") else 1
    if restore_failed:
        why.append("the restarted process found no checkpoint")
    values = {
        "setup_s": setup_s,
        "save_stall_ms": window.get("save_stall_ms"),
        "resume_s": rep1["first_step_done"] - t_kill,
    }
    return {
        "correct": not why,
        "why": why,
        "attempted": len(before) + len(after_steps) + 1,
        "failed": failed + restore_failed,
        "values": values,
        "device": common.device_block(rep0["device"], [rep0, rep1]),
        "ctx": {
            "window": window,
            "trace": reduced,
            "counts": {
                "step_programs": rep1["step_programs"],
                "step_hbm_bytes": common.step_hbm_total(rep0),
                "resume_cache_misses": rep1["cache_misses_total"],
                "saves_dropped": len(tried) - len(saves),
            },
            "marks": marks,
        },
        "detail": {
            "resume_s": rep1["first_step_done"] - t_kill,
            "reference": rep0.get("reference"),
            "window": window,
            "marks": marks,
            "spans": {
                "relaunch_s": _span(marks, "kill", "proc_start"),
                "bootstrap_s": _span(marks, "proc_start", "built"),
                "restore_s": _span(marks, "built", "restore_done"),
                "first_step_s": _span(marks, "restore_done", "first_step_done"),
            },
            "saves": tried,
            "saves_dropped": len(tried) - len(saves),
            "committed_on_disk_at_kill": committed,
            "kill_counted_from_save": anchor.get("step"),
            "restored_step": rep1["start_step"],
            "newest_saved_step": saves[-1]["save_step"] if saves else None,
            "restored_newest": bool(
                saves and rep1["start_step"] == saves[-1]["save_step"]
            ),
            "steps_lost": (before[-1]["step"] - rep1["start_step"])
            if before else None,
            "killed_after_step": before[-1]["step"] if before else None,
            "first_loss_after_resume": rep1["first_loss"],
            "resume_cache": [rep1["cache_hits_total"], rep1["cache_misses_total"]],
            "step_hbm": rep0.get("step_hbm"),
        },
    }
