"""Multi-job pool drill: gang scheduling + checkpoint-backed
preemption on a hermetic 4-slice fake pool.

The pool counterpart of ``serve_drill``/``chaos_drill``: an
in-process :class:`~dlrover_tpu.pool.TPUPoolMaster` owns 4 fake
slices; drill "trainers" are worker THREADS speaking the real wire
protocol (``MasterClient(job_id=...)`` over the pool's single gRPC
endpoint, routed by the ``_job`` envelope id) and consuming shards
from their job's own ledger. The drill plays one full capacity
incident and asserts the acceptance contract:

* a low-priority job is running when a high-priority gang that does
  not fit arrives;
* the low job is preempted through the GRACEFUL path: its workers
  finish the in-flight shard, flash-checkpoint through the shm
  staging format, and the checkpoint is durably staged (tracker
  file) BEFORE the pool releases a single slice — asserted on the
  trace spans;
* the high gang is placed WHOLE, never partially — asserted on the
  pool's allocation events;
* on the high job's completion the preempted job resumes
  ELASTICALLY (fewer slices than its gang, >= min_slices, via
  backfill under a capacity-blocked head) and finishes with
  exactly-once shard accounting: every ledger task completed exactly
  once across both incarnations, none lost, none double-counted;
* an over-quota submission queues with a quota verdict while other
  tenants keep placing (no starvation), and the whole
  queue -> preempt -> place -> resume story is ONE distributed trace
  via ``query_traces``; ``dlrover_pool_*`` metrics expose queue
  depth, placement latency, and preemption counts.

Usage::

    python tools/pool_drill.py --selftest     # seeded, <60s (CI)
    python tools/pool_drill.py --json out.json
"""

import _repo_path  # noqa: F401  (sys.path, must precede dlrover_tpu)

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np


class DrillError(AssertionError):
    pass


def wait_for(cond, timeout: float, what: str, poll: float = 0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(poll)
    raise DrillError(f"timeout ({timeout:.0f}s) waiting for {what}")


class JobState:
    """Drill-side per-job book: which worker processed which ledger
    task how many times (the exactly-once evidence), plus checkpoint
    facts, shared across placement incarnations."""

    def __init__(self, job_id: str, dataset: str, dataset_size: int,
                 shard_ms: float, ckpt_dir: str = ""):
        self.job_id = job_id
        self.dataset = dataset
        self.dataset_size = dataset_size
        self.shard_ms = shard_ms
        self.ckpt_dir = ckpt_dir
        self.lock = threading.Lock()
        self.processed = {}  # task_id -> completion count
        self.records = 0
        self.records_at_park = -1
        self.parked_steps = []  # steps flash-checkpointed at park
        self.restored_steps = []  # steps read back on resume
        self.threads = []


def _flash_checkpoint(state: JobState, node_id: int) -> int:
    """The graceful-park checkpoint: stage this worker's state
    through the flash-checkpoint shm format (the 16.2x staging path),
    then persist payload + tracker file durably — the same
    stage-then-persist shape the agent saver uses."""
    from dlrover_tpu.common.ckpt_shm import (
        SharedMemoryHandler,
        pack_meta,
        plan_entries,
    )
    from dlrover_tpu.common.constants import CheckpointConstant

    with state.lock:
        done = np.asarray(sorted(state.processed), np.int64)
        step = int(state.records)
    entries, _ = plan_entries(
        [(
            "worker_state", "int64", [len(done)],
            [[0, len(done)]], done.nbytes,
        )]
    )
    handler = SharedMemoryHandler(node_id, job=state.job_id)
    try:
        handler.save(step, entries, [done])
        loaded = handler.load()
        if loaded is None:
            raise DrillError(
                f"{state.job_id} worker {node_id}: shm stage lost"
            )
        l_step, l_entries, _, payload = loaded
        step_dir = os.path.join(state.ckpt_dir, f"iter_{l_step}")
        os.makedirs(step_dir, exist_ok=True)
        blob = os.path.join(step_dir, f"worker_{node_id}.ckpt")
        tmp = blob + ".tmp"
        with open(tmp, "wb") as f:
            meta = pack_meta(l_step, l_entries, {})
            f.write(len(meta).to_bytes(8, "little"))
            f.write(meta)
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, blob)
        tracker = os.path.join(
            state.ckpt_dir, CheckpointConstant.TRACKER_FILE
        )
        tmp = tracker + f".tmp{node_id}"
        with open(tmp, "w") as f:
            f.write(str(l_step))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, tracker)
    finally:
        handler.unlink()
    with state.lock:
        state.parked_steps.append(step)
        state.records_at_park = step
    return step


def _restore_checkpoint(state: JobState) -> None:
    from dlrover_tpu.common.constants import CheckpointConstant

    tracker = os.path.join(
        state.ckpt_dir, CheckpointConstant.TRACKER_FILE
    )
    try:
        with open(tracker) as f:
            step = int(f.read().strip())
    except (OSError, ValueError):
        return
    with state.lock:
        state.restored_steps.append(step)


def drill_worker(
    addr: str,
    state: JobState,
    node_id: int,
    resume: bool,
) -> None:
    """One drill trainer: real RPCs against the pool endpoint with
    the job id on the envelope. Contract on park: finish + report the
    in-flight shard, flash-checkpoint durably on ``save_checkpoint``,
    exit on ``stop_training``."""
    from dlrover_tpu.agent.master_client import (
        MasterClient,
        MasterOutageError,
    )
    from dlrover_tpu.common.constants import EventAction, TaskType

    client = MasterClient(
        addr, node_id=node_id, job_id=state.job_id
    )
    parking = False
    try:
        client.register_node("worker")
        if node_id == 0 and not resume:
            client.create_dataset(
                state.dataset,
                dataset_size=state.dataset_size,
                batch_size=1,
                num_minibatches_per_shard=1,
            )
        if resume and state.ckpt_dir and node_id == 0:
            _restore_checkpoint(state)
        while True:
            action = client.heartbeat()
            if action == EventAction.SAVE_CHECKPOINT.value:
                if state.ckpt_dir:
                    _flash_checkpoint(state, node_id)
                parking = True
                continue
            if action == EventAction.STOP_TRAINING.value:
                client.report_succeeded()
                return
            if parking:
                # Parked: no new work; wait for stop_training.
                time.sleep(0.01)
                continue
            task = client.get_task(state.dataset)
            if task.task_id < 0:
                if task.task_type == TaskType.NONE:
                    client.report_succeeded()
                    return
                time.sleep(0.02)
                continue
            n = max(task.shard.end - task.shard.start, 0)
            time.sleep(state.shard_ms / 1000.0)
            with state.lock:
                state.processed[task.task_id] = (
                    state.processed.get(task.task_id, 0) + 1
                )
                state.records += n
            client.report_task_result(
                state.dataset, task.task_id, True
            )
    except MasterOutageError:
        return
    finally:
        client.close()


def make_launcher(states):
    def launch(job_id, addr, slices, resume):
        state = states[job_id]
        for i in range(len(slices)):
            t = threading.Thread(
                target=drill_worker,
                args=(addr, state, i, resume),
                name=f"{job_id}-w{i}",
                daemon=True,
            )
            t.start()
            state.threads.append(t)

    return launch


def run_pool_drill(seed: int = 7, shard_ms: float = 20.0) -> dict:
    import dlrover_tpu.obs as obs
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.pool import (
        PoolJobSpec,
        PoolJobState,
        TPUPoolMaster,
        tracker_ckpt_probe,
    )

    tracer = obs.configure_tracer()  # in-memory ring
    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="pool_drill_")
    low_ckpt = os.path.join(tmp, "low_ckpt")
    os.makedirs(low_ckpt, exist_ok=True)

    states = {
        "low": JobState("low", "ds-low", 40, shard_ms, low_ckpt),
        "high": JobState("high", "ds-high", 12, shard_ms),
        "med": JobState("med", "ds-med", 8, shard_ms),
        "overq": JobState("overq", "ds-overq", 6, shard_ms),
    }
    job_master_defaults = dict(
        rdzv_timeout=1.0,
        heartbeat_timeout=60.0,
        monitor_interval=600.0,
        collect_interval=999.0,
        health_interval=9999.0,
        remediation_interval=9999.0,
    )
    master = TPUPoolMaster(
        slices=4,
        tenant_quotas={"research": 3},
        park_timeout_s=30.0,
        watch_interval=0.1,
        worker_launcher=make_launcher(states),
        job_master_defaults=job_master_defaults,
    )
    master.prepare()
    client = MasterClient(master.addr, node_id=-1)

    def status(job_id):
        return client.pool_job_status(job_id)

    def submit(job_id, tenant, priority, n, mins=0, probe=None):
        r = master.submit(
            PoolJobSpec(
                job_id=job_id, tenant=tenant, priority=priority,
                n_slices=n, min_slices=mins,
            ),
            ckpt_probe=probe,
        )
        if not r.get("state"):
            raise DrillError(
                f"submit {job_id} rejected: {r.get('reason')}"
            )
        return r

    try:
        # 1. Low-priority research job on 3 of 4 slices.
        r_low = submit(
            "low", "research", 1, 3, mins=1,
            probe=tracker_ckpt_probe(low_ckpt),
        )
        wait_for(
            lambda: status("low").state == PoolJobState.PLACED,
            10, "low placed",
        )
        wait_for(
            lambda: len(states["low"].processed) >= 5,
            20, "low making shard progress",
        )

        # 2. High-priority gang of 4: must preempt low gracefully.
        r_high = submit("high", "prod", 5, 4)
        wait_for(
            lambda: status("high").state == PoolJobState.PLACED,
            30, "high gang placed after preemption",
        )
        st_low = status("low")
        if st_low.state not in (
            PoolJobState.PREEMPTED, PoolJobState.PLACED
        ):
            raise DrillError(
                f"low in unexpected state {st_low.state!r} after "
                "preemption"
            )
        if not states["low"].parked_steps:
            raise DrillError(
                "low parked without writing a flash checkpoint"
            )

        # Trace: park(staged) -> release -> place, one incident trace.
        tq = client.query_traces(trace_id=r_high["trace_id"])
        if not tq.enabled or not tq.traces:
            raise DrillError("high's incident trace missing")
        spans = tq.traces[0]["spans"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        for needle in (
            "pool.submit", "pool.queue_wait", "pool.park",
            "pool.release", "pool.place",
        ):
            if needle not in by_name:
                raise DrillError(
                    f"incident trace missing {needle!r}: has "
                    f"{sorted(by_name)}"
                )
        park = by_name["pool.park"][0]
        if park["tags"].get("staged") is not True:
            raise DrillError(
                f"park span not staged: {park['tags']}"
            )
        if int(park["tags"].get("ckpt_step", -1)) < 0:
            raise DrillError(
                f"park span carries no checkpoint step: "
                f"{park['tags']}"
            )
        release = by_name["pool.release"][0]
        park_end = park["start_ts"] + park["dur_s"]
        if release["start_ts"] < park_end - 1e-6:
            raise DrillError(
                "slices released BEFORE the checkpoint was staged: "
                f"release at {release['start_ts']}, park ended "
                f"{park_end}"
            )
        place_high = [
            s for s in by_name["pool.place"]
            if s["tags"].get("job_id") == "high"
        ]
        if not place_high:
            raise DrillError("no pool.place span for high")
        if place_high[0]["start_ts"] < release["start_ts"] - 1e-6:
            raise DrillError(
                "high placed before the victim's slices were "
                "released"
            )
        granted = place_high[0]["tags"].get("slices", "")
        if len(granted.split(",")) != 4:
            raise DrillError(
                f"high gang not whole: placed on {granted!r}"
            )
        # Never-partial: every pool.allocate event for high grants
        # the full gang (the allocator is all-or-nothing; this
        # asserts it stayed that way end to end).
        events, _ = tracer.events_since(0)
        high_allocs = [
            e for e in events
            if e.get("name") == "pool.allocate"
            and e.get("job_id") == "high"
        ]
        if len(high_allocs) != 1 or len(
            high_allocs[0].get("slices", "").split(",")
        ) != 4:
            raise DrillError(
                f"partial/duplicate allocation for high: "
                f"{high_allocs}"
            )
        # The checkpoint really is durable on disk.
        from dlrover_tpu.common.constants import CheckpointConstant

        tracker = os.path.join(
            low_ckpt, CheckpointConstant.TRACKER_FILE
        )
        if not os.path.exists(tracker):
            raise DrillError("no durable checkpoint tracker file")

        # 3. While high runs: med queues (no capacity, cannot
        # preempt a higher band); overq (research) queues too.
        submit("med", "prod", 3, 2)
        submit("overq", "research", 3, 3)
        for jid in ("med", "overq"):
            if status(jid).state != PoolJobState.QUEUED:
                raise DrillError(
                    f"{jid} should queue while high holds the pool "
                    f"(got {status(jid).state!r})"
                )

        # 4. High completes -> med places; low resumes ELASTICALLY
        # via backfill under the capacity-blocked overq head.
        wait_for(
            lambda: status("high").state == PoolJobState.DONE,
            30, "high completing",
        )
        wait_for(
            lambda: status("med").state in (
                PoolJobState.PLACED, PoolJobState.DONE
            ),
            10, "med placed after high",
        )
        wait_for(
            lambda: status("low").state in (
                PoolJobState.PLACED, PoolJobState.DONE
            ),
            10, "low resumed",
        )
        st_low = status("low")
        if st_low.preemptions != 1:
            raise DrillError(
                f"low preemptions {st_low.preemptions} != 1"
            )
        if st_low.state == PoolJobState.PLACED and len(
            st_low.slices
        ) >= 3:
            raise DrillError(
                f"low resume was not elastic: {st_low.slices}"
            )
        if not states["low"].restored_steps:
            raise DrillError(
                "resumed low never read its checkpoint back"
            )
        # The resume rides the SAME incident trace (pool.resume).
        tq = client.query_traces(trace_id=r_high["trace_id"])
        names = {s["name"] for s in tq.traces[0]["spans"]}
        if "pool.resume" not in names:
            raise DrillError(
                f"incident trace has no pool.resume: {sorted(names)}"
            )
        # ... and the victim is a queryable subject of it.
        subj = client.query_traces(subject="pooljob:low").traces
        if r_high["trace_id"] not in {
            t["trace_id"] for t in subj
        }:
            raise DrillError(
                "subject query pooljob:low does not surface the "
                "incident trace"
            )

        # 5. Quota: with low (research) resumed on 2 slices of the
        # 3-slice research quota, overq's 3-slice ask is quota-denied
        # and must keep queueing WITHOUT starving anyone.
        wait_for(
            lambda: (
                client.query_pool().snapshot["counters"][
                    "quota_denied"
                ].get("research", 0) >= 1
            ),
            20, "overq quota-denied verdict",
        )
        if status("overq").state != PoolJobState.QUEUED:
            raise DrillError("overq should still be queued")

        # 6. Everything drains: med, low, then overq (quota frees).
        for jid in ("med", "low", "overq"):
            wait_for(
                lambda j=jid: status(j).state == PoolJobState.DONE,
                40, f"{jid} completing",
            )

        # 7. Exactly-once shard accounting for the preempted job:
        # every ledger task completed exactly once across BOTH
        # incarnations; none lost, none double-counted.
        low = states["low"]
        with low.lock:
            processed = dict(low.processed)
            records = low.records
            records_at_park = low.records_at_park
        expect_tasks = set(range(low.dataset_size))
        got_tasks = set(processed)
        if got_tasks != expect_tasks:
            raise DrillError(
                f"lost shards: {sorted(expect_tasks - got_tasks)}; "
                f"unknown: {sorted(got_tasks - expect_tasks)}"
            )
        doubles = {t: c for t, c in processed.items() if c != 1}
        if doubles:
            raise DrillError(
                f"double-counted shards: {doubles}"
            )
        if records != low.dataset_size:
            raise DrillError(
                f"record count {records} != dataset "
                f"{low.dataset_size}"
            )
        if not 0 < records_at_park < low.dataset_size:
            raise DrillError(
                f"park did not interrupt the dataset "
                f"(records_at_park={records_at_park})"
            )
        ctx = master.context("low")
        if not ctx.master.task_manager.finished():
            raise DrillError("low's master ledger not finished")

        # 8. Metrics + snapshot exposure.
        snap = client.query_pool().snapshot
        counters = snap["counters"]
        if counters["preemptions"].get("priority", 0) != 1:
            raise DrillError(
                f"preemption counters wrong: {counters}"
            )
        if counters["backfills"] < 1:
            raise DrillError("elastic resume did not backfill")
        if not snap["wait_seconds"]:
            raise DrillError("no wait-time percentiles in snapshot")
        text = client.query_metrics()
        for needle in (
            "dlrover_pool_queue_depth",
            "dlrover_pool_preemptions_total",
            "dlrover_pool_placement_seconds",
            "dlrover_pool_quota_denied_total",
        ):
            if needle not in text:
                raise DrillError(
                    f"{needle} missing from /metrics exposition"
                )

        waits = snap["wait_seconds"]
        return {
            "seed": seed,
            "jobs": 4,
            "preemptions": counters["preemptions"],
            "quota_denied": counters["quota_denied"],
            "backfills": counters["backfills"],
            "placements": counters["placements"],
            "low_tasks": len(processed),
            "low_records_at_park": records_at_park,
            "low_parked_step": max(low.parked_steps),
            "low_resume_slices": len(st_low.slices),
            "incident_trace": r_high["trace_id"],
            "wait_p99_by_band": {
                band: w["p99"] for band, w in waits.items()
            },
            "wall_s": round(time.monotonic() - t0, 1),
        }
    finally:
        client.close()
        master.stop()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def selftest() -> int:
    t0 = time.monotonic()
    report = run_pool_drill(seed=7)
    print(
        f"pool drill ok: high gang placed whole after graceful "
        f"preemption (ckpt step {report['low_parked_step']} staged "
        f"before release), low resumed elastically on "
        f"{report['low_resume_slices']} slice(s) with "
        f"{report['low_tasks']} shards exactly-once, "
        f"quota_denied={report['quota_denied']}"
    )
    print(
        f"pool drill selftest ok ({time.monotonic() - t0:.1f}s)"
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("pool_drill")
    parser.add_argument("--selftest", action="store_true",
                        help="seeded quick mode (<60s) for CI")
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--shard_ms", type=float, default=20.0)
    parser.add_argument("--json", type=str, default="",
                        help="write the drill report to this path")
    args = parser.parse_args(argv)
    if args.selftest:
        return selftest()
    try:
        report = run_pool_drill(
            seed=args.seed, shard_ms=args.shard_ms
        )
        report["ok"] = True
        rc = 0
    except DrillError as e:
        report = {"ok": False, "error": str(e)}
        rc = 1
    print(json.dumps(report))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
    return rc


if __name__ == "__main__":
    sys.exit(main())
