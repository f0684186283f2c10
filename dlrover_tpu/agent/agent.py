"""Per-host elastic agent: supervises the training process.

Parity: dlrover/python/elastic_agent/torch/training.py (
MasterRendezvousHandler :132, ElasticTrainingAgent :313, launch_agent
:642), redesigned for the JAX process model: ONE training process per
host owns all local TPU chips (instead of torchelastic's
one-process-per-GPU), and world bootstrap hands the process
``jax.distributed.initialize`` coordinates (coordinator addr, process
id, process count) via env vars instead of a c10d TCPStore.

Restart semantics are the reference's: on membership change or process
failure the agent kills and respawns the *training process* while the
agent itself stays up, which is exactly the teardown/re-init JAX needs
since its distributed world is static per initialization.
"""

from __future__ import annotations

import collections
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

from dlrover_tpu import obs
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.common.comm import find_free_port
from dlrover_tpu.common.config import (
    ensure_framework_on_pythonpath,
    tmp_path,
)
from dlrover_tpu.common.constants import (
    EventAction,
    NodeAction,
    NodeEnv,
    NodeType,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import get_logger

logger = get_logger("agent")

_HEARTBEAT_FAILURES = obs.counter(
    "dlrover_agent_heartbeat_failures_total",
    "Agent->master heartbeat RPC failures (consecutive streaks are "
    "logged once per power-of-two length, not per tick)",
)


class RendezvousTimeoutError(RuntimeError):
    pass


class MasterRendezvousHandler:
    """Agent-side rendezvous: join, poll for the frozen world, compute
    this node's rank and the JAX bootstrap coordinates."""

    def __init__(
        self,
        client: MasterClient,
        local_world_size: int,
        rdzv_name: str = RendezvousName.TRAINING,
        timeout: float = 600.0,
        poll_interval: float = 0.3,
    ):
        self.client = client
        self.local_world_size = local_world_size
        self.rdzv_name = rdzv_name
        self.timeout = timeout
        self.poll_interval = poll_interval

    def next_rendezvous(self) -> "WorldSpec":
        round_ = self.client.join_rendezvous(
            self.local_world_size, rdzv_name=self.rdzv_name
        )
        deadline = time.monotonic() + self.timeout
        while time.monotonic() < deadline:
            rdzv_round, group, world = self.client.get_comm_world(
                rdzv_name=self.rdzv_name
            )
            if world and self.client.node_rank in world:
                return self._build_spec(rdzv_round, group, world)
            if world and self.client.node_rank not in world:
                # Frozen without us (e.g. node_unit rounding): rejoin.
                round_ = self.client.join_rendezvous(
                    self.local_world_size, rdzv_name=self.rdzv_name
                )
            time.sleep(self.poll_interval)
        raise RendezvousTimeoutError(
            f"{self.rdzv_name} rendezvous not completed in {self.timeout}s "
            f"(joined round {round_})"
        )

    def _build_spec(
        self, rdzv_round: int, group: int, world: Dict[int, int]
    ) -> "WorldSpec":
        ranks = sorted(world.keys())
        my_rank = ranks.index(self.client.node_rank)
        # Process ids: one training process per node; process_id equals
        # the node's position; chips-per-host is the local world size.
        spec = WorldSpec(
            round=rdzv_round,
            group=group,
            world=world,
            node_world_size=len(ranks),
            node_rank=my_rank,
            process_id=my_rank,
            num_processes=len(ranks),
        )
        # Rank-0 of the world publishes the coordinator endpoint.
        kv_key = f"coordinator/{self.rdzv_name}/{rdzv_round}/{group}"
        if my_rank == 0:
            host = os.getenv("DLROVER_TPU_HOST_IP", "127.0.0.1")
            port = find_free_port()
            spec.coordinator = f"{host}:{port}"
            self.client.kv_set(kv_key, spec.coordinator.encode())
        else:
            spec.coordinator = self.client.kv_wait(
                kv_key, timeout=self.timeout
            ).decode()
        return spec


@dataclass
class WorldSpec:
    round: int
    group: int
    world: Dict[int, int]
    node_world_size: int
    node_rank: int
    process_id: int
    num_processes: int
    coordinator: str = ""


@dataclass
class AgentConfig:
    node_id: int = 0
    node_rank: int = -1
    # Role this agent's node plays (NodeType): "worker" nodes join the
    # elastic rendezvous; an "evaluator" runs its command standalone
    # (it follows checkpoints, not the training world) while the
    # master still owns its lifecycle (critical role, relaunch).
    node_type: str = "worker"
    local_world_size: int = 1
    max_restarts: int = 3
    monitor_interval: float = 2.0
    rdzv_timeout: float = 600.0
    network_check: bool = False
    # With network_check: a node the master judges a straggler (>2x
    # median check time) exits instead of joining training, so the
    # scaler replaces it (ref dlrover-run --exclude-straggler,
    # trainer/torch/elastic_run.py:99-137).
    exclude_straggler: bool = False
    heartbeat_interval: float = 15.0
    # >0 enables hang detection: restart the training process when no
    # step progress for this many seconds (ref: atorch
    # --relaunch_on_hanging, fault_tolerance/custom_agent.py:19).
    hang_timeout: float = 0.0
    env: Dict[str, str] = field(default_factory=dict)


class ElasticAgent:
    """Supervises one training process through restarts and membership
    changes."""

    def __init__(
        self,
        config: AgentConfig,
        entry_cmd: List[str],
        client: Optional[MasterClient] = None,
    ):
        self.config = config
        self.entry_cmd = entry_cmd
        self.client = client or MasterClient.singleton()
        self._rdzv = MasterRendezvousHandler(
            self.client,
            config.local_world_size,
            timeout=config.rdzv_timeout,
        )
        self._proc: Optional[subprocess.Popen] = None
        # Tail of the child's stderr, kept so failure reports carry the
        # actual error text (OOM / RESOURCE_EXHAUSTED / preemption) the
        # master's classifier keys on (ref: error log monitor).
        self._stderr_tail: Deque[bytes] = collections.deque(maxlen=50)
        self._stderr_thread: Optional[threading.Thread] = None
        self._tail_lock = threading.Lock()
        self._restart_count = 0
        self._stop = threading.Event()
        self._spec: Optional[WorldSpec] = None
        self._ckpt_saver = None
        # Set by the heartbeat thread; acted on ONLY by the monitor
        # loop so process lifecycle has a single owner (no concurrent
        # kill/spawn races).
        self._restart_requested = threading.Event()
        # Set by the master's `cordon` heartbeat action (remediation):
        # the agent parks its trainer and sits out rendezvous while
        # still heartbeating; RESTART_TRAINING un-cordons.
        self._cordon_requested = threading.Event()
        # In-flight PROFILE capture worker (one at a time).
        self._profile_thread: Optional[threading.Thread] = None

    # -- process management -------------------------------------------------

    def _spawn(self, spec: WorldSpec) -> None:
        # Remove the previous incarnation's step-metrics file: the
        # hang detector and training monitor must not baseline on a
        # stale step (a resume can legitimately restart at a LOWER
        # step, which a stale high-water mark would misread as a hang
        # / silence).
        from dlrover_tpu.agent.monitor import (
            default_metrics_file,
            METRICS_FILE_ENV,
        )

        try:
            os.remove(os.getenv(METRICS_FILE_ENV, default_metrics_file()))
        except OSError:
            pass
        env = ensure_framework_on_pythonpath(dict(os.environ))
        env.update(self.config.env)
        env.update(
            {
                "DLROVER_TPU_AGENT_PRESENT": "1",
                NodeEnv.NODE_ID: str(self.config.node_id),
                NodeEnv.NODE_RANK: str(spec.node_rank),
                NodeEnv.NODE_NUM: str(spec.node_world_size),
                NodeEnv.LOCAL_WORLD_SIZE: str(
                    self.config.local_world_size
                ),
                NodeEnv.COORDINATOR_ADDR: spec.coordinator,
                NodeEnv.PROCESS_ID: str(spec.process_id),
                NodeEnv.NUM_PROCESSES: str(spec.num_processes),
                NodeEnv.RESTART_COUNT: str(self._restart_count),
                NodeEnv.MASTER_ADDR: self.client._client.addr,
            }
        )
        logger.info(
            "spawning training process (round=%d rank=%d/%d restart=%d): %s",
            spec.round,
            spec.node_rank,
            spec.node_world_size,
            self._restart_count,
            " ".join(self.entry_cmd),
        )
        # Fresh deque per incarnation: if a previous pump thread out-
        # lives its 3s join (a grandchild kept the pipe open), it keeps
        # appending to the *old* deque and cannot pollute this
        # incarnation's tail or race its readers.
        with self._tail_lock:
            self._stderr_tail = collections.deque(maxlen=50)
        self._proc = subprocess.Popen(
            self.entry_cmd, env=env, stderr=subprocess.PIPE
        )
        # worker_pid, not pid: that is every record's own process tag.
        obs.event(
            "agent.worker_spawned",
            worker_pid=self._proc.pid,
            restart_count=self._restart_count,
        )
        TrainingMonitor.mark_phase("agent.spawned")
        self._stderr_thread = threading.Thread(
            target=self._pump_stderr,
            args=(self._proc.stderr, self._stderr_tail),
            daemon=True,
        )
        self._stderr_thread.start()

    def _pump_stderr(self, pipe, tail: Deque[bytes]) -> None:
        """Forward the child's stderr while keeping the last lines.

        ``tail`` is this incarnation's deque, bound at spawn time."""
        try:
            for line in iter(pipe.readline, b""):
                with self._tail_lock:
                    tail.append(line)
                try:
                    sys.stderr.buffer.write(line)
                    sys.stderr.buffer.flush()
                except (AttributeError, ValueError, OSError):
                    # stderr replaced by a text-only capture (pytest) or
                    # closed: keep the tail, drop the passthrough.
                    pass
        finally:
            pipe.close()

    def _stderr_text(self, limit: int = 2048) -> str:
        with self._tail_lock:
            lines = list(self._stderr_tail)
        text = b"".join(lines).decode("utf-8", "replace")
        return text[-limit:]

    def _kill_proc(self, grace: float = 10.0) -> None:
        if self._proc is None or self._proc.poll() is not None:
            self._join_stderr_pump()
            return
        self._proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + grace
        while time.monotonic() < deadline:
            if self._proc.poll() is not None:
                self._join_stderr_pump()
                return
            time.sleep(0.2)
        self._proc.kill()
        self._proc.wait()
        self._join_stderr_pump()

    def _join_stderr_pump(self) -> None:
        """Drain the old incarnation's pump thread so its buffered
        stderr cannot leak into the next incarnation's tail."""
        if (
            self._stderr_thread is not None
            and self._stderr_thread is not threading.current_thread()
        ):
            self._stderr_thread.join(timeout=3.0)
        self._stderr_thread = None

    # -- forensics ----------------------------------------------------------

    def _snapshot_trainer_stacks(self, timeout: float = 3.0) -> str:
        """The training process's Python stacks, as text.

        Alive process: SIGUSR1 triggers its flight recorder's
        C-level faulthandler dump (registered at install; works even
        with the main thread wedged in a C call) and the growth of
        its stacks file is returned. Dead process: the tail the crash
        handlers already left behind."""
        from dlrover_tpu.obs import flight_recorder as fr

        proc = self._proc
        if proc is None:
            return ""
        path = fr.stacks_file_path(proc.pid)
        try:
            before = os.path.getsize(path)
        except OSError:
            before = 0
        if proc.poll() is not None:
            return fr.read_stacks_tail(
                path, since=max(before - 8192, 0)
            )
        if not hasattr(signal, "SIGUSR1"):
            return ""
        if not fr.sigusr1_ready(proc.pid):
            # No registered handler (recorder disabled, still
            # importing, or registration failed): the default
            # disposition would KILL the process we are trying to
            # diagnose. No signal, no stacks.
            return ""
        try:
            proc.send_signal(signal.SIGUSR1)
        except OSError:
            return ""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                if os.path.getsize(path) > before:
                    # Give the C handler a beat to finish the dump.
                    time.sleep(0.2)
                    break
            except OSError:
                pass
            time.sleep(0.1)
        return fr.read_stacks_tail(path, since=before)

    def _collect_forensics(self, kind: str, **notes):
        """(digest, bundle_path): snapshot the training process's
        stacks, write this agent's black-box bundle (with the trainer
        stacks embedded), and build the size-capped digest failure
        reports and the master's history carry. Never raises."""
        from dlrover_tpu.obs import flight_recorder as fr

        stacks = ""
        try:
            stacks = self._snapshot_trainer_stacks()
        except Exception:  # noqa: BLE001 — forensics must never
            # break the recovery path it documents
            logger.warning(
                "trainer stack snapshot failed", exc_info=True
            )
        rec = fr.get_flight_recorder()
        bundle_path = ""
        if rec is not None:
            # Incident facts ride THIS bundle only — merging them
            # into the recorder's persistent notes would make every
            # later diagnose/crash digest replay a stale hang.
            bundle_path = (
                rec.dump(
                    kind,
                    reason=f"agent {kind} forensics",
                    extra={"trainer_stacks": stacks},
                    incident=notes,
                )
                or ""
            )
        digest = fr.make_digest(
            kind, stacks_text=stacks, recorder=rec, incident=notes
        )
        if bundle_path:
            digest = f"bundle: {bundle_path}\n{digest}"
        return digest, bundle_path

    def _run_diagnose(self) -> None:
        """Master-pushed `diagnose` action: on-demand stack-and-state
        snapshot, shipped back as a DiagnosticsReport."""
        digest, bundle_path = self._collect_forensics("diagnose")
        self.client.report_diagnostics(
            "diagnose", bundle_path=bundle_path, digest=digest
        )

    def _on_stale_beacon(self, stamp: dict) -> None:
        """ResourceMonitor found the trainer's progress beacon wedged
        (no stamp for DLROVER_TPU_BEACON_STALL_S): capture forensics
        while the wedge is live — the SIGUSR1 stack snapshot shows
        exactly which collective the trainer is parked in — and ship
        them as a kind-``stall`` DiagnosticsReport. The master-side
        correlator does the cross-host localization; this capture is
        the host-local half of the evidence."""
        digest, bundle_path = self._collect_forensics(
            "stall",
            beacon_step=stamp.get("step"),
            beacon_microbatch=stamp.get("microbatch"),
            beacon_phase=stamp.get("phase"),
            beacon_age_s=stamp.get("age_s"),
        )
        self.client.report_diagnostics(
            "stall", bundle_path=bundle_path, digest=digest
        )

    def _run_profile(self) -> None:
        """Master-pushed `profile` action: ask the co-hosted trainer
        for an N-step step-phase/MFU capture and ship the digest back
        as a DiagnosticsReport(kind="profile").

        Runs in its own daemon thread: the capture spans N training
        steps (seconds to minutes), and the heartbeat loop must keep
        beating while the trainer gets there. One capture at a time —
        a second PROFILE while one is in flight is dropped (the
        running capture's digest answers it)."""
        if (
            self._profile_thread is not None
            and self._profile_thread.is_alive()
        ):
            logger.info("profile capture already in flight; skipping")
            return
        self._profile_thread = threading.Thread(
            target=self._profile_worker,
            name="profile-capture",
            daemon=True,
        )
        self._profile_thread.start()

    def _profile_worker(self) -> None:
        try:
            self._profile_worker_inner()
        except Exception:  # noqa: BLE001 — a failed capture must
            # neither kill the agent nor masquerade as a crash (an
            # uncaught thread exception would write a forensics
            # bundle via threading.excepthook)
            logger.warning("profile capture failed", exc_info=True)

    def _profile_worker_inner(self) -> None:
        import json as _json

        from dlrover_tpu.obs import profiling

        req_id = profiling.write_profile_request()
        wait_s = float(os.getenv("DLROVER_TPU_PROFILE_WAIT_S", "120"))
        deadline = time.monotonic() + wait_s
        digest = None
        while time.monotonic() < deadline:
            digest = profiling.read_profile_digest(expect_id=req_id)
            if digest is not None:
                break
            time.sleep(0.25)
        if digest is None:
            # The answer is itself diagnostic: no digest within the
            # wait usually means no live trainer loop (hung, between
            # restarts, or a loop without a step-phase profiler).
            self.client.report_diagnostics(
                "profile",
                digest=_json.dumps(
                    {
                        "id": req_id,
                        "error": f"no profile digest within {wait_s:.0f}s"
                        " (trainer not stepping, or its loop has no"
                        " StepPhaseProfiler)",
                    }
                ),
            )
            return
        self.client.report_diagnostics(
            "profile",
            bundle_path=profiling.profile_digest_file(),
            digest=_json.dumps(digest, indent=1, sort_keys=True),
        )

    # -- health check -------------------------------------------------------

    def run_network_check(self) -> bool:
        """Run the psum/matmul benchmark payload in a throwaway process
        group and report the result (ref: NetworkCheckElasticAgent)."""
        handler = MasterRendezvousHandler(
            self.client,
            self.config.local_world_size,
            rdzv_name=RendezvousName.NETWORK_CHECK,
            timeout=self.config.rdzv_timeout,
        )
        for _ in range(2):  # two grouping rounds localize the fault
            spec = handler.next_rendezvous()
            start = time.monotonic()
            result = subprocess.run(
                [
                    sys.executable,
                    "-m",
                    "dlrover_tpu.trainer.network_check",
                ],
                env={
                    **ensure_framework_on_pythonpath(dict(os.environ)),
                    NodeEnv.COORDINATOR_ADDR: spec.coordinator,
                    NodeEnv.PROCESS_ID: str(spec.process_id),
                    NodeEnv.NUM_PROCESSES: str(spec.num_processes),
                },
                timeout=300,
                check=False,
            )
            elapsed = time.monotonic() - start
            normal = result.returncode == 0
            self.client.report_network_check(normal, elapsed)
        return self.network_check_verdict()

    def network_check_verdict(self) -> bool:
        """Consume the master's fault + straggler verdicts for this
        node after check results were reported. Split from
        run_network_check so the decision (incl. --exclude-straggler)
        is testable without live rendezvous timing."""
        deadline = time.monotonic() + self.config.rdzv_timeout
        faults, reason = self.client.query_fault_nodes()
        while reason == "waiting":
            if time.monotonic() > deadline:
                logger.error(
                    "network-check verdict not available within %ss "
                    "(peers never reported); treating as failure",
                    self.config.rdzv_timeout,
                )
                return False
            time.sleep(1.0)
            faults, reason = self.client.query_fault_nodes()
        if self.client.node_rank in faults:
            logger.error("this node FAILED the network check")
            return False
        try:
            stragglers, _ = self.client.query_stragglers()
        except Exception:  # noqa: BLE001 — a transient RPC failure
            # must not kill a healthy node over an advisory check
            logger.warning(
                "straggler query failed; assuming not a straggler",
                exc_info=True,
            )
            stragglers = []
        if self.client.node_rank in stragglers:
            if self.config.exclude_straggler:
                logger.error(
                    "this node is a STRAGGLER (>2x median check "
                    "time) and --exclude-straggler is set; exiting "
                    "so it gets replaced"
                )
                return False
            logger.warning(
                "this node is a STRAGGLER (>2x median check time); "
                "continuing (pass --exclude-straggler to exit "
                "instead)"
            )
        return True

    # -- main loop ----------------------------------------------------------

    def run(self) -> int:
        self.client.register_node(node_type=self.config.node_type)
        # The network check is a training-world rendezvous sized to the
        # worker fleet — an evaluator joining it would freeze a wrong-
        # sized world and skew the straggler median, so only workers
        # run it.
        is_evaluator = self.config.node_type == NodeType.EVALUATOR
        if (
            not is_evaluator
            and self.config.network_check
            and not self.run_network_check()
        ):
            self.client.report_failure(
                "network check failed",
                TrainingExceptionLevel.NODE_ERROR,
            )
            return 1
        heartbeat = threading.Thread(
            target=self._heartbeat_loop, daemon=True
        )
        heartbeat.start()
        # Telemetry to the master: node resources + training progress
        # (ref elastic_agent/monitor/{resource,training}.py).
        from dlrover_tpu.agent.monitor import (
            ResourceMonitor,
            TrainingMonitor,
        )
        from dlrover_tpu.agent.paral_config_tuner import ParalConfigTuner

        res_mon = ResourceMonitor(
            self.client, on_stale_beacon=self._on_stale_beacon
        )
        train_mon = TrainingMonitor(self.client)
        tuner = ParalConfigTuner(self.client)
        # After a master reconnect (possibly to a warm-restarted
        # replacement), resend a full telemetry snapshot immediately:
        # the new master's fleet view re-primes now, not a reporting
        # cadence later. (Registration itself is already resent by
        # the client's supervisor.)
        self.client.add_reconnect_callback(res_mon.report_once)
        res_mon.start()
        train_mon.start()
        tuner.start()
        try:
            result = self._invoke_run()
        finally:
            res_mon.stop()
            train_mon.stop()
            tuner.stop()
            self._stop.set()
        return result

    def _ensure_ckpt_saver(self, spec: WorldSpec) -> None:
        """Start/refresh the agent-hosted flash-checkpoint saver (ref:
        saver started at _invoke_run, elastic_agent/torch/
        training.py:509; agent ownership means a crashed trainer's shm
        still gets flushed). World facts refresh on every rendezvous."""
        import os as _os

        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        default_dir = tmp_path(
            f"dlrover_tpu_ckpt_{_os.getenv('DLROVER_TPU_JOB_NAME', 'job')}"
        )
        saver = AsyncCheckpointSaver.start_async_saving_ckpt(
            checkpoint_dir=default_dir,
            local_shard_num=1,
            global_shard_num=max(spec.num_processes, 1),
            is_commit_owner=spec.node_rank == 0,
        )
        saver.global_shard_num = max(spec.num_processes, 1)
        saver.is_commit_owner = spec.node_rank == 0
        if self._ckpt_saver is None:
            saver.register_signal_handler()
        self._ckpt_saver = saver

    def _flush_ckpt_shm(self) -> None:
        """Persist any staged-but-unpersisted checkpoint before a
        restart (ref: _save_ckpt_to_storage, training.py:572)."""
        if self._ckpt_saver is not None:
            try:
                self._ckpt_saver.save_shm_to_storage()
            except Exception:  # noqa: BLE001
                logger.warning(
                    "pre-restart checkpoint flush failed", exc_info=True
                )

    def _standalone_spec(self) -> WorldSpec:
        """World of one for roles outside the training rendezvous
        (evaluator): the process runs alone, keyed by this node."""
        return WorldSpec(
            round=0,
            group=0,
            world={self.config.node_id: self.config.local_world_size},
            node_world_size=1,
            node_rank=0,
            process_id=0,
            num_processes=self.config.local_world_size,
        )

    def _invoke_run(self) -> int:
        from dlrover_tpu.agent.hang_detector import HangDetector

        hang = (
            HangDetector(hang_timeout=self.config.hang_timeout)
            if self.config.hang_timeout > 0
            else None
        )
        if self.config.node_type == NodeType.EVALUATOR:
            # Evaluators run outside the training world: no rendezvous
            # join (which would block or distort the worker world), a
            # world of one; master-side lifecycle still applies.
            self._spec = self._standalone_spec()
        else:
            self._spec = self._rdzv.next_rendezvous()
        self._ensure_ckpt_saver(self._spec)
        self._spawn(self._spec)
        while not self._stop.is_set():
            time.sleep(self.config.monitor_interval)
            if self._cordon_requested.is_set():
                # Cordoned by the master's remediation engine: stop
                # the trainer (it would otherwise wedge the fleet's
                # collectives), skip rendezvous/membership handling so
                # this node sits OUT of the next world, keep
                # heartbeating so the master can un-cordon (rollback)
                # or retire us. A pending restart request stays set —
                # it fires the moment the cordon clears.
                if self._proc is not None and self._proc.poll() is None:
                    logger.warning(
                        "cordoned by master; stopping training "
                        "process and sitting out rendezvous"
                    )
                    obs.event(
                        "agent.cordoned", node_id=self.config.node_id
                    )
                    self._flush_ckpt_shm()
                    self._kill_proc()
                self._proc = None
                if hang is not None:
                    hang.reset()
                continue
            if hang is not None and hang.check():
                exhausted = (
                    self._restart_count >= self.config.max_restarts
                )
                logger.error(
                    "training process hung (%.0fs without step "
                    "progress); %s",
                    hang.seconds_since_progress(),
                    "giving up" if exhausted else "restarting it",
                )
                # Forensics BEFORE any kill/restart: the SIGUSR1 stack
                # snapshot needs the hung process still alive, and the
                # digest must ride the failure report so the hang is
                # diagnosable, not just counted.
                digest, bundle_path = self._collect_forensics(
                    "hang",
                    hang_seconds=round(
                        hang.seconds_since_progress(), 1
                    ),
                    last_step=hang.last_step,
                )
                action = NodeAction.RESTART_IN_PLACE
                try:
                    action = self.client.report_failure(
                        "training process hanging",
                        TrainingExceptionLevel.PROCESS_ERROR,
                        restart_count=self._restart_count,
                        fatal=exhausted,
                        diagnostics=digest,
                    )
                except Exception:  # noqa: BLE001
                    logger.warning("could not report hang", exc_info=True)
                self.client.report_diagnostics(
                    "hang", bundle_path=bundle_path, digest=digest
                )
                if exhausted:
                    self._kill_proc()  # a hung proc still holds chips
                    return 1
                if action != NodeAction.RESTART_IN_PLACE:
                    # Master took ownership (node relaunch/stop): same
                    # handover as _handle_failure.
                    logger.info(
                        "master verdict %r on hang; agent stops "
                        "supervising", action,
                    )
                    self._kill_proc()
                    return 1
                self._restart_count += 1
                self._restart_workers(reason="hang")
                hang.reset()
                continue
            code = self._proc.poll() if self._proc else None
            if code is not None:
                obs.event(
                    "agent.worker_exit_seen",
                    worker_pid=self._proc.pid,
                    returncode=code,
                )
                if code == 0:
                    logger.info("training process finished successfully")
                    try:
                        self.client.report_succeeded()
                    except Exception:  # noqa: BLE001
                        logger.warning(
                            "could not report success to master",
                            exc_info=True,
                        )
                    return 0
                # A failure opens a relaunch: the agent's marks of the
                # last one go, and this one's start here. A clean exit
                # ends the job and leaves them for whoever reads next.
                TrainingMonitor.mark_phase("agent.exit_seen")
                if not self._handle_failure(code):
                    return code
                continue
            if self._restart_requested.is_set():
                self._restart_requested.clear()
                logger.info("master requested restart")
                self._restart_workers(reason="master_request")
            elif self._membership_changed():
                logger.info(
                    "membership changed; restarting training process "
                    "for re-rendezvous"
                )
                self._restart_workers()
        self._kill_proc()
        return 0

    def _handle_failure(self, exitcode: int) -> bool:
        """Report and decide restart. True = keep running."""
        self._join_stderr_pump()
        exhausted = self._restart_count >= self.config.max_restarts
        error_data = (
            f"training process exit code {exitcode}\n"
            + self._stderr_text()
        )
        # The dead trainer's crash hooks (excepthook bundle /
        # faulthandler stacks) already wrote to the forensics dir;
        # fold their tail + this agent's black box into a digest. It
        # rides the failure report's `diagnostics` field, NOT
        # error_data: stack frames must not perturb the master's
        # stderr keyword classifier (a frame through
        # preemption_drill.py is not a preemption).
        digest, bundle_path = self._collect_forensics(
            "crash", exit_code=exitcode
        )
        action = NodeAction.RESTART_IN_PLACE
        try:
            action = self.client.report_failure(
                error_data,
                TrainingExceptionLevel.PROCESS_ERROR,
                restart_count=self._restart_count,
                fatal=exhausted,
                diagnostics=digest,
            )
        except Exception:  # noqa: BLE001
            # An unreachable master must not take the agent down with
            # it — restarts are still locally meaningful.
            logger.warning(
                "could not report failure to master", exc_info=True
            )
        self.client.report_diagnostics(
            "crash", bundle_path=bundle_path, digest=digest
        )
        if exhausted:
            logger.error(
                "exhausted %d restarts; giving up", self.config.max_restarts
            )
            return False
        if action != NodeAction.RESTART_IN_PLACE:
            # The master took ownership (node relaunch or stop): this
            # agent must not also restart the process in place.
            logger.info(
                "master verdict %r; agent stops supervising", action
            )
            return False
        self._restart_count += 1
        self._restart_workers(reason="process_exit")
        return True

    def _restart_workers(self, reason: str = "membership") -> None:
        from dlrover_tpu import obs

        obs.event(
            "agent.worker_restart",
            reason=reason,
            restart_count=self._restart_count,
            node_id=self.config.node_id,
        )
        self._flush_ckpt_shm()
        self._kill_proc()
        self._spec = (
            self._standalone_spec()
            if self.config.node_type == NodeType.EVALUATOR
            else self._rdzv.next_rendezvous()
        )
        self._ensure_ckpt_saver(self._spec)
        self._spawn(self._spec)

    def _membership_changed(self) -> bool:
        # Evaluators are not part of the training world: worker churn
        # must not restart the evaluation loop.
        if self.config.node_type == NodeType.EVALUATOR:
            return False
        return self.client.num_nodes_waiting() > 0

    def _heartbeat_loop(self) -> None:
        streak = 0
        next_warn = 1
        while not self._stop.wait(self.config.heartbeat_interval):
            try:
                action = self.client.heartbeat()
            except Exception:  # noqa: BLE001
                # Repeated failures are counted, and warned once per
                # power-of-two streak length — a master outage must
                # show up in telemetry without a log line per tick.
                streak += 1
                _HEARTBEAT_FAILURES.inc()
                if streak >= next_warn:
                    logger.warning(
                        "heartbeat failed (%d consecutive "
                        "failure%s; next warning at %d)",
                        streak,
                        "" if streak == 1 else "s",
                        next_warn * 2,
                        exc_info=True,
                    )
                    next_warn *= 2
                continue
            if streak:
                logger.info(
                    "heartbeat recovered after %d failure%s",
                    streak, "" if streak == 1 else "s",
                )
                streak = 0
                next_warn = 1
                # The master may be a warm-restarted replacement (or
                # a cold one that lost the node table): re-announce
                # this node and let subscribers resend snapshots.
                try:
                    self.client.notify_master_recovered()
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "post-recovery re-registration failed",
                        exc_info=True,
                    )
            if action == EventAction.RESTART_TRAINING.value:
                if self._cordon_requested.is_set():
                    # restart_training doubles as un-cordon (the
                    # remediation rollback path): clear the cordon
                    # FIRST so the supervision loop acts on the
                    # restart instead of skipping it.
                    self._cordon_requested.clear()
                    logger.info(
                        "master un-cordoned this node; rejoining at "
                        "the next rendezvous"
                    )
                self._restart_requested.set()
            elif action == EventAction.CORDON.value:
                logger.warning(
                    "master cordoned this node (remediation); parking "
                    "the trainer"
                )
                self._cordon_requested.set()
            elif action == EventAction.STOP_TRAINING.value:
                self._stop.set()
            elif action == EventAction.DIAGNOSE.value:
                try:
                    self._run_diagnose()
                except Exception:  # noqa: BLE001 — an on-demand
                    # snapshot must never take the heartbeat down
                    logger.warning("diagnose failed", exc_info=True)
            elif action == EventAction.PROFILE.value:
                try:
                    self._run_profile()
                except Exception:  # noqa: BLE001 — an on-demand
                    # capture must never take the heartbeat down
                    logger.warning("profile failed", exc_info=True)

    def stop(self) -> None:
        self._stop.set()
        self._kill_proc()
