"""Pipelined input prefetch: stage batch N+1 while step N computes.

The steady-state training loop must never wait on the input pipeline:
Python collate AND host->device staging (``jax.device_put`` under the
step's ``NamedSharding`` / ``make_array_from_process_local_data``) for
the NEXT batch should run while XLA executes the CURRENT step.
:class:`Prefetcher` is that overlap: a single background thread pulls
items from a source iterable (typically an ``ElasticDataLoader``),
applies ``stage_fn`` (host-side collate), then ``h2d_fn`` (device
placement — the worker finishes with committed device arrays), and
parks the staged result in a bounded queue — double-buffered by
default — that the train loop pops with near-zero wait.

The two stages are timed separately so the win is *attributable*:
every batch's host cost (source pull + collate) and H2D cost land in
``dlrover_prefetch_stage_seconds_total{phase="host"|"h2d"}``, and the
consumer's wait splits the same way (``wait_breakdown()``), feeding
the ``data_wait`` / ``h2d_stage`` step phases of
``dlrover_step_phase_seconds_total`` (obs/profiling.py).

Elasticity contract: a checkpoint taken mid-stream must not count an
in-flight batch (pulled from the sampler but not yet trained on) as
consumed — whether it is parked host-side or already device-resident.
The worker snapshots ``sampler.state_dict()`` immediately after
pulling each item; :meth:`Prefetcher.sampler_state_dict` returns the
snapshot of the last batch actually DELIVERED to the consumer, so an
elastic restart resumes exactly after the last trained-on batch and
the queued-but-untrained ones are replayed. ``close()`` additionally
frees the device buffers of staged-but-undelivered batches so dropped
HBM slots return immediately instead of waiting for GC.

One knob (see docs/PERFORMANCE.md): ``DLROVER_TPU_PREFETCH_DEPTH`` —
queue depth (staged batches held ahead), default 2.

Observability: every consumer wait lands in the
``dlrover_train_data_wait_seconds`` histogram; with tracing on, the
worker emits ``trainer.prefetch_stage`` (host) and
``trainer.prefetch_h2d`` (device placement) spans per staged batch
and the consumer emits ``trainer.prefetch_wait`` events carrying the
split, so ``tools/obs_report.py`` can show data-wait vs host-staging
vs H2D-staging vs step time.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Iterable, Optional, Tuple

from dlrover_tpu import obs
from dlrover_tpu.common.log import get_logger

logger = get_logger("prefetch")

PREFETCH_DEPTH_ENV = "DLROVER_TPU_PREFETCH_DEPTH"
DEFAULT_DEPTH = 2

_DATA_WAIT = obs.histogram(
    "dlrover_train_data_wait_seconds",
    "Time the train loop waited on the input pipeline per batch "
    "(near zero when prefetch keeps up)",
)
_BATCHES = obs.counter(
    "dlrover_prefetch_batches_total",
    "Prefetcher batches by outcome",
    ("outcome",),  # staged | delivered | dropped
)
_STAGE_SECONDS = obs.counter(
    "dlrover_prefetch_stage_seconds_total",
    "Input staging cost by phase: host (source pull + collate) vs "
    "h2d (device placement), both in the worker",
    ("phase",),  # host | h2d
)


def prefetch_depth(default: int = DEFAULT_DEPTH) -> int:
    try:
        depth = int(os.getenv(PREFETCH_DEPTH_ENV, str(default)))
    except ValueError:
        return default
    return max(1, depth)


def free_device_buffers(batch) -> None:
    """Best-effort eager free of a dropped batch's device buffers.

    Walks tuples/lists/dicts and calls ``.delete()`` on any leaf that
    has one (jax Arrays; duck-typed so this module never imports jax).
    A dropped device-resident batch must hand its HBM slot back at
    close() time, not whenever GC finds the queue entry."""
    if isinstance(batch, (tuple, list)):
        for item in batch:
            free_device_buffers(item)
        return
    if isinstance(batch, dict):
        for item in batch.values():
            free_device_buffers(item)
        return
    delete = getattr(batch, "delete", None)
    if callable(delete):
        try:
            deleted = getattr(batch, "is_deleted", None)
            if callable(deleted) and deleted():
                return
            delete()
        except Exception:  # noqa: BLE001 — freeing is best-effort
            logger.debug("device buffer free failed", exc_info=True)


def _epoch_stream(source, sampler, auto_epoch: bool, name: str):
    """Items from ``source``; on exhaustion with ``auto_epoch``, bump
    the sampler epoch and re-iterate.

    A resumed sampler's FIRST pass may legitimately yield nothing
    (checkpoint taken near the epoch boundary with a drop_last tail),
    so one empty pass just rolls the epoch; two CONSECUTIVE empty
    passes mean the dataset cannot fill a single batch — raise
    loudly instead of spinning forever with the consumer blocked.
    """
    empty_passes = 0
    while True:
        yielded = False
        for item in source:
            yielded = True
            empty_passes = 0
            yield item
        if not auto_epoch:
            return
        if not yielded:
            empty_passes += 1
            if empty_passes >= 2:
                raise RuntimeError(
                    f"input source {name!r} yielded no batches for a "
                    "whole epoch (dataset smaller than one batch "
                    "with drop_last?)"
                )
        sampler.set_epoch(sampler.epoch + 1)


class _End:
    """Queue sentinel: source exhausted (and auto_epoch is off)."""


class _Error:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Entry:
    """One staged batch in flight: payload + sampler snapshot + the
    per-stage costs the consumer uses to split its wait."""

    __slots__ = ("batch", "state", "host_s", "h2d_s")

    def __init__(self, batch, state, host_s, h2d_s):
        self.batch = batch
        self.state = state
        self.host_s = host_s
        self.h2d_s = h2d_s


class Prefetcher:
    """Background staging pipeline over a batch source.

    Parameters
    ----------
    source: an iterable of raw batches (an ``ElasticDataLoader``, a
        generator, ...). With ``auto_epoch`` it must be RE-iterable —
        ``iter(source)`` is called again after each exhaustion.
    stage_fn: optional ``raw_batch -> staged_batch`` run in the
        worker thread (host-side collate). None = identity.
    h2d_fn: optional ``staged_batch -> device_batch`` — the
        host->device placement step (``jax.device_put`` under the
        step's ``NamedSharding``, e.g.
        ``ElasticTrainer.shard_microbatches``). Runs in the worker,
        so the queue hands the trainer committed device arrays. An
        ``h2d_fn`` failure is relayed to the consumer as a loud step
        error, never a hang.
    depth: staged batches held ahead of the consumer (bounded queue;
        the worker blocks when full). None = DLROVER_TPU_PREFETCH_DEPTH
        or 2 (double buffering).
    sampler: optional object with ``state_dict()`` / ``set_epoch()``
        (an ``ElasticDistributedSampler``). Enables the
        delivered-batch state snapshots and auto_epoch.
    auto_epoch: when the source exhausts, bump ``sampler.set_epoch
        (epoch + 1)`` and re-iterate instead of ending the stream —
        the shape of the high-level Trainer's epoch loop.
    """

    def __init__(
        self,
        source: Iterable,
        stage_fn: Optional[Callable[[Any], Any]] = None,
        depth: Optional[int] = None,
        sampler=None,
        auto_epoch: bool = False,
        name: str = "train",
        h2d_fn: Optional[Callable[[Any], Any]] = None,
    ):
        if auto_epoch and sampler is None:
            raise ValueError("auto_epoch requires a sampler")
        self._source = source
        self._stage_fn = stage_fn
        self._h2d_fn = h2d_fn
        self.depth = depth if depth is not None else prefetch_depth()
        if self.depth < 1:
            raise ValueError(f"depth must be >= 1, got {self.depth}")
        self._sampler = sampler
        self._auto_epoch = auto_epoch
        self.name = name
        self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._exhausted = False
        self._closed = False
        # State as of the last DELIVERED batch — what a checkpoint
        # must record so in-flight batches are replayed, not skipped.
        self._delivered_state = (
            dict(sampler.state_dict()) if sampler is not None else None
        )
        self.staged = 0
        self.delivered = 0
        self.dropped = 0
        self.wait_s_total = 0.0
        # Wait split totals + last-batch split (wait_breakdown()).
        self.host_wait_s_total = 0.0
        self.h2d_wait_s_total = 0.0
        self._last_split: Tuple[float, float] = (0.0, 0.0)
        # Staging cost totals.
        self.host_stage_s_total = 0.0
        self.h2d_stage_s_total = 0.0
        obs.event(
            "trainer.prefetch_start",
            pipeline=name,
            depth=self.depth,
        )
        self._thread = threading.Thread(
            target=self._run, name=f"prefetch-{name}", daemon=True
        )
        self._thread.start()

    # -- worker --------------------------------------------------------------

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close()."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        try:
            it = _epoch_stream(
                self._source, self._sampler, self._auto_epoch,
                self.name,
            )
            while not self._stop.is_set():
                t_pull = time.perf_counter()
                try:
                    raw = next(it)
                except StopIteration:
                    self._put(_End)
                    return
                # Snapshot AFTER the pull: the state in which this
                # batch (and everything before it) counts as consumed.
                state = (
                    dict(self._sampler.state_dict())
                    if self._sampler is not None
                    else None
                )
                with obs.span(
                    "trainer.prefetch_stage", pipeline=self.name
                ):
                    staged = (
                        self._stage_fn(raw)
                        if self._stage_fn is not None
                        else raw
                    )
                host_s = time.perf_counter() - t_pull
                h2d_s = 0.0
                if self._h2d_fn is not None:
                    # The worker finishes with committed device
                    # arrays: a failing device_put lands in the
                    # _Error relay below — a loud step error at the
                    # consumer, never a silent hang on the queue.
                    t_h2d = time.perf_counter()
                    with obs.span(
                        "trainer.prefetch_h2d", pipeline=self.name
                    ):
                        staged = self._h2d_fn(staged)
                    h2d_s = time.perf_counter() - t_h2d
                    _STAGE_SECONDS.inc(h2d_s, phase="h2d")
                self.host_stage_s_total += host_s
                self.h2d_stage_s_total += h2d_s
                _STAGE_SECONDS.inc(host_s, phase="host")
                # Count BEFORE the put: a concurrent close() may
                # drain (and count dropped) the entry immediately,
                # and staged == delivered + dropped must hold at
                # prefetch_stop.
                self.staged += 1
                _BATCHES.inc(outcome="staged")
                entry = _Entry(staged, state, host_s, h2d_s)
                if not self._put(entry):
                    # Stopped while blocked on a full queue: the
                    # batch never reached the consumer — free any
                    # device buffers it holds.
                    free_device_buffers(entry.batch)
                    self.dropped += 1
                    _BATCHES.inc(outcome="dropped")
                    return
        except BaseException as exc:  # noqa: BLE001 — relayed to consumer
            self._put(_Error(exc))

    # -- consumer ------------------------------------------------------------

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        if self._closed:
            raise RuntimeError("Prefetcher is closed")
        if self._exhausted:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            # Bounded get so a close() from ANOTHER thread (elastic
            # restart, watchdog) unblocks a consumer waiting on an
            # empty queue instead of deadlocking it forever; a batch
            # landing mid-wait still wakes the get immediately.
            try:
                entry = self._queue.get(timeout=0.1)
                break
            except queue.Empty:
                if self._closed:
                    raise RuntimeError(
                        "Prefetcher closed while waiting for a batch"
                    ) from None
        wait = time.perf_counter() - t0
        if entry is _End:
            self._exhausted = True
            raise StopIteration
        if isinstance(entry, _Error):
            self._exhausted = True
            raise entry.exc
        # Queue wait splits by what the worker was doing for this
        # batch: a blocked consumer was waiting on host staging and
        # H2D in that proportion (both ~0 on a queue hit).
        stage_total = entry.host_s + entry.h2d_s
        frac = entry.h2d_s / stage_total if stage_total > 0 else 0.0
        host_wait, h2d_wait = wait * (1.0 - frac), wait * frac
        # Record the wait only for REAL batches — the terminal
        # sentinel fetch must not add a phantom sample to the
        # data-wait histogram / trainer.prefetch_wait stream.
        self.wait_s_total += wait
        self.host_wait_s_total += host_wait
        self.h2d_wait_s_total += h2d_wait
        self._last_split = (host_wait, h2d_wait)
        _DATA_WAIT.observe(wait)
        obs.event(
            "trainer.prefetch_wait",
            pipeline=self.name,
            dur_s=round(wait, 6),
            host_s=round(host_wait, 6),
            h2d_s=round(h2d_wait, 6),
        )
        if entry.state is not None:
            self._delivered_state = entry.state
        self.delivered += 1
        _BATCHES.inc(outcome="delivered")
        return entry.batch

    def wait_breakdown(self) -> Tuple[float, float]:
        """(host_wait_s, h2d_wait_s) of the LAST delivered batch's
        consumer wait — what the train loop feeds
        ``StepPhaseProfiler.note_data_wait(host, h2d_seconds=h2d)``
        so the ``data_wait`` phase splits attributably."""
        return self._last_split

    def sampler_state_dict(self) -> Optional[dict]:
        """Sampler state as of the last batch the CONSUMER received.

        Batches staged ahead in the queue (or mid-stage in the
        worker) are NOT counted, so checkpointing this dict makes an
        elastic restart replay them instead of skipping data.
        """
        state = self._delivered_state
        return dict(state) if state is not None else None

    # -- shutdown ------------------------------------------------------------

    def close(self) -> None:
        """Stop the worker and drop staged-but-undelivered batches,
        eagerly freeing their device buffers (HBM slots return now,
        not at GC time).

        Idempotent; called on elastic restart and normal shutdown.
        The dropped batches were never delivered, so
        :meth:`sampler_state_dict` has never counted them — the next
        incarnation's sampler replays them.
        """
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # Drain so a worker blocked on a full queue can observe the
        # stop event and exit.
        self._drain_dropped()
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():  # pragma: no cover — stage_fn hang
            logger.warning(
                "prefetch worker %r did not stop within 5s", self.name
            )
        # A put already in flight when stop was set may have landed
        # after the first drain; sweep again now the worker is done.
        self._drain_dropped()
        obs.event(
            "trainer.prefetch_stop",
            pipeline=self.name,
            staged=self.staged,
            delivered=self.delivered,
            dropped=self.dropped,
            wait_s_total=round(self.wait_s_total, 6),
            host_stage_s_total=round(self.host_stage_s_total, 6),
            h2d_stage_s_total=round(self.h2d_stage_s_total, 6),
        )

    def _drain_dropped(self) -> None:
        while True:
            try:
                entry = self._queue.get_nowait()
            except queue.Empty:
                return
            if entry is not _End and not isinstance(entry, _Error):
                free_device_buffers(entry.batch)
                self.dropped += 1
                _BATCHES.inc(outcome="dropped")

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# The name every train loop (and the benchmark) builds its feeder by:
# ``make_input_pipeline(source, h2d_fn=..., name=...)``.
make_input_pipeline = Prefetcher
