"""ElasticTrainer: fixed global batch size under elasticity.

TPU-native counterpart of the reference's ElasticTrainer
(dlrover/trainer/torch/elastic/trainer.py:225 and
_set_gradient_accumulation_steps :420): the *global* batch size the
user asked for stays constant while the number of data-parallel shards
changes across elastic restarts, by recomputing the gradient
accumulation factor every time the world (here: the mesh ``data`` x
``fsdp`` extent) changes.

Design differences from the torch original, on purpose:

* no optimizer/model wrapper objects — JAX training state is explicit
  (params, opt_state), so the trainer owns a compiled
  ``accumulate-then-update`` step built with ``lax.scan`` over
  microbatches: one XLA program, gradients psum'd once per *global*
  step, not per microbatch (the reference gets the same effect with
  DDP no_sync, trainer.py:76).
* world size is read from the mesh, not torch.distributed; an elastic
  restart builds a new mesh and a new trainer, then restores state
  from flash checkpoint.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu import obs
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.obs.profiling import (
    MFU_ENV,
    CompileTracker,
    MfuMeter,
    StepPhaseProfiler,
)
from dlrover_tpu.parallel.mesh import under_mesh
from dlrover_tpu.parallel.sharding import prune_specs_to_mesh
from dlrover_tpu.trainer.async_metrics import AsyncScalarReporter
from dlrover_tpu.trainer.step import StateStep, batch_spec

logger = get_logger("elastic_trainer")

_STEPS_TOTAL = obs.counter(
    "dlrover_train_steps_total", "Optimizer steps taken this process"
)
_STEP_SECONDS = obs.histogram(
    "dlrover_train_step_seconds",
    "Wall time between consecutive train_step DISPATCHES (first "
    "sample per trainer covers the XLA compile). The zero-sync hot "
    "loop no longer blocks per step on async backends, so individual "
    "samples measure host-side pacing, small until the loop hits a "
    "sync point (log interval, reporter backpressure, checkpoint); "
    "the MEAN over a window still equals true step time, because the "
    "samples' sum is wall time",
)


def data_shards(mesh: Mesh) -> int:
    """Number of data-parallel shards the batch dim is split over."""
    return mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)


def gradient_accumulation_steps(
    global_batch_size: int, micro_batch_size: int, num_shards: int
) -> int:
    """Microbatches per optimizer update so that
    num_shards * micro_batch_size * accum >= global_batch_size, i.e.
    the effective batch never shrinks when nodes are lost
    (ref: trainer.py:420 rounds the same way)."""
    per_step = micro_batch_size * num_shards
    return (global_batch_size + per_step - 1) // per_step


@dataclasses.dataclass
class TrainerReport:
    """Per-step scalars for the master speed monitor."""

    step: int
    loss: float
    global_batch_size: int
    accum_steps: int


class ElasticTrainer:
    """Builds a compiled global-step function with gradient
    accumulation and keeps the global batch size fixed.

    Parameters
    ----------
    mesh: the device mesh (source of the data-parallel world size).
    loss_fn: ``loss_fn(params, tokens, targets) -> scalar``.
    optimizer: an optax transformation.
    global_batch_size: what the user wants per optimizer update.
    micro_batch_size: per-shard microbatch the hardware can hold.
    report_fn: optional callback(TrainerReport) — wired to the master
        client's speed reporting by the agent integration.
    """

    def __init__(
        self,
        mesh: Mesh,
        loss_fn: Optional[Callable],
        optimizer: optax.GradientTransformation,
        global_batch_size: int,
        micro_batch_size: int,
        report_fn: Optional[Callable[[TrainerReport], None]] = None,
        step_fn: Optional[Callable] = None,
        donate_state: bool = True,
        report_max_pending: int = 8,
    ):
        """``step_fn``: a prebuilt full-batch training step —
        ``step_fn(params, opt_state, tokens[B, ...], targets) ->
        (params, opt_state, metrics)`` — replacing the built-in
        scan-accumulation step. This is how pipelined training rides
        the elastic loop: pass a models/pipeline_lm step (its internal
        1F1B microbatching takes over the role of grad accumulation;
        the fixed-global-batch contract and per-process batch
        assembly are unchanged). ``loss_fn`` may be None then.

        ``donate_state``: build the jitted step with
        ``donate_argnums`` for (params, opt_state) so XLA updates the
        training state IN PLACE — halves peak HBM and removes the
        copy-on-update. The returned (params, opt_state) must replace
        the caller's references (the inputs' buffers are deleted).
        The escape hatch for callers that ALIAS state — keep a handle
        to the pre-step params for comparison, feed the same pytree to
        two trainers, hold a reference from an in-flight async
        consumer — is ``donate_state=False``; see
        docs/PERFORMANCE.md for the caveats.

        ``report_max_pending``: bound of the async reporter's deque of
        un-materialized (step, device-loss) entries; above it the
        oldest entry is force-fetched so memory stays bounded."""
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.global_batch_size = global_batch_size
        self.micro_batch_size = micro_batch_size
        self.report_fn = report_fn
        self.donate_state = donate_state
        self.num_shards = data_shards(mesh)
        self.step_num = 0
        # Loss scalars reach report_fn via the async drain: the hot
        # loop hands the DEVICE scalar over and never blocks on a
        # device->host transfer; values arrive (in order, exactly
        # once) one step late, plus a flush() at checkpoint/shutdown.
        self._reporter: Optional[AsyncScalarReporter] = None
        if report_fn is not None:
            self._reporter = AsyncScalarReporter(
                self._emit_report,
                max_pending=report_max_pending,
                reason="speed_report",
            )
        # perf_counter of the last train_step completion; None until
        # the first step of THIS trainer instance (each elastic
        # restart builds a new trainer, so the first sample after any
        # world change covers that world's compile).
        self._last_step_t: Optional[float] = None
        # Perf observability: recompile accounting on the jitted step
        # (every elastic restart builds a new trainer, so counter
        # increments attribute to this world's function), a live MFU
        # meter fed by cost-analysis FLOPs derived at the compile
        # boundary (DLROVER_TPU_MFU=0 skips the extra trace+lower),
        # and an optional step-phase profiler the owning loop attaches
        # (attach_profiler) to get dispatch/compile phases noted.
        self.mfu_meter = MfuMeter()
        self.profiler: Optional[StepPhaseProfiler] = None
        if step_fn is not None:
            if loss_fn is not None:
                raise ValueError(
                    "pass either loss_fn or step_fn, not both — "
                    "step_fn would silently win"
                )
            # The external step (e.g. a 1F1B pipeline) consumes the
            # WHOLE global batch in one call and owns its own
            # microbatching: accumulation collapses to 1, and the
            # per-shard slice must be exactly micro_batch_size so
            # [1, global] stays a plain block-sharded batch (an
            # accum>1 flatten would interleave shard ownership and
            # force resharding inside the step).
            if micro_batch_size * self.num_shards != global_batch_size:
                raise ValueError(
                    f"step_fn mode needs micro_batch_size "
                    f"({micro_batch_size}) x batch shards "
                    f"({self.num_shards}) == global_batch_size "
                    f"({global_batch_size}); rebuild the trainer "
                    "with the resized mesh's per-shard batch"
                )
            self.accum_steps = 1
            self._compiled = self._wrap_flat_step(step_fn)
        else:
            if loss_fn is None:
                raise ValueError(
                    "loss_fn is required without a prebuilt step_fn"
                )
            self.accum_steps = gradient_accumulation_steps(
                global_batch_size, micro_batch_size, self.num_shards
            )
            self._compiled = self._build_step()
        self._compile_tracker = CompileTracker(
            "train_step", jfn=self._compiled
        )
        logger.info(
            "elastic trainer: %d shards x micro %d x accum %d >= "
            "global %d%s",
            self.num_shards,
            micro_batch_size,
            self.accum_steps,
            global_batch_size,
            " (external step_fn)" if step_fn is not None else "",
        )

    # -- step construction --------------------------------------------------

    def _build_step(self):
        accum = self.accum_steps
        optimizer = self.optimizer
        mesh = self.mesh
        loss_fn = under_mesh(self.loss_fn, mesh)
        bspec = batch_spec(mesh)
        # Microbatch dim leads: [accum, per_shard_batch, ...]
        mb_spec = P(None, *bspec)

        def train_step(params, opt_state, tokens, targets):

            def micro(carry, batch):
                grad_acc, loss_acc = carry
                mb_tokens, mb_targets = batch
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, mb_tokens, mb_targets
                )
                # Each microbatch pre-scaled by 1/accum: no final
                # divide. The accumulator is float32 whatever the
                # parameters are: a bf16 sum drops late microbatches
                # once |acc| >> |g / accum|.
                grad_acc = jax.tree.map(
                    lambda a, g: a + (g / accum).astype(a.dtype),
                    grad_acc,
                    grads,
                )
                return (grad_acc, loss_acc + loss), None

            # "accumulate" owns the microbatch scan's own cost (the
            # accumulator's zeros and scaled add, the slicing, the
            # while); the model's scopes inside it keep theirs
            # (obs.profiling.compiled_scopes).
            with jax.named_scope("accumulate"):
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (grads, loss_sum), _ = jax.lax.scan(
                    micro, (zeros, 0.0), (tokens, targets)
                )
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss_sum / accum

        self._mb_spec = mb_spec
        return StateStep(train_step, self._donate_argnums())

    def _wrap_flat_step(self, step_fn):
        """Adapt an external full-batch step to the trainer's
        [accum, per_shard_batch, ...] microbatch layout: flatten the
        leading dims back to one batch axis (the external step — e.g.
        a 1F1B pipeline — owns its own microbatching) and normalize
        its metrics to the scalar loss the loop reports."""
        bspec = batch_spec(self.mesh)
        self._mb_spec = P(None, *bspec)

        def train_step(params, opt_state, tokens, targets):
            # accum is pinned to 1 in step_fn mode, so this flatten
            # just drops the leading singleton — the batch dim keeps
            # its block sharding; jitted so it fuses into the step.
            flat_tok = tokens.reshape((-1,) + tokens.shape[2:])
            flat_tgt = targets.reshape((-1,) + targets.shape[2:])
            params, opt_state, metrics = step_fn(
                params, opt_state, flat_tok, flat_tgt
            )
            loss = (
                metrics["loss"]
                if isinstance(metrics, dict)
                else metrics
            )
            return params, opt_state, loss

        return StateStep(train_step, self._donate_argnums())

    def _donate_argnums(self) -> Tuple[int, ...]:
        """(params, opt_state) positions when in-place update is on."""
        return (0, 1) if self.donate_state else ()

    def shard_microbatches(
        self, tokens, targets
    ) -> Tuple[jax.Array, jax.Array]:
        """Host arrays -> [accum, micro * shards, ...] device arrays
        laid out on the mesh.

        Single-process: pass the full global batch
        ([samples_per_step, ...]). Multi-process: each process passes
        only ITS portion ([local_samples_per_step, ...] — the samples
        its sharded sampler produced); the global array is assembled
        from the per-process shards, never requiring (or silently
        duplicating) identical host data across processes."""
        spec = prune_specs_to_mesh(self.mesh, self._mb_spec)
        sharding = NamedSharding(self.mesh, spec)
        accum = self.accum_steps
        n_proc = jax.process_count()
        if n_proc <= 1:
            n = self.samples_per_step
            tokens = tokens[:n].reshape(
                (accum, -1) + tokens.shape[1:]
            )
            targets = targets[:n].reshape(
                (accum, -1) + targets.shape[1:]
            )
            return (
                jax.device_put(tokens, sharding),
                jax.device_put(targets, sharding),
            )
        n = self.local_samples_per_step
        global_mb = self.micro_batch_size * self.num_shards
        local = np.asarray(tokens[:n]).reshape(
            (accum, -1) + tuple(tokens.shape[1:])
        )
        local_t = np.asarray(targets[:n]).reshape(
            (accum, -1) + tuple(targets.shape[1:])
        )
        gshape = lambda a: (accum, global_mb) + a.shape[2:]  # noqa: E731
        return (
            jax.make_array_from_process_local_data(
                sharding, local, gshape(local)
            ),
            jax.make_array_from_process_local_data(
                sharding, local_t, gshape(local_t)
            ),
        )

    @property
    def samples_per_step(self) -> int:
        return self.accum_steps * self.micro_batch_size * self.num_shards

    @property
    def local_samples_per_step(self) -> int:
        """Samples THIS process must supply per optimizer step (its
        sharded sampler's slice of the global batch).

        Requires the batch-sharding mesh axes (data/fsdp) to span
        whole processes — num_shards divisible by process_count — so
        every process owns an equal contiguous slice of every
        microbatch. A mesh whose batch axes do NOT cover all
        processes (e.g. tensor-parallel-only multi-host) replicates
        the batch across processes, which this per-process-slice
        contract cannot express; feed pre-sharded device arrays to
        train_step directly in that regime."""
        n_proc = jax.process_count()
        if self.num_shards % n_proc:
            raise ValueError(
                f"batch shards ({self.num_shards}) not divisible by "
                f"processes ({n_proc}): the batch axes of this mesh "
                "do not span whole hosts, so a per-process batch "
                "slice does not exist — pass pre-sharded arrays to "
                "train_step instead"
            )
        return self.samples_per_step // n_proc

    def train_step(self, params, opt_state, tokens, targets):
        """One optimizer update over ``accum`` microbatches.

        tokens/targets: numpy host arrays to be sharded here, or
        [accum, micro*shards, ...] device arrays already staged (use
        shard_microbatches, ideally off-thread via
        ``dlrover_tpu.data.prefetch.Prefetcher``).

        Zero-sync contract: with pre-staged inputs this neither reads
        nor writes host memory — the returned ``loss`` is a DEVICE
        scalar (materialize it with
        ``async_metrics.materialize(loss)``, never ``float(loss)``,
        in guarded hot loops) and the speed report drains
        asynchronously one step late. ``flush_metrics()`` delivers
        the tail at checkpoint/shutdown.

        With ``donate_state`` (default) params/opt_state buffers are
        donated to XLA: rebind them from the return value and never
        touch the inputs again.
        """
        if isinstance(tokens, np.ndarray):
            # Host batch of ANY rank gets staged; device arrays
            # are assumed already sharded and are never re-staged.
            tokens, targets = self.shard_microbatches(tokens, targets)
        else:
            # Loud contract check for the passthrough path: a caller
            # still feeding flat [N, ...] jnp host batches (the
            # pre-donation calling convention) must hear "stage it"
            # here, not a shape error deep inside lax.scan — or
            # worse, a silently wrong update when N == accum.
            expect = (
                self.accum_steps,
                self.micro_batch_size * self.num_shards,
            )
            if tokens.ndim < 2 or tuple(tokens.shape[:2]) != expect:
                raise ValueError(
                    f"device-array batch must be pre-staged as "
                    f"[accum={expect[0]}, micro*shards={expect[1]}, "
                    f"...]; got shape {tuple(tokens.shape)} — pass a "
                    "numpy host batch or stage with "
                    "shard_microbatches() (ideally via "
                    "data.prefetch.make_input_pipeline)"
                )
        if self._last_step_t is None:
            TrainingMonitor.mark_phase("first_dispatch")
            if (
                self.mfu_meter.flops_per_step is None
                and os.getenv(MFU_ENV, "1") != "0"
            ):
                # Compile boundary: price the step with XLA's cost
                # model BEFORE dispatch (donation deletes the input
                # buffers after it). Trace+lower only — never a second
                # compile.
                self.mfu_meter.set_flops(
                    self._compile_tracker.price(
                        params, opt_state, tokens, targets
                    )
                )
        args = (params, opt_state, tokens, targets)
        t0 = time.perf_counter()
        with obs.span(
            "trainer.dispatch", step=self.step_num + 1
        ) as span:
            params, opt_state, loss = self._compiled(*args)
            now = time.perf_counter()
            compiled_now = self._compile_tracker.observe_call(
                now - t0, args
            )
            span.set(compiled=compiled_now)
        if self.profiler is not None:
            self.profiler.note_dispatch(now - t0, compiled=compiled_now)
        if self._last_step_t is None:
            # Dispatch of the first call traces + compiles
            # synchronously: this sample is the compile boundary.
            _STEP_SECONDS.observe(now - t0)
            obs.event(
                "trainer.compile_done",
                dur_s=round(now - t0, 3),
                world_shards=self.num_shards,
            )
        else:
            _STEP_SECONDS.observe(now - self._last_step_t)
            # MFU rides the same between-dispatch cadence as
            # _STEP_SECONDS (the window mean equals true step time);
            # the compile-boundary sample is excluded so one slow
            # first step cannot depress the gauge for a whole window.
            # A loop with an attached profiler feeds the meter from
            # end_step() instead (same wall, plus phase context).
            if self.profiler is None:
                self.mfu_meter.observe_step(now - self._last_step_t)
        self._last_step_t = now
        _STEPS_TOTAL.inc()
        self.step_num += 1
        if self._reporter is not None:
            self._reporter.offer(self.step_num, loss)
        return params, opt_state, loss

    def attach_profiler(self, profiler: StepPhaseProfiler) -> None:
        """Hook a step-phase profiler into the hot path: train_step
        notes its dispatch (or compile) time on it, and the profiler's
        shared meter/tracker give captures the live MFU and compile
        counts. The owning loop still calls ``profiler.end_step()``
        once per step (it alone knows the data-wait boundary)."""
        profiler.mfu = self.mfu_meter
        profiler.compile_tracker = self._compile_tracker
        self.profiler = profiler

    @property
    def mfu(self) -> Optional[float]:
        """Live windowed MFU, None until FLOPs+steps are known."""
        return self.mfu_meter.mfu

    def _emit_report(self, step: int, loss: float) -> None:
        self.report_fn(
            TrainerReport(
                step=step,
                loss=loss,
                global_batch_size=self.samples_per_step,
                accum_steps=self.accum_steps,
            )
        )

    def flush_metrics(self) -> None:
        """Deliver every pending async loss report (blocking). Call
        before checkpointing trainer state and at shutdown so the
        master's speed monitor sees every step exactly once."""
        if self._reporter is not None:
            self._reporter.flush()

    # -- state for flash checkpoint -----------------------------------------

    def state_dict(self) -> dict:
        return {"step_num": self.step_num}

    def load_state_dict(self, state: dict) -> None:
        self.step_num = int(state.get("step_num", 0))


class ElasticDistributedSampler:
    """Checkpointable shuffling sampler (ref:
    trainer/torch/elastic/sampler.py:25).

    Yields dataset indices for THIS shard; ``state_dict`` records how
    many samples this epoch consumed so a restart — possibly with a
    different shard count — resumes exactly where training stopped
    instead of replaying or skipping data.
    """

    def __init__(
        self,
        dataset_size: int,
        num_shards: int = 1,
        shard_rank: int = 0,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
    ):
        if not 0 <= shard_rank < num_shards:
            raise ValueError(
                f"shard_rank {shard_rank} not in [0, {num_shards})"
            )
        self.dataset_size = dataset_size
        self.num_shards = num_shards
        self.shard_rank = shard_rank
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        self.consumed = 0  # samples consumed this epoch, GLOBAL count

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        self.consumed = 0

    def _epoch_order(self):
        import numpy as np

        order = np.arange(self.dataset_size)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            rng.shuffle(order)
        if self.drop_last:
            usable = (
                self.dataset_size
                // self.num_shards
                * self.num_shards
            )
            order = order[:usable]
        else:
            pad = (-len(order)) % self.num_shards
            if pad:
                order = np.concatenate([order, order[:pad]])
        return order

    def __iter__(self):
        order = self._epoch_order()
        # Round-robin interleave so the global consumed counter remains
        # meaningful when the shard count changes on resume.
        for global_pos in range(
            self.consumed + self.shard_rank, len(order), self.num_shards
        ):
            self.consumed = global_pos + (
                self.num_shards - self.shard_rank
            )
            yield int(order[global_pos])

    def __len__(self):
        # Derived arithmetically — materializing/shuffling the whole
        # permutation per len() call would be O(dataset) each time.
        if self.drop_last:
            order_len = (
                self.dataset_size // self.num_shards * self.num_shards
            )
        else:
            order_len = self.dataset_size + (
                (-self.dataset_size) % self.num_shards
            )
        return max(0, order_len - self.consumed) // self.num_shards

    def state_dict(self) -> dict:
        return {
            "epoch": self.epoch,
            "consumed": self.consumed,
            "seed": self.seed,
        }

    def load_state_dict(self, state: dict) -> None:
        self.epoch = int(state.get("epoch", 0))
        self.consumed = int(state.get("consumed", 0))
        self.seed = int(state.get("seed", self.seed))
        # Align to a shard boundary so no shard replays a neighbor's
        # sample after a world-size change.
        self.consumed -= self.consumed % self.num_shards


class ElasticDataLoader:
    """Batches a map-style dataset through a sampler, with optional
    master-driven dynamic sharding (ref:
    trainer/torch/elastic/dataloader.py + elastic_agent/sharding).

    ``sharding_client`` takes precedence: indices then come from the
    master's todo/doing shard queues (IndexShardingClient), giving
    at-least-once delivery when a worker dies mid-shard.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler: Optional[ElasticDistributedSampler] = None,
        sharding_client=None,
        collate_fn: Optional[Callable] = None,
        drop_last: bool = True,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler
        self.sharding_client = sharding_client
        self.collate_fn = collate_fn or _default_collate
        self.drop_last = drop_last

    def _index_stream(self):
        if self.sharding_client is not None:
            while True:
                idx = self.sharding_client.fetch_sample_index()
                if idx is None:
                    return
                yield idx
        elif self.sampler is not None:
            yield from self.sampler
        else:
            yield from range(len(self.dataset))

    def __iter__(self):
        batch = []
        for idx in self._index_stream():
            batch.append(self.dataset[idx])
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)


def _default_collate(samples):
    import numpy as np

    first = samples[0]
    if isinstance(first, tuple):
        return tuple(
            np.stack([s[i] for s in samples]) for i in range(len(first))
        )
    if isinstance(first, dict):
        return {k: np.stack([s[k] for s in samples]) for k in first}
    return np.stack(samples)
