"""High-level Trainer: the one-object training loop.

Parity with AtorchTrainer (atorch/trainer/atorch_trainer.py:121, an
HF-Trainer-style loop integrating auto_accelerate + flash checkpoint
saves): give it a functional model and a dataset, call ``train()``.
Integrates every layer of this framework: strategy (explicit or
searched), mesh + sharded step, fixed-global-batch accumulation,
checkpointable sampler, flash checkpoint save/restore, step-metrics
file for the agent's monitors, and master-pushed parallel-config
overrides when running under the elastic agent.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import Any, Callable, Optional, Tuple

import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.common.log import get_logger

logger = get_logger("trainer")


@dataclasses.dataclass
class TrainingArguments:
    """(ref transformers.TrainingArguments subset the AtorchTrainer
    consumes, atorch_trainer.py:121)"""

    max_steps: int = 1000
    global_batch_size: int = 32
    micro_batch_size: int = 4
    learning_rate: float = 3e-4
    optimizer: str = "adamw"
    checkpoint_dir: str = ""
    save_steps: int = 100
    log_steps: int = 10
    eval_steps: int = 0  # 0 = no periodic eval during train()
    eval_max_batches: int = 0  # 0 = the whole eval dataset
    warmup_steps: int = 0
    lr_schedule: str = "constant"  # constant | cosine (over max_steps)
    grad_clip_norm: float = 0.0  # 0 = no clipping
    seed: int = 0
    strategy: Optional[Any] = None  # accelerate.Strategy or None=search
    apply_paral_config: bool = True


class Trainer:
    def __init__(
        self,
        model_init: Callable,
        model_loss: Callable,
        logical_axes: Any,
        dataset,  # map-style: dataset[i] -> (tokens, targets)
        args: TrainingArguments,
        collate_fn: Optional[Callable] = None,
        eval_dataset=None,
    ):
        self.args = args
        self.model_init = model_init
        self.model_loss = model_loss
        self.logical_axes = logical_axes
        self.dataset = dataset
        self.eval_dataset = eval_dataset
        self.collate_fn = collate_fn
        self._eval_step = None  # jitted lazily by _run_eval

        if args.apply_paral_config:
            self._apply_paral_config()

    def _ckpt_dir(self) -> str:
        return self.args.checkpoint_dir or os.path.join(
            tempfile.gettempdir(), "dlrover_tpu_trainer_ckpt"
        )

    def _optimizer_name(self) -> str:
        """The optimizer actually used by train(): the strategy's
        (auto_accelerate reads strategy.optimizer), falling back to
        args.optimizer only when no explicit strategy is set."""
        if self.args.strategy is not None:
            return self.args.strategy.optimizer
        return self.args.optimizer

    def _optimizer_kwargs(self) -> dict:
        """Schedule/clipping knobs — passed IDENTICALLY by train()
        and evaluate() so checkpoint skeletons always match."""
        return {
            "warmup_steps": self.args.warmup_steps,
            "decay_steps": self.args.max_steps,
            "schedule": self.args.lr_schedule,
            "grad_clip_norm": self.args.grad_clip_norm,
        }

    def _apply_paral_config(self) -> None:
        """Master-pushed overrides staged by the agent's tuner. Only
        applied when actually running under the elastic agent — a
        standalone run must not pick up another job's leftover file."""
        if os.getenv("DLROVER_TPU_AGENT_PRESENT", "") != "1":
            return
        from dlrover_tpu.agent.paral_config_tuner import (
            read_parallel_config,
        )

        cfg = read_parallel_config()
        if not cfg:
            return
        if cfg.get("micro_batch_size"):
            self.args.micro_batch_size = int(cfg["micro_batch_size"])
            logger.info(
                "paral config v%s: micro_batch_size=%d",
                cfg.get("version"),
                self.args.micro_batch_size,
            )

    def train(self) -> dict:
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.accelerate import auto_accelerate
        from dlrover_tpu.agent.monitor import TrainingMonitor
        from dlrover_tpu.data.prefetch import make_input_pipeline
        from dlrover_tpu.trainer import jax_env
        from dlrover_tpu.trainer.async_metrics import materialize
        from dlrover_tpu.trainer.elastic_trainer import (
            ElasticDataLoader,
            ElasticDistributedSampler,
            ElasticTrainer,
        )
        from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
            Checkpointer,
            StorageType,
        )

        args = self.args
        jax_env.setup_distributed()

        first = self.dataset[0]
        sample = (
            jnp.asarray(first[0])[None],
            jnp.asarray(first[1])[None],
        )
        res = auto_accelerate(
            self.model_init,
            self.model_loss,
            self.logical_axes,
            sample,
            learning_rate=args.learning_rate,
            strategy=args.strategy,
            optimizer_kwargs=self._optimizer_kwargs(),
        )
        trainer = ElasticTrainer(
            res.mesh,
            self.model_loss,
            res.optimizer,
            global_batch_size=args.global_batch_size,
            micro_batch_size=args.micro_batch_size,
        )
        params, opt_state = res.init_fn(
            jax.random.PRNGKey(args.seed)
        )

        ckpt_dir = self._ckpt_dir()
        ckpt = Checkpointer(ckpt_dir)
        sampler = ElasticDistributedSampler(
            dataset_size=len(self.dataset),
            num_shards=jax_env.num_processes(),
            shard_rank=max(jax_env.process_id(), 0),
            seed=args.seed,
        )
        start_step = 0
        restored = ckpt.load_checkpoint((params, opt_state))
        if restored is not None:
            params, opt_state = restored
            start_step = ckpt.last_restored_step
            sampler_state = ckpt.last_restored_extra.get("sampler")
            if sampler_state is not None:
                # Exact data-resume guarantee: the checkpointed sampler
                # state carries epoch + global consumed count and is
                # world-size-change aware (load_state_dict re-rounds to
                # the new shard count).
                sampler.load_state_dict(dict(sampler_state))
            else:
                # Old checkpoint without sampler state: estimate with
                # the per-process draw (the loader pulls
                # local_samples_per_step from this process's shard),
                # not the global batch size.
                sampler.consumed = (
                    start_step * trainer.local_samples_per_step
                ) % max(len(self.dataset), 1)
            logger.info("resumed from checkpoint step %d", start_step)
        trainer.step_num = start_step

        # Each process loads only ITS slice of the global batch (the
        # sampler is process-sharded); shard_microbatches assembles
        # the global device array from the per-process portions.
        loader = ElasticDataLoader(
            self.dataset,
            batch_size=trainer.local_samples_per_step,
            sampler=sampler,
            collate_fn=self.collate_fn,
        )

        def _collate(batch):
            # Host-side stage: collate output normalized to numpy —
            # runs in the prefetch worker, timed as the "host" half of
            # the staging split.
            tokens, targets = batch
            return np.asarray(tokens), np.asarray(targets)

        def _h2d(batch):
            # Device stage: H2D under the step's NamedSharding. This
            # also runs in the worker, so the queue hands the loop
            # committed device arrays and step N+1's transfer overlaps
            # step N's compute.
            return trainer.shard_microbatches(*batch)

        batches = make_input_pipeline(
            loader,
            stage_fn=_collate,
            h2d_fn=_h2d,
            sampler=sampler,
            auto_epoch=True,
            name="trainer",
        )

        def _sampler_state() -> dict:
            # The pipeline's snapshot counts only DELIVERED batches,
            # so a restart replays staged-but-untrained ones. Never
            # fall back to the live sampler here: the worker has
            # already advanced it past the in-flight batches.
            return batches.sampler_state_dict()

        # Device scalars only in the hot loop: the loss is fetched to
        # host ON the logging interval and once at the end, never per
        # step (async_metrics.materialize = explicit, counted sync).
        last_loss = None
        last_eval, last_eval_step = None, -1
        t0 = time.time()
        step = start_step
        prev_step_t = time.time()
        # Step-phase attribution + on-demand PROFILE capture: this
        # loop notes the data-wait boundary, the trainer notes
        # dispatch/compile, end_step() books the residual as device
        # time and polls for master-pushed profile requests.
        from dlrover_tpu.obs.profiling import StepPhaseProfiler

        profiler = StepPhaseProfiler()
        trainer.attach_profiler(profiler)
        try:
            for step in range(start_step + 1, args.max_steps + 1):
                tokens, targets = next(batches)
                # The pipeline measured this batch's wait itself and
                # splits it host-side vs H2D staging — the attribution
                # that makes a device-prefetch win visible in
                # dlrover_step_phase_seconds_total.
                host_w, h2d_w = batches.wait_breakdown()
                profiler.note_data_wait(host_w, h2d_seconds=h2d_w)
                params, opt_state, last_loss = trainer.train_step(
                    params, opt_state, tokens, targets
                )
                profiler.end_step()
                # Per-step wall time (dispatch pacing, same caveat as
                # dlrover_train_step_seconds): rides the metrics file
                # to the agent and on to the master's straggler
                # scorer, so relative slowness is comparable fleetwide.
                now_t = time.time()
                step_wall, prev_step_t = now_t - prev_step_t, now_t
                TrainingMonitor.write_metrics(
                    step,
                    tokens=step
                    * args.global_batch_size
                    * tokens.shape[-1],
                    step_time=step_wall,
                    mfu=trainer.mfu,
                )
                if step % args.log_steps == 0:
                    loss_val = materialize(last_loss, reason="log")
                    # The already-paid host sync doubles as the black
                    # box's last-known-loss (no extra fetch).
                    obs.recorder_note(loss=float(loss_val))
                    logger.info(
                        "step %d: loss %.4f (%.1f steps/s)",
                        step,
                        loss_val,
                        args.log_steps / max(time.time() - t0, 1e-9),
                    )
                    t0 = time.time()
                if (
                    self.eval_dataset is not None
                    and args.eval_steps
                    and step % args.eval_steps == 0
                ):
                    last_eval = self._run_eval(res.mesh, params)
                    last_eval_step = step
                    logger.info(
                        "step %d: eval_loss %.4f ppl %.2f (%d batches)",
                        step, last_eval["eval_loss"],
                        last_eval["perplexity"], last_eval["batches"],
                    )
                if args.save_steps and step % args.save_steps == 0:
                    trainer.flush_metrics()
                    ckpt.save_checkpoint(
                        step, (params, opt_state),
                        storage_type=StorageType.DISK,
                        extra={
                            "sampler": _sampler_state(),
                            "strategy": res.strategy.to_json(),
                        },
                    )
            trainer.flush_metrics()
            ckpt.save_checkpoint(
                step, (params, opt_state),
                storage_type=StorageType.DISK,
                extra={
                    "sampler": _sampler_state(),
                    "strategy": res.strategy.to_json(),
                },
            )
        finally:
            batches.close()
        final_eval = None
        if self.eval_dataset is not None:
            # reuse the in-loop result when the last step already ran it
            final_eval = (
                last_eval
                if last_eval_step == step
                else self._run_eval(res.mesh, params)
            )
        ckpt.wait_latest_checkpoint()
        ckpt.close()
        return {
            "final_step": step,
            "final_loss": (
                materialize(last_loss, reason="final")
                if last_loss is not None
                else None
            ),
            "eval": final_eval,
            "params": params,
            "opt_state": opt_state,
            "strategy": res.strategy,
        }

    def _run_eval(self, mesh, params) -> dict:
        """Mean loss + perplexity over eval_dataset (the evaluator
        role of the reference's estimator stack — here any process
        holding params can evaluate; see also ``evaluate()`` for the
        standalone checkpoint-watching evaluator node).

        Eval batches are sized like a training micro-step
        (micro_batch_size per data shard), so eval never spikes
        activation memory above what training already uses; the tail
        that doesn't fill a batch is dropped (standard drop_last).
        """
        import jax.numpy as jnp

        from dlrover_tpu.trainer.step import make_eval_step, shard_batch

        if self._eval_step is None:
            self._eval_step = make_eval_step(self.model_loss)
        args = self.args
        shape = dict(mesh.shape)
        data_shards = shape.get("data", 1) * shape.get("fsdp", 1)
        bs = args.micro_batch_size * data_shards
        n = len(self.eval_dataset)
        if n < bs:
            raise ValueError(
                f"eval_dataset has {n} samples < one eval batch "
                f"({bs} = micro_batch_size x data shards)"
            )
        total_batches = n // bs
        max_batches = min(
            args.eval_max_batches or total_batches, total_batches
        )
        total = 0.0
        for b in range(max_batches):
            pairs = [
                self.eval_dataset[b * bs + i] for i in range(bs)
            ]
            tokens = np.stack([p[0] for p in pairs])
            targets = np.stack([p[1] for p in pairs])
            tokens, targets = shard_batch(
                mesh, jnp.asarray(tokens), jnp.asarray(targets)
            )
            total += float(self._eval_step(params, tokens, targets))
        mean = total / max(max_batches, 1)
        return {
            "eval_loss": mean,
            "perplexity": float(np.exp(min(mean, 30.0))),
            "batches": max_batches,
        }

    def evaluate(self, params=None, mesh=None) -> dict:
        """Standalone evaluation (the reference's evaluator node,
        master/node per-role managers): restore the latest committed
        checkpoint when ``params`` is None and score eval_dataset.
        """
        import jax

        from dlrover_tpu.accelerate import make_optimizer
        from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
            Checkpointer,
        )

        if self.eval_dataset is None:
            raise ValueError("Trainer was built without eval_dataset")
        args = self.args
        if params is None and args.strategy is None:
            raise ValueError(
                "evaluate(params=None) needs args.strategy to rebuild "
                "the checkpoint's optimizer-state skeleton — a "
                "strategy=None training run searched one (train() "
                "records it in the checkpoint extras under "
                "'strategy'); pass that Strategy here."
            )
        if mesh is None:
            # Eval is read-only: build the mesh straight from the
            # strategy's shape (or plain DP) — no strategy search, no
            # throwaway optimizer/init plumbing.
            from dlrover_tpu.parallel.mesh import (
                MeshConfig,
                build_mesh,
            )

            if args.strategy is not None:
                shape = dict(args.strategy.mesh_shape)
                n_dev = 1
                for v in shape.values():
                    n_dev *= v
                mesh = build_mesh(
                    MeshConfig(**shape),
                    devices=jax.devices()[:n_dev],
                )
            else:
                mesh = build_mesh(
                    MeshConfig(data=len(jax.devices()))
                )
        if params is None:
            from dlrover_tpu.parallel.sharding import tree_shardings
            from dlrover_tpu.trainer.step import (
                _match_opt_sharding,
                init_opt_state,
            )

            # Skeleton matches what train() SAVED: the strategy's
            # optimizer (auto_accelerate never reads args.optimizer)
            # with the SAME schedule/clipping knobs.
            opt = make_optimizer(
                self._optimizer_name(), args.learning_rate,
                **self._optimizer_kwargs(),
            )
            like = jax.eval_shape(
                lambda k: (
                    self.model_init(k),
                    init_opt_state(opt, self.model_init(k)),
                ),
                jax.random.PRNGKey(0),
            )
            # Shardings make the restore STREAM (each host reads only
            # its shards) and land params already placed per the rule
            # table — no host-side full assembly, no per-batch
            # re-upload of replicated numpy leaves.
            param_shard = tree_shardings(mesh, self.logical_axes)
            opt_shard = _match_opt_sharding(
                like[1], like[0], param_shard, mesh
            )
            ckpt_dir = self._ckpt_dir()
            ckpt = Checkpointer(ckpt_dir)
            try:
                state = ckpt.load_checkpoint(
                    like, shardings=(param_shard, opt_shard)
                )
                if state is None:
                    raise FileNotFoundError(
                        f"no committed checkpoint under {ckpt_dir!r}"
                    )
                params = state[0]
            finally:
                ckpt.close()
        return self._run_eval(mesh, params)
