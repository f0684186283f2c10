"""Elastic nanoGPT pretraining demo (the reference's headline example,
examples/pytorch/nanogpt/train.py, rebuilt on this framework's stack).

Run standalone on one host (CPU mesh or TPU):

    python -m dlrover_tpu.trainer.elastic_run --standalone \
        examples/nanogpt/train.py -- --smoke

Everything the framework offers is exercised: device mesh + sharded
train step (auto_accelerate), fixed-global-batch ElasticTrainer,
checkpointable sampler, flash checkpoint save/restore, step reporting
to the agent's training monitor, and the master-driven dynamic data
sharding when launched under the agent.

Data is synthetic character-level text (Zipfian token stream) so the
demo is hermetic — no downloads.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import tempfile
import time

import numpy as np

# Running as a script puts examples/nanogpt (not the repo root) first
# on sys.path; fix up here rather than ask for PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=0,
                   help="0 = default (50, or 8 with --smoke)")
    p.add_argument("--global-batch-size", type=int, default=32)
    p.add_argument("--micro-batch-size", type=int, default=4)
    p.add_argument("--block-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "agd", "adam8bit"])
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--checkpoint-every", type=int, default=20)
    p.add_argument("--smoke", action="store_true",
                   help="tiny model + few steps (CI)")
    p.add_argument("--search", action="store_true",
                   help="strategy search instead of default mesh")
    return p.parse_args(argv)


def synthetic_tokens(n_tokens: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    # Zipf-ish unigram stream with local structure (bigram mixing)
    base = rng.zipf(1.3, size=n_tokens).astype(np.int64) % vocab
    shifted = np.roll(base, 1)
    mix = rng.random(n_tokens) < 0.3
    return np.where(mix, (shifted * 7 + 3) % vocab, base).astype(
        np.int32
    )


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.agent.monitor import TrainingMonitor
    from dlrover_tpu.data.prefetch import make_input_pipeline
    from dlrover_tpu.models import gpt
    from dlrover_tpu.trainer import jax_env
    from dlrover_tpu.trainer.async_metrics import materialize
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticDistributedSampler,
        ElasticTrainer,
    )
    from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
        Checkpointer,
        StorageType,
    )

    # Phase marks (a dict store each, kept in the process; written to
    # DLROVER_TPU_PHASES_FILE too where that is set): chaos drills
    # split recovery time into these segments, and
    # obs.profiling.startup_timeline() returns them with JAX's own
    # trace / lower / compile records: what this start was made of.
    TrainingMonitor.mark_phase("proc_start")
    jax_env.setup_distributed()
    TrainingMonitor.mark_phase("dist_ready")

    if args.smoke:
        cfg = gpt.GPTConfig(
            vocab_size=256, block_size=args.block_size, n_layer=2,
            n_head=2, n_embd=64,
            dtype=jnp.float32, remat=False,
        )
        if args.steps <= 0:
            args.steps = 8
    else:
        cfg = gpt.GPTConfig.nano()
        if args.steps <= 0:
            args.steps = 50

    model_init = functools.partial(gpt.init_params, cfg=cfg)
    model_loss = functools.partial(gpt.loss_fn, cfg=cfg)
    axes = gpt.param_logical_axes(cfg)

    data = synthetic_tokens(2_000_000, cfg.vocab_size)

    sample = jnp.zeros((2, cfg.block_size), jnp.int32)
    n_dev = len(jax.devices())
    strategy = None
    if not args.search:
        # default: pure data parallel over all chips
        strategy = Strategy(
            mesh_shape=(("data", n_dev),),
            dtype="float32" if args.smoke else "bfloat16",
            optimizer=args.optimizer,
            micro_batch_size=args.micro_batch_size,
        )
    res = auto_accelerate(
        model_init, model_loss, axes, (sample, sample),
        learning_rate=args.lr, strategy=strategy,
    )

    trainer = ElasticTrainer(
        res.mesh,
        model_loss,
        res.optimizer,
        global_batch_size=args.global_batch_size,
        micro_batch_size=args.micro_batch_size,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    TrainingMonitor.mark_phase("built")

    ckpt_dir = args.checkpoint_dir or os.path.join(
        tempfile.gettempdir(), "dlrover_tpu_nanogpt_ckpt"
    )
    ckpt = Checkpointer(ckpt_dir)
    start_step = 0
    # Pass shardings: the restore then STREAMS — each host fetches
    # only the shard byte-ranges its devices need (engine.py
    # load_streaming), instead of assembling the full state host-side.
    state_shardings = jax.tree.map(
        lambda x: x.sharding, (params, opt_state)
    )
    restored = ckpt.load_checkpoint(
        (params, opt_state), shardings=state_shardings
    )
    if restored is not None:
        params, opt_state = restored
        start_step = ckpt.last_restored_step
        print(f"restored checkpoint at step {start_step}")
    TrainingMonitor.mark_phase("restore_done")

    sampler = ElasticDistributedSampler(
        dataset_size=len(data) - cfg.block_size - 1,
        num_shards=jax_env.num_processes(),
        shard_rank=max(jax_env.process_id(), 0),
        seed=1337,
    )
    trainer.step_num = start_step
    it = iter(sampler)

    def next_batch(n):
        idx = np.fromiter(
            (next(it) for _ in range(n)), np.int64, count=n
        )
        tok = np.stack([data[i : i + cfg.block_size] for i in idx])
        tgt = np.stack(
            [data[i + 1 : i + cfg.block_size + 1] for i in idx]
        )
        return tok, tgt

    # Each process feeds its own shard of the global batch (the
    # sampler is sharded by process); shard_microbatches assembles the
    # global device array from the per-process portions. The prefetch
    # worker gathers + stages batch N+1 while step N computes, so the
    # hot loop below touches host memory only on the logging interval.
    def batch_stream():
        while True:
            yield next_batch(trainer.local_samples_per_step)

    def stage(batch):
        # Device placement under the step's sharding — registered as
        # h2d_fn so the worker delivers committed device arrays and
        # the host/H2D staging split lands in the metrics.
        return trainer.shard_microbatches(*batch)

    batches = make_input_pipeline(
        batch_stream(), h2d_fn=stage, name="nanogpt"
    )

    t0 = time.time()
    tokens_seen = 0
    loss_val = float("nan")  # NaN when fully resumed (no steps left)
    try:
        for step in range(start_step + 1, args.steps + 1):
            tok, tgt = next(batches)
            params, opt_state, loss = trainer.train_step(
                params, opt_state, tok, tgt
            )
            tokens_seen += trainer.samples_per_step * cfg.block_size
            if step == start_step + 1:
                # First step covers the train-step compile.
                TrainingMonitor.mark_phase("first_step_done")
            TrainingMonitor.write_metrics(step, tokens=tokens_seen)
            if step % 10 == 0 or step == args.steps:
                # The ONLY per-interval device->host fetch: the loss
                # lands on the log line, not in every step.
                loss_val = materialize(loss, reason="log")
                dt = time.time() - t0
                print(
                    f"step {step}: loss {loss_val:.4f} "
                    f"({tokens_seen / max(dt, 1e-9):.0f} tok/s)",
                    flush=True,
                )
            if (
                args.checkpoint_every
                and step % args.checkpoint_every == 0
            ):
                ckpt.save_checkpoint(
                    step, (params, opt_state),
                    storage_type=StorageType.DISK,
                )
    finally:
        batches.close()
    # final checkpoint so a restart resumes cleanly
    ckpt.save_checkpoint(
        args.steps, (params, opt_state), storage_type=StorageType.DISK
    )
    ckpt.wait_latest_checkpoint()
    ckpt.close()
    print(f"done: {args.steps} steps, final loss {loss_val:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
