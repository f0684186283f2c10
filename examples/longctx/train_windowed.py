"""Long-context training demo: sliding-window attention + GQA +
sequence parallelism in one script.

Trains a Mistral-shaped tiny model (grouped-query attention, sliding-
window band) with the sequence dimension sharded over a ``seq`` mesh
axis — the round-5 long-context surface end to end:

* the windowed flash ring statically skips band-dead ring hops
  (O(T*window/shards) attention work, O(window) ICI traffic per
  device — parallel/ring_attention.py);
* K/V rides the ring COMPACT (n_kv_head tensors, 1/q_per_kv the
  ppermute bytes — the constructors advertise ``supports_gqa`` and
  the model skips its pre-broadcast);
* strategy/mesh wiring through auto_accelerate, which forwards
  ``cfg.sliding_window`` into the seq-parallel binding.

Hermetic synthetic data (shifted-structure token stream). Runs on the
virtual CPU mesh or a real TPU slice (the --smoke CPU run uses the
XLA ring — mask-only, so it exercises the windowed MATH; the static
band-dead hop skipping is the flash ring's, which smoke-interpret CPU
runs are too slow to demo — see tests/test_parallel.py's jaxpr hop
assertions for that property):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/longctx/train_windowed.py --smoke

Reference contrast: the reference's long-sequence path is blockwise
SP over allgather/reduce-scatter with full-causal cost
(atorch/modules/distributed_transformer/distributed_attention.py);
there is no banded/windowed sharded attention there at all.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny dims, 6 steps (CI / CPU mesh)")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--seq-shards", type=int, default=2)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.models import llama

    steps = args.steps or (6 if args.smoke else 60)
    if args.seq_shards < 1:
        raise SystemExit(
            f"--seq-shards must be >= 1, got {args.seq_shards}"
        )
    n_dev = len(jax.devices())
    seq_n = min(args.seq_shards, n_dev)
    data_n = n_dev // seq_n

    if args.smoke:
        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(),       # GQA 4:2 heads
            block_size=128,
            sliding_window=24,              # band spans 2+ ring blocks
            use_flash_attention=False,      # CPU mesh: XLA ring path
        )
        batch = 2 * data_n
    else:
        # Mistral-tiny: 4:1 GQA, 4k band inside an 8k context — the
        # regime where band-dead hop skipping and compact-KV rotation
        # actually bind.
        cfg = llama.LlamaConfig(
            vocab_size=32000, block_size=8192, n_layer=8, n_head=16,
            n_kv_head=4, n_embd=1024, intermediate=3584,
            dtype=jnp.bfloat16, sliding_window=4096, remat=True,
        )
        batch = max(data_n, 1)

    init = functools.partial(llama.init_params, cfg=cfg)
    loss = functools.partial(llama.loss_fn, cfg=cfg)
    axes = llama.param_logical_axes(cfg)
    strategy = Strategy(
        mesh_shape=(("data", data_n), ("seq", seq_n)),
        dtype="float32" if args.smoke else "bfloat16",
        micro_batch_size=batch,
        seq_impl="ring",
    )
    sample = jnp.zeros((batch, cfg.block_size), jnp.int32)
    res = auto_accelerate(
        init, loss, axes, (sample, sample), strategy=strategy,
        devices=jax.devices()[:n_dev],
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))

    def batch_at(i):
        # Learnable structure: segments are affine transforms of a
        # shared base stream, so loss decreases (uniform-random
        # tokens would floor at log V).
        key = jax.random.PRNGKey(100 + i)
        base = jax.random.randint(
            key, (batch, cfg.block_size // 4), 0, cfg.vocab_size // 4
        )
        toks = jnp.concatenate(
            [base, (2 * base + 1) % cfg.vocab_size,
             (3 * base + 5) % cfg.vocab_size, base],
            axis=1,
        )
        return res.shard_batch_fn(toks, jnp.roll(toks, -1, axis=1))

    batches = [batch_at(j) for j in range(min(4, steps))]
    first = last = None
    for i in range(steps):
        tok, tgt = batches[i % len(batches)]
        params, opt_state, m = res.step_fn(params, opt_state, tok, tgt)
        loss_v = float(m["loss"])
        first = loss_v if first is None else first
        last = loss_v
        if i % max(steps // 6, 1) == 0 or i == steps - 1:
            print(f"step {i:4d} loss {loss_v:.4f}", flush=True)

    print(f"windowed seq-sharded training: loss {first:.4f} -> "
          f"{last:.4f} over {steps} steps "
          f"(mesh data={data_n} seq={seq_n}, window="
          f"{cfg.sliding_window}, kv_heads={cfg.n_kv_head}/"
          f"{cfg.n_head})")
    # Too few steps to expect monotone progress; the demo's loss
    # contract only binds on a real (>= 4 step) run.
    assert steps < 4 or last < first, "loss did not decrease"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
