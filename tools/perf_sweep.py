"""Perf sweep harness: times the GPT-2 train step across configs.

Usage:
  python tools/perf_sweep.py 'remat,flash,batch[,bq,bk[,bqb,bkb]]'
  remat: full | attn | none | dots | offload
  flash: flash | xla | noop (noop stubs attention to measure the
         step's non-attention cost by subtraction)
  bqb,bkb: backward-kernel block sizes (default = forward blocks)

Prints one line per config: config, step ms, MFU, vs_baseline.
"""

from __future__ import annotations

import dataclasses
import os
import functools
import sys
import time

import _repo_path  # noqa: F401

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.models import gpt
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.step import (
    make_sharded_init,
    make_train_step,
    shard_batch,
)

PEAK = 197e12
REF_HFU = 0.496


def is_unroll_token(p: str) -> bool:
    """"uK" scan-unroll flag token. Shared with capture_perf's
    winner_env so spec parsing and env pinning can't desynchronize."""
    return len(p) > 1 and p[0] == "u" and p[1:].isdigit()


def is_xent_token(p: str) -> bool:
    """"xcN" fused-CE chunk-count flag token (same sharing rule)."""
    return len(p) > 2 and p[:2] == "xc" and p[2:].isdigit()


def build_spec(spec: str):
    """Parse a sweep spec -> (cfg, attn_fn, batch, xent_chunks).
    Omitted fields default to flash attention with the kernel's own
    autotuned block sizes and batch 16; xent_chunks resolves here
    (xcN token, else SWEEP_XENT_CHUNKS, else 8) so every caller sees
    one value."""
    parts = spec.split(",")
    # "uK" and "xcN" are flag tokens, not positional: stripped before
    # the positional fields so they work anywhere in the spec.
    # "uK" (e.g. u2, u4): lax.scan unroll factor for the layer stack.
    # "xcN" (e.g. xc4): fused-CE chunk count (r5 trace: the f32 dwte
    # accumulator is re-read/written once per chunk — 144 MB x chunks
    # of pure accumulator traffic at GPT-2 vocab).
    unroll, xent_chunks = 1, None
    for p in parts:
        if is_unroll_token(p):
            unroll = int(p[1:])
        elif is_xent_token(p):
            xent_chunks = int(p[2:])
    if xent_chunks is None:  # token absent — env fallback, then 8
        xent_chunks = int(os.getenv("SWEEP_XENT_CHUNKS", "8"))
    parts = [
        p for p in parts
        if not is_unroll_token(p) and not is_xent_token(p)
    ]
    remat_s = parts[0]
    flash_s = parts[1] if len(parts) > 1 else "flash"
    batch = int(parts[2]) if len(parts) > 2 else 16
    def _blk(i):
        # "-" (or absence) = kernel default for any block field
        if len(parts) <= i or parts[i] == "-":
            return None
        return int(parts[i])

    block_q = _blk(3)
    block_k = _blk(4)
    block_q_bwd = _blk(5)
    block_k_bwd = _blk(6)
    remat = {
        "full": True, "attn": "attention", "none": False,
        "dots": "dots", "offload": "offload",
    }[remat_s]
    use_flash = flash_s == "flash"

    cfg = dataclasses.replace(
        gpt.GPTConfig.gpt2(), remat=remat,
        use_flash_attention=use_flash, scan_unroll=unroll,
    )
    attn_fn = None
    if flash_s == "noop":
        # Attention stubbed to identity-on-v: measures the step's
        # non-attention cost by subtraction.
        attn_fn = lambda q, k, v: v  # noqa: E731
    elif use_flash:
        from dlrover_tpu.ops.flash_attention import flash_attention

        # block_q/block_k None -> default_block_sizes autotuning
        attn_fn = functools.partial(
            flash_attention, causal=True, block_q=block_q,
            block_k=block_k, block_q_bwd=block_q_bwd,
            block_k_bwd=block_k_bwd,
        )
    return cfg, attn_fn, batch, xent_chunks


def run_config(mesh, spec: str) -> None:
    cfg, attn_fn, batch, spec_chunks = build_spec(spec)

    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    # Fused-CE chunk count (bigger chunks = bigger gradient matmuls
    # and fewer dwte accumulator round-trips, more logits HBM at
    # once); fully resolved by build_spec.
    chunks = spec_chunks
    loss = functools.partial(
        gpt.loss_fn_fused, cfg=cfg, attn_fn=attn_fn, num_chunks=chunks,
    )
    init, _ = make_sharded_init(
        mesh,
        functools.partial(gpt.init_params, cfg=cfg),
        gpt.param_logical_axes(cfg),
        optimizer,
    )
    params, opt_state = init(jax.random.PRNGKey(0))
    step = make_train_step(mesh, loss, optimizer)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, cfg.block_size), 0, cfg.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    tokens, targets = shard_batch(mesh, tokens, targets)

    try:
        for _ in range(3):
            params, opt_state, metrics = step(
                params, opt_state, tokens, targets
            )
        float(metrics["loss"])
        n_steps = 10
        t0 = time.time()
        for _ in range(n_steps):
            params, opt_state, metrics = step(
                params, opt_state, tokens, targets
            )
        float(metrics["loss"])
        dt = (time.time() - t0) / n_steps
    except Exception as e:  # noqa: BLE001
        print(f"{spec:32s} FAILED: {type(e).__name__}: {str(e)[:120]}")
        return
    tok_s = batch * cfg.block_size / dt
    mfu = tok_s * gpt.flops_per_token(cfg) / PEAK
    print(
        f"{spec:32s} step={dt*1000:7.1f}ms tok/s={tok_s:9.0f} "
        f"mfu={mfu:.3f} vs={mfu/REF_HFU:.3f}",
        flush=True,
    )


def main():
    mesh = build_mesh(MeshConfig(data=len(jax.devices())))
    for spec in sys.argv[1:]:
        run_config(mesh, spec)


if __name__ == "__main__":
    main()
