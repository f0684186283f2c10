"""Microbenchmark: flash-attention kernel vs XLA attention, fwd+bwd.

Usage: python tools/attn_bench.py [B T H D] [--window W]

--window adds sliding-window rows (band W) to the sweep: expected
speedup over full causal approaches T/(2W) as T grows (dead kv blocks
are skipped, ops/flash_attention.py _dispatch_block).
"""

from __future__ import annotations

import functools
import sys
import time

import _repo_path  # noqa: F401

import jax
import jax.numpy as jnp

from dlrover_tpu.models.gpt import _default_attention
from dlrover_tpu.ops.flash_attention import flash_attention


def timeit(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    # one readback, so the warm-up has really finished
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
    return (time.time() - t0) / n


def main():
    args = sys.argv[1:]
    window = None
    if "--window" in args:
        i = args.index("--window")
        try:
            window = int(args[i + 1])
        except (IndexError, ValueError):
            sys.exit("usage: attn_bench.py [B T H D] --window <int>")
        del args[i:i + 2]
    B, T, H, D = 16, 1024, 12, 64
    if len(args) >= 4:
        B, T, H, D = map(int, args[:4])
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)
    do = jax.random.normal(kg, (B, T, H, D), jnp.bfloat16)

    # Attention matmul FLOPs: fwd 4*B*H*T*T*D, bwd 2x+recompute.
    fwd_fl = 4 * B * H * T * T * D
    causal = 0.5  # causal effectively halves useful work

    def bench(name, attn):
        f = jax.jit(attn)
        vjp_f = jax.jit(
            lambda q, k, v, do: jax.vjp(attn, q, k, v)[1](do)
        )
        tf = timeit(f, q, k, v)
        tb = timeit(vjp_f, q, k, v, do)
        print(
            f"{name:28s} fwd={tf*1e3:7.2f}ms ({fwd_fl/tf/1e12:6.1f} TF/s "
            f"dense) bwd+fwd={tb*1e3:7.2f}ms",
            flush=True,
        )

    bench("xla", functools.partial(_default_attention, causal=True))
    for bq, bk in [(128, 128), (256, 256), (512, 512), (256, 512),
                   (512, 256), (1024, 128), (128, 1024)]:
        bench(
            f"flash bq={bq} bk={bk}",
            functools.partial(
                flash_attention, causal=True, block_q=bq, block_k=bk
            ),
        )
    if window is not None:
        for bq, bk in [(128, 128), (256, 256), (512, 512)]:
            bench(
                f"flash W={window} bq={bq} bk={bk}",
                functools.partial(
                    flash_attention, causal=True, window=window,
                    block_q=bq, block_k=bk,
                ),
            )


if __name__ == "__main__":
    main()
