"""Long-context attention benchmark on the real chip.

Long context is first-class (SURVEY §5): the flash kernel must hold
its throughput as T grows — an O(T^2)-HBM attention would OOM where
flash is merely compute-bound, and the sliding-window band should
approach T/(2W) speedup as dead kv blocks are skipped. This measures
flash fwd+bwd at long T (GPT-2-shaped heads) plus the banded variant,
and writes LONGCTX_r05.json.

Run:  python tools/longctx_bench.py [--max-t 32768]
"""

from __future__ import annotations

import json
import os
import sys
import time

import _repo_path  # noqa: F401

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
import jax.numpy as jnp

from dlrover_tpu.ops.flash_attention import flash_attention


def bench(f, *args, n=10):
    out = f(*args)
    jax.block_until_ready(out)
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
    t0 = time.time()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
    return (time.time() - t0) / n


def main() -> int:
    max_t = 32768
    # --cpu-check: one tiny size, plumbing only (interpret-mode flash
    # at real long-context sizes is infeasible on CPU).
    cpu_check = "--cpu-check" in sys.argv
    for i, a in enumerate(sys.argv):
        if a == "--max-t":
            max_t = int(sys.argv[i + 1])
    h, d = 12, 64  # GPT-2 heads
    results = []
    t = 128 if cpu_check else 4096
    if cpu_check:
        max_t = t
    while t <= max_t:
        b = 1 if cpu_check else max(1, 32768 // t)
        q, k, v = (
            jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
            for kk in jax.random.split(jax.random.PRNGKey(0), 3)
        )

        def fwd_bwd(window=None):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(
                        q, k, v, causal=True, window=window
                    ).astype(jnp.float32) ** 2
                )

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        row = {"t": t, "batch": b, "heads": h, "head_dim": d}
        # Progress marker BEFORE the compile: the r5 run died
        # mid-compile at t=8192 and the log couldn't say where.
        print(f"# t={t} b={b}: compiling + timing full...", flush=True)
        try:
            dt = bench(fwd_bwd(), q, k, v, n=1 if cpu_check else 10)
            # causal flash fwd+bwd ~ 3.5 * (T^2/2) * H * D * 2*B FLOPs
            flops = 3.5 * 0.5 * t * t * h * d * 2 * b * 2
            row["full_ms"] = round(dt * 1e3, 2)
            row["full_tflops"] = round(flops / dt / 1e12, 1)
            w = 4096
            if t > w:
                print(f"# t={t}: windowed (w={w})...", flush=True)
                dtw = bench(fwd_bwd(window=w), q, k, v)
                row["window4k_ms"] = round(dtw * 1e3, 2)
                row["window_speedup"] = round(dt / dtw, 2)
        except Exception as exc:  # noqa: BLE001
            row["error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
        results.append(row)
        print(json.dumps(row), flush=True)
        # Checkpoint after every size: the r5 run died mid-compile
        # at t=8192 and the all-at-the-end write lost the measured
        # t=4096 row with it. The round artifact still only lands
        # once a >=8k row has real numbers (the VERDICT bar); shorter
        # partials go to /tmp so a retry can see what happened.
        on_tpu = jax.default_backend() == "tpu"
        out = {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "results": results,
        }
        landed = any(
            r.get("full_ms") and r["t"] >= 8192 for r in results
        )
        path = (
            "LONGCTX_r05.json" if (on_tpu and not cpu_check and landed)
            else "/tmp/longctx_partial.json" if not cpu_check
            else "/tmp/longctx_check.json"
        )
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        t *= 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
