"""Autotune the flash-attention BACKWARD block sizes on real hardware.

Sweeps (block_q_bwd, block_k_bwd) over the divisibility-chain-valid
grid at the shipped forward blocks (1024/1024 — the r4 sweep
optimum), full remat, batch 18,
fused CE without saved logits — the bench.py configuration —
and prints the ranked results with the winning bench spec.

Run (TPU):  python tools/autotune_bwd_blocks.py [--quick]
Each config costs one compile (~20-40 s cold; the persistent compile
cache makes re-runs cheap) + ~2 s of measurement.
"""

from __future__ import annotations

import argparse
import math
import sys

import _repo_path  # noqa: F401


def valid_chain(blocks) -> bool:
    return math.lcm(*blocks) <= 2 * max(blocks)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="only the most promising half of the grid")
    p.add_argument("--fwd", default="1024,1024",
                   help="forward block_q,block_k")
    args = p.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("warning: not on TPU; timings are meaningless",
              file=sys.stderr)

    from perf_sweep import run_config  # noqa: E402
    from dlrover_tpu.parallel.mesh import (  # noqa: E402
        MeshConfig,
        build_mesh,
    )

    bq, bk = (int(x) for x in args.fwd.split(","))
    candidates = []
    sizes = (128, 256, 512, 1024)
    for bqb in sizes:
        for bkb in sizes:
            if args.quick and (bqb < 256 or bkb < 256):
                continue
            if valid_chain((bq, bk, bqb, bkb)):
                candidates.append((bqb, bkb))

    mesh = build_mesh(MeshConfig(data=len(jax.devices())))
    # Machine-readable device count: spec batch is GLOBAL for this
    # mesh, bench.py's BENCH_BATCH_PER_CHIP is per-chip — the capture
    # tool needs this line to convert units when pinning a winner.
    print(f"n_devices: {len(jax.devices())}")
    print(f"sweeping {len(candidates)} bwd-block configs at "
          f"fwd {bq}/{bk}")
    # The baseline first: the shipped blocks, every lever at rest.
    run_config(mesh, f"full,flash,18,{bq},{bk}")
    # Layer-scan unroll sweep: the r5 step profile attributes ~16% of
    # step time to scan-carry dynamic-update-slice fusions; unrolling
    # lets XLA fuse across layers at the cost of program size. Also
    # re-check the remat choice at the unrolled optimum — the
    # full-remat win was measured rolled.
    for u in (2, 3, 4, 6, 12):
        run_config(mesh, f"full,flash,18,{bq},{bk},u{u}")
    run_config(mesh, f"none,flash,18,{bq},{bk},u4")
    run_config(mesh, f"dots,flash,18,{bq},{bk},u4")
    # Fused-CE chunk count: the r5 trace prices the CE loops at
    # 35.5 ms/step with the f32 dwte accumulator re-read per chunk;
    # fewer chunks trade accumulator round-trips for logits HBM.
    for xc in (2, 4, 16):
        run_config(mesh, f"full,flash,18,{bq},{bk},xc{xc}")
    # Lever combinations: each pair/triple, so the winner isn't
    # hostage to one lever losing on hardware.
    run_config(mesh, f"full,flash,18,{bq},{bk},u4,xc4")
    # Batch interacts with the memory knobs (a small xc holds bigger
    # logits): re-check the b18 optimum one notch up and down on the
    # combined candidate.
    run_config(mesh, f"full,flash,20,{bq},{bk},u4,xc4")
    run_config(mesh, f"full,flash,16,{bq},{bk},u4,xc4")
    for bqb, bkb in candidates:
        run_config(mesh, f"full,flash,18,{bq},{bk},{bqb},{bkb}")
    print("pick the fastest line; bench.py BENCH_* env then pins it")
    return 0


if __name__ == "__main__":
    sys.exit(main())
