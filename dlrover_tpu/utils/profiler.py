"""Profiler: FLOPs / memory / wall-time / MFU for jitted functions.

Parity with atorch's AProfiler (atorch/utils/prof.py:39 — module-hook
profiler with 60+ hand-written per-op FLOPs formulas). The JAX route
is structurally better: XLA's own cost model (``compiled.cost_analysis``)
prices every fused op after optimization, so there are no formulas to
maintain — we keep one analytic transformer model only to sanity-check
the compiler numbers and to attribute cost per component the way the
reference attributes per module.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

# Peak bf16 TFLOP/s and HBM GB/s per chip by generation. The single
# source of truth — bench.py, the MFU gauge and the module profiler
# read these tables. Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s
# bf16 and 819 GB/s HBM per chip; likewise "TPU v4", "TPU v5p",
# "TPU v6e").
PEAK_TFLOPS = {"v4": 275.0, "v5e": 197.0, "v5p": 459.0, "v6e": 918.0}
PEAK_HBM_GBPS = {
    "v4": 1228.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6e": 1640.0,
}
# jax.devices()[0].device_kind -> generation.
DEVICE_KIND_GENERATION = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


def chip_generation() -> Optional[str]:
    """The attached TPU's generation; None off a TPU backend. A TPU
    whose device kind is not in the table is an error, not a default:
    a utilisation against another chip's peak is a wrong number."""
    if jax.default_backend() != "tpu":
        return None
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_KIND_GENERATION:
        raise ValueError(
            f"no peaks recorded for device kind {kind!r}; add it to "
            "utils/profiler.py with its source"
        )
    return DEVICE_KIND_GENERATION[kind]


def chip_peaks(default: Optional[str] = None) -> Tuple[float, float]:
    """(peak TFLOP/s, peak HBM GB/s) of the attached TPU. Off a TPU
    backend, those of ``default``, the generation the caller plans
    for — nobody inherits one in silence."""
    gen = chip_generation() or default
    if gen is None:
        raise ValueError(
            f"backend {jax.default_backend()!r} is not a TPU: name "
            "the generation to plan for, "
            f"one of {sorted(PEAK_TFLOPS)}"
        )
    return PEAK_TFLOPS[gen], PEAK_HBM_GBPS[gen]


@dataclasses.dataclass
class FnProfile:
    flops: float  # per call, from XLA cost analysis
    bytes_accessed: float
    peak_memory_bytes: int
    wall_time_s: float  # measured per call
    achieved_tflops: float
    mfu: Optional[float]  # vs chip peak, None off-TPU
    arithmetic_intensity: float  # flops / byte

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _device_peak_tflops() -> Optional[float]:
    gen = chip_generation()
    return None if gen is None else PEAK_TFLOPS[gen]


def profile_fn(
    fn: Callable,
    *args,
    iters: int = 10,
    static_argnums: Tuple[int, ...] = (),
) -> FnProfile:
    """Compile fn, read XLA's cost/memory analysis, time real calls."""
    jfn = jax.jit(fn, static_argnums=static_argnums)
    lowered = jfn.lower(*args)
    compiled = lowered.compile()

    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    peak_mem = 0
    try:
        mem = compiled.memory_analysis()
        peak_mem = int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
        )
    except Exception:  # noqa: BLE001 — backend-dependent
        pass

    out = jfn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / iters

    achieved = flops / wall / 1e12 if wall > 0 else 0.0
    peak = _device_peak_tflops()
    return FnProfile(
        flops=flops,
        bytes_accessed=bytes_accessed,
        peak_memory_bytes=peak_mem,
        wall_time_s=wall,
        achieved_tflops=achieved,
        mfu=(achieved / peak) if peak else None,
        arithmetic_intensity=(
            flops / bytes_accessed if bytes_accessed else 0.0
        ),
    )


def transformer_component_flops(
    n_layer: int,
    n_embd: int,
    seq_len: int,
    vocab_size: int,
    batch: int = 1,
    backward: bool = True,
) -> Dict[str, float]:
    """Analytic per-component attribution (the reference's per-module
    breakdown, prof.py:490+): forward matmul FLOPs x3 for fwd+bwd."""
    mult = 6.0 if backward else 2.0  # 2 FLOPs/MAC, x3 with backward
    tokens = batch * seq_len
    qkv_o = 4 * n_embd * n_embd  # wqkv (3E^2) + wo (E^2)
    mlp = 8 * n_embd * n_embd  # wi (4E^2) + wo2 (4E^2)
    attn_scores = 2 * seq_len * n_embd  # qk^T + pv per token
    return {
        "attention_proj": mult * tokens * n_layer * qkv_o,
        "attention_scores": mult * tokens * n_layer * attn_scores,
        "mlp": mult * tokens * n_layer * mlp,
        "unembedding": mult * tokens * vocab_size * n_embd,
    }


def summarize(profile: FnProfile, name: str = "fn") -> str:
    lines = [
        f"profile[{name}]: {profile.flops/1e9:.2f} GFLOP/call, "
        f"{profile.bytes_accessed/1e6:.1f} MB accessed "
        f"(AI={profile.arithmetic_intensity:.1f} flop/B)",
        f"  wall {profile.wall_time_s*1e3:.2f} ms -> "
        f"{profile.achieved_tflops:.2f} TFLOP/s"
        + (
            f" (MFU {profile.mfu*100:.1f}%)"
            if profile.mfu is not None
            else ""
        ),
        f"  peak memory {profile.peak_memory_bytes/(1<<20):.1f} MiB",
    ]
    return "\n".join(lines)
