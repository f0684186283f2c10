"""Static model analysis: params, FLOPs, memory — no execution.

The reference's analyser (atorch/auto/analyser/analyser.py:327LoC)
walks torch modules; here everything comes from ``jax.eval_shape``
(param/activation shapes without running) and an analytic transformer
FLOPs model, so analysis is instant even for 100B-param configs. Used
to prune strategy candidates before the (expensive: compile-dominated)
dry-runs — the reference has the same compile-cost problem with
dynamo, we just say it out loud.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.accelerate.strategy import Strategy

# HBM per chip by generation (GiB); conservative defaults.
HBM_BYTES = {
    "v4": 32 << 30,
    "v5e": 16 << 30,
    "v5p": 95 << 30,
    "v6e": 32 << 30,
}
DEFAULT_HBM = 16 << 30


@dataclasses.dataclass
class ModelAnalysis:
    n_params: int
    param_bytes_f32: int
    largest_leaf: int

    def param_bytes(self, dtype: str) -> int:
        itemsize = 2 if dtype in ("bfloat16", "float16") else 4
        return self.n_params * itemsize


def analyse_model(
    init_fn: Callable[[jax.Array], Any]
) -> ModelAnalysis:
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    n = sum(int(np.prod(l.shape)) for l in leaves)
    return ModelAnalysis(
        n_params=n,
        param_bytes_f32=4 * n,
        largest_leaf=max(int(np.prod(l.shape)) for l in leaves),
    )


_OPT_STATE_MULT = {
    # moment bytes per param byte (f32 master basis)
    "adamw": 2.0,
    "agd": 2.0,
    "adam8bit": 0.55,  # int8 m + int8 sqrt(v) + scales
    "adam4bit": 0.3,  # packed nibbles + scales
    "sgd": 0.0,
}


def estimate_step_memory(
    analysis: ModelAnalysis,
    strategy: Strategy,
    activation_bytes_per_sample: int,
    hbm_bytes: int = DEFAULT_HBM,
) -> Tuple[int, bool]:
    """(estimated bytes per device, fits) — the pre-filter the
    reference lacks (its dry-runner discovers OOM by running,
    dry_runner.py 'profile')."""
    mesh = strategy.mesh_dict
    model_shards = (
        mesh.get("fsdp", 1) * mesh.get("tensor", 1) * mesh.get("pipe", 1)
    )
    p_bytes = analysis.param_bytes(strategy.dtype) / model_shards
    # grads same dtype as params; optimizer state in f32 basis
    g_bytes = p_bytes
    o_bytes = (
        analysis.param_bytes_f32
        * _OPT_STATE_MULT.get(strategy.optimizer, 2.0)
        / model_shards
    )
    # Pipe note: 1F1B (parallel/pipeline.py) keeps up to `pipe`
    # microbatches in flight, each resident for 1/pipe of the layers —
    # activation residency stays ~the full-model single-microbatch
    # figure, so act is deliberately NOT divided by pipe. (GPipe-style
    # scheduling would multiply it by n_micro/pipe instead; the
    # framework's scheduler is 1F1B.)
    act = activation_bytes_per_sample * strategy.micro_batch_size
    from dlrover_tpu.accelerate.remat import canonical

    remat = canonical(strategy.remat)
    if remat == "full":
        act = act * 0.2  # block-boundary activations only
    elif remat == "dots":
        # dots_saveable keeps EVERY dot output, including batch-dim
        # attention scores on the non-flash path — residency is close
        # to no-remat, only elementwise intermediates are recomputed
        act = act * 0.9
    elif remat == "offload":
        act = act * 0.1  # boundaries live in host RAM, not HBM
    elif remat == "attention":
        act = act * 0.6  # attention internals recomputed
    total = int(p_bytes + g_bytes + o_bytes + act)
    # 20% headroom for XLA temp buffers / fragmentation
    return total, total < hbm_bytes * 0.8


def transformer_flops_per_token(
    n_params_matmul: int, n_layer: int, seq_len: int, n_embd: int
) -> float:
    """PaLM convention: 6N + 12*L*T*E (fwd+bwd attention term)."""
    return 6.0 * n_params_matmul + 12.0 * n_layer * seq_len * n_embd


def compiled_cost(fn, *args) -> Dict[str, float]:
    """FLOPs/bytes from XLA's own cost model for a jitted fn — the
    accurate path used to sanity-check the analytic numbers."""
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return {
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
    }
