"""Per-module cost attribution and roofline step-time prediction.

Parity with atorch's AProfiler (atorch/utils/prof.py:39,490 — a
module-hook profiler with 60+ hand-written per-op FLOPs formulas that
feeds the strategy engine). The JAX reformulation attributes cost by
walking the *jaxpr*: every equation carries the ``jax.named_scope``
stack it was traced under, so a model annotated with scopes gets exact
per-module FLOPs / memory-traffic / activation-size attribution with a
handful of per-primitive formulas (JAX has few primitives, unlike the
reference's 60+ torch ops) — no hooks, no execution, no compilation.

Two consumers, mirroring the reference:

* the strategy engine (``auto_accelerate``) ranks candidates by
  :func:`predict_step_time` — a roofline estimate from profiled totals
  with the strategy's sharding/remat/dtype factors applied — so the
  Bayesian search dry-runs the likely-best candidates first and needs
  fewer compiles to find the winner;
* the TP planner consumes per-scope activation bytes
  (``ModuleCost.out_bytes``) as per-edge costs instead of one global
  activation-size guess.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.extend import core as jax_core

from dlrover_tpu.common.log import get_logger

logger = get_logger("module_profiler")

from dlrover_tpu.utils.profiler import chip_peaks  # noqa: E402


@dataclasses.dataclass
class ModuleCost:
    """Aggregated cost of all equations attributed to one scope."""

    flops: float = 0.0
    # Memory-traffic proxy: operand + result bytes of every equation.
    bytes: float = 0.0
    # Result bytes only — the activations this scope emits (per-edge
    # cost input for the TP planner).
    out_bytes: float = 0.0
    eqns: int = 0

    def add(self, flops: float, in_bytes: float, out_bytes: float):
        self.flops += flops
        self.bytes += in_bytes + out_bytes
        self.out_bytes += out_bytes
        self.eqns += 1


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _aval_bytes(var) -> float:
    aval = getattr(var, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0.0
    try:
        return float(_prod(aval.shape) * aval.dtype.itemsize)
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0.0


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(
        d for i, d in enumerate(lhs) if i not in lb and i not in lc
    )
    n = _prod(
        d for i, d in enumerate(rhs) if i not in rb and i not in rc
    )
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_features = rhs.shape[dn.rhs_spec[0]]
    macs_per_out = _prod(rhs.shape) / max(out_features, 1)
    return 2.0 * _prod(out.shape) * macs_per_out


# Transform wrappers the name stack acquires under jit/grad/vmap —
# these are not user scopes and are stripped during attribution.
# 'rematted_computation' is the scope jax.checkpoint's transposition
# inserts around the recompute; cost-wise it belongs to the original
# module scopes nested under it.
# 'layers' and 'accumulate' are the program's own scopes around its
# scans (models/, trainer/elastic_trainer.py), there for the device
# profile's reader (obs.profiling.compiled_scopes); here cost belongs
# to the module scopes inside them, under the keys it always had.
_TRANSFORM_RE = re.compile(r"\b(?:jvp|transpose|vmap|mask)\(")
_SYNTH_SCOPES = (
    "rematted_computation", "checkpoint", "layers", "accumulate",
)


def _user_scope(name_stack: Any) -> str:
    """'transpose(jvp(block/attn))' -> 'block/attn'."""
    s = str(name_stack)
    if not s:
        return ""
    s = _TRANSFORM_RE.sub("", s).replace(")", "")
    parts = [
        p for p in s.split("/") if p and p not in _SYNTH_SCOPES
    ]
    return "/".join(parts)


def _sub_jaxprs(eqn):
    """(jaxpr, multiplier) pairs nested in an equation's params.

    cond branches all contribute (an upper bound — only one runs, but
    for transformer stacks branches are rare and similar)."""
    out = []
    for key, val in eqn.params.items():
        mult = 1.0
        if key == "jaxpr" and eqn.primitive.name == "scan":
            mult = float(eqn.params.get("length", 1) or 1)
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                out.append((v.jaxpr, mult))
            elif isinstance(v, jax_core.Jaxpr):
                out.append((v, mult))
    return out


def _walk(jaxpr, costs: Dict[str, ModuleCost], prefix: str,
          mult: float) -> None:
    for eqn in jaxpr.eqns:
        scope = _user_scope(eqn.source_info.name_stack)
        scope = "/".join(p for p in (prefix, scope) if p)
        subs = _sub_jaxprs(eqn)
        if subs:
            for sub, sub_mult in subs:
                _walk(sub, costs, scope, mult * sub_mult)
            continue
        prim = eqn.primitive.name
        if prim == "dot_general":
            flops = _dot_general_flops(eqn)
        elif prim == "conv_general_dilated":
            flops = _conv_flops(eqn)
        else:
            # Nominal 1 FLOP/element for everything else — exact for
            # add/mul, an undercount for transcendentals, irrelevant
            # next to the matmul terms this prior ranks by.
            flops = float(
                sum(_prod(v.aval.shape) for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
            )
        in_bytes = sum(_aval_bytes(v) for v in eqn.invars)
        out_bytes = sum(_aval_bytes(v) for v in eqn.outvars)
        costs.setdefault(scope or "<root>", ModuleCost()).add(
            mult * flops, mult * in_bytes, mult * out_bytes
        )


def profile_modules(
    fn: Callable,
    *args,
    grad: bool = False,
    top_level_only: bool = False,
) -> Dict[str, ModuleCost]:
    """Attribute FLOPs / bytes to the ``jax.named_scope`` tree of fn.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` trees
    (abstract tracing — nothing executes). ``grad=True`` profiles
    ``value_and_grad(fn)`` so backward cost lands on the same scopes
    (the jaxpr's transpose equations keep their forward scope names).
    ``top_level_only`` collapses 'block/attn/softmax' -> 'block'.
    """
    target = jax.value_and_grad(fn) if grad else fn
    closed = jax.make_jaxpr(target)(*args)
    costs: Dict[str, ModuleCost] = {}
    _walk(closed.jaxpr, costs, "", 1.0)
    if top_level_only:
        merged: Dict[str, ModuleCost] = {}
        for scope, c in costs.items():
            top = scope.split("/", 1)[0]
            m = merged.setdefault(top, ModuleCost())
            m.flops += c.flops
            m.bytes += c.bytes
            m.out_bytes += c.out_bytes
            m.eqns += c.eqns
        return merged
    return costs


def total_cost(costs: Dict[str, ModuleCost]) -> ModuleCost:
    total = ModuleCost()
    for c in costs.values():
        total.flops += c.flops
        total.bytes += c.bytes
        total.out_bytes += c.out_bytes
        total.eqns += c.eqns
    return total


def summarize(costs: Dict[str, ModuleCost]) -> str:
    total = total_cost(costs)
    lines = []
    for scope, c in sorted(
        costs.items(), key=lambda kv: -kv[1].flops
    ):
        share = c.flops / total.flops * 100 if total.flops else 0.0
        lines.append(
            f"{scope:<32} {c.flops/1e9:10.2f} GFLOP ({share:5.1f}%) "
            f"{c.bytes/1e6:10.1f} MB  {c.eqns:5d} eqns"
        )
    lines.append(
        f"{'TOTAL':<32} {total.flops/1e9:10.2f} GFLOP          "
        f"{total.bytes/1e6:10.1f} MB  {total.eqns:5d} eqns"
    )
    return "\n".join(lines)


# -- roofline step-time prior for the strategy engine ------------------

# FLOPs multiplier of rematerialization policies (recompute cost on
# top of the fwd+bwd 3x base: full block remat re-runs the forward,
# +1/3 (less the flash forward kernel where the block has one: its
# kept (o, lse) feed the backward; about a quarter of a block's
# forward at GPT-2's shapes); attention/dots recompute a slice of it).
_REMAT_FLOPS_FACTOR = {
    "none": 1.0,
    "full": 4.0 / 3.0,
    "attention": 1.08,
    "dots": 1.12,
    "offload": 1.0,
}

_DTYPE_BYTES_FACTOR = {"bfloat16": 1.0, "float32": 2.0, "half": 1.0}


# Aggregate ICI bandwidth per chip for inter-device collectives,
# GB/s. Order-of-magnitude (v5e ~ 4x ~400Gbps links); only the RATIO
# against HBM bandwidth matters for ranking.
DEFAULT_ICI_GBPS = 90.0


def predict_step_time(
    per_sample: ModuleCost,
    strategy,
    n_devices: int,
    peak_tflops: Optional[float] = None,
    peak_hbm_gbps: Optional[float] = None,
    param_bytes: Optional[int] = None,
    ici_gbps: float = DEFAULT_ICI_GBPS,
) -> float:
    """Roofline estimate of one train-step's seconds for a strategy.

    ``per_sample`` is the fwd+bwd cost of ONE sample at base dtype
    (``profile_modules(..., grad=True)`` totals divided by the traced
    batch). The strategy's factors are applied analytically:
    micro-batch scales work, every mesh axis shards it, remat
    multiplies FLOPs, the dtype policy scales memory traffic. Absolute
    numbers are rough; the RANKING is what seeds the search.

    With ``param_bytes`` the estimate adds per-step ICI time — the
    term that separates the parallelism FAMILIES: data/fsdp axes
    re-synchronize parameters/gradients every step (traffic scales
    with model size), pipe ships only stage-boundary activations but
    pays the 1F1B bubble (n_micro/(n_micro+P-1) efficiency at the
    n_micro=2P convention parallel/pipeline.py's dryrun uses). A deep
    model on a slow interconnect ranks pipe above fsdp; a small model
    ranks fsdp above pipe — matching the reference's treatment of
    pipeline_parallel as a searchable method rather than a default
    (optimization_library.py:38-56).
    """
    if peak_tflops is None or peak_hbm_gbps is None:
        pf, pb = chip_peaks()
        peak_tflops = peak_tflops or pf
        peak_hbm_gbps = peak_hbm_gbps or pb
    from dlrover_tpu.accelerate.remat import canonical

    mesh = dict(strategy.mesh_shape)
    shards = max(
        1, math.prod(s for s in mesh.values() if s > 1)
    )
    remat = canonical(strategy.remat)
    flops = (
        per_sample.flops
        * strategy.micro_batch_size
        * _REMAT_FLOPS_FACTOR.get(remat, 1.0)
        / min(shards, n_devices)
    )
    byte_f = _DTYPE_BYTES_FACTOR.get(strategy.dtype, 1.0)
    traffic = (
        per_sample.bytes
        * strategy.micro_batch_size
        * byte_f
        / min(shards, n_devices)
    )
    t_compute = flops / (peak_tflops * 1e12)
    t_memory = traffic / (peak_hbm_gbps * 1e9)
    t = max(t_compute, t_memory)

    pipe = mesh.get("pipe", 1)
    if pipe > 1:
        # 1F1B bubble at the n_micro = 2*pipe convention.
        n_micro = 2 * pipe
        t *= (n_micro + pipe - 1) / n_micro

    if param_bytes is not None:
        # Inter-device traffic per device per step, by axis family:
        # fsdp all-gathers weights (fwd+bwd) and reduce-scatters
        # grads, data all-reduces grads — both scale with MODEL size;
        # tensor all-reduces partial activations inside every layer —
        # scales with ACTIVATION size; pipe ships only stage-boundary
        # activations (negligible next to any of these, its cost is
        # the bubble above).
        dsize = 2 if strategy.dtype in ("bfloat16", "half") else 4
        model_bytes = param_bytes * dsize / 4  # param_bytes is f32
        model_shards = (
            mesh.get("fsdp", 1)
            * mesh.get("tensor", 1)
            * pipe
        )
        sync = 0.0
        f = mesh.get("fsdp", 1)
        if f > 1:
            sync += 3.0 * (model_bytes / model_shards) * (f - 1)
        # Every axis that REPLICATES parameters must re-synchronize
        # gradients: data and seq both do (sequence shards compute
        # partial grads for the whole non-pipe-sharded model).
        # Known omission: the seq axis's per-layer K/V ring rotation
        # (parallel/ring_attention.py) is not modeled — ModuleCost is
        # scope-aggregate, so per-layer KV bytes aren't available
        # here. The omission under-costs seq slightly; it shrank by
        # q_per_kv for GQA models when compact-KV rotation landed,
        # and the dry-run measurement pass (not this prior) is what
        # ranks finalists anyway.
        reps = mesh.get("data", 1) * mesh.get("seq", 1)
        if reps > 1:
            # ring all-reduce of this device's grad shard
            sync += (
                2.0 * (model_bytes / model_shards) * (reps - 1) / reps
            )
        tp = mesh.get("tensor", 1)
        if tp > 1:
            # two partial-sum all-reduces per layer fwd + the mirrored
            # pair in bwd, approximated by the profiled activation
            # output traffic of this device's micro-batch
            act_bytes = (
                per_sample.out_bytes
                * strategy.micro_batch_size
                * byte_f
                / min(shards, n_devices)
            )
            sync += 4.0 * act_bytes * (tp - 1) / tp
        t += sync / (ici_gbps * 1e9)

    # Per-step time normalized per sample so different micro-batch
    # sizes rank by throughput, not raw latency.
    return t / strategy.micro_batch_size


def strategy_time_priors(
    per_sample: ModuleCost,
    strategies,
    n_devices: int,
    param_bytes: Optional[int] = None,
) -> list:
    """Lower-is-better per-sample step-time priors for a candidate
    list (drop-in for BayesStrategySearch's cost_prior)."""
    return [
        predict_step_time(
            per_sample, s, n_devices, param_bytes=param_bytes
        )
        for s in strategies
    ]
