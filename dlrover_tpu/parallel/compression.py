"""Compressed gradient synchronization over a mesh axis.

Reference counterparts: the quantized-reduction CUDA kernels
(atorch/ops/csrc/quantization/quant_reduce.cu,
swizzled_quantize.cu) and ADP's gradient-compression DDP hooks
(atorch/data_parallel/adp.py). On TPU the equivalent lever is the
*collective schedule*, not a custom allreduce: an allreduce is a
reduce-scatter (which must stay high-precision — it sums) followed by
an all-gather (which is pure broadcast and compresses safely). This
module implements

    psum_mean = psum_scatter(bf16/f32)  ->  quantize shard
                -> all_gather(int8 + per-block scales) -> dequantize

cutting the all-gather phase to ~1/2 (int8 vs bf16) or ~1/4 (packed
int4) of the bytes — worth it exactly where the data axis crosses DCN
(multi-slice outer axis, parallel/mesh.py), which is also where the
reference deployed gradient compression.

Opt-in via ``make_compressed_train_step`` for the replicated-params
data-parallel regime; per-leaf quantization error is bounded by the
per-block absmax / 127 (or /7 at 4 bits), and tests bound the
end-to-end gradient deviation.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# Deliberately the jnp (_ref) quantizers, NOT the Pallas kernels:
# inside shard_map XLA fuses these elementwise ops straight into the
# collective schedule (quantize overlaps the reduce-scatter epilogue),
# whereas a pallas_call is an opaque boundary XLA cannot fuse or
# overlap through. Wire format (int8 / packed-nibble uint8 + f32
# per-block scales) is identical to the kernel path by construction —
# test_int4_wire_format_is_packed pins that.
from dlrover_tpu.ops.quantization import (
    dequantize_blockwise_4bit_ref,
    dequantize_blockwise_ref,
    quantize_blockwise_4bit_ref,
    quantize_blockwise_ref,
)

# Below this many elements the collective is latency-bound and
# padding to n*block would inflate tiny leaves (biases, norms) by
# orders of magnitude — plain pmean wins.
DEFAULT_MIN_SIZE = 16384


def compressed_psum_mean(
    x: jax.Array,
    axis_name: str,
    bits: int = 8,
    block: int = 1024,
    min_size: int = DEFAULT_MIN_SIZE,
) -> jax.Array:
    """Mean of ``x`` over ``axis_name`` with an int-quantized
    all-gather phase (packed two-per-byte at 4 bits — the
    ops/quantization.py wire format). Must run inside shard_map;
    returns the mean replicated across the axis (like ``lax.pmean``).
    Leaves smaller than ``min_size`` fall back to plain pmean.
    """
    if bits not in (4, 8):
        raise ValueError("bits must be 4 or 8")
    if x.size < min_size:
        return jax.lax.pmean(x, axis_name)
    n = jax.lax.psum(1, axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)  # keep input dtype: RS bytes match baseline
    size = flat.size
    pad = (-size) % (n * block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunk = flat.size // n
    # Phase 1: reduce-scatter in the gradient dtype (sums must not
    # quantize; same precision/bytes as the baseline psum's RS phase).
    shard = jax.lax.psum_scatter(
        flat.reshape(n, chunk), axis_name, scatter_dimension=0,
        tiled=False,
    )  # [chunk], this device's reduced shard
    # Phase 2: quantize the reduced shard, broadcast cheaply.
    shard32 = shard.astype(jnp.float32)
    if bits == 4:
        q, scale, _ = quantize_blockwise_4bit_ref(shard32, block)
    else:
        q, scale, _ = quantize_blockwise_ref(shard32, block)
    q_all = jax.lax.all_gather(q, axis_name)  # [n, rows, wire-width]
    s_all = jax.lax.all_gather(scale, axis_name)
    rows = q_all.shape[0] * q_all.shape[1]
    q2 = q_all.reshape(rows, q_all.shape[2])
    s2 = s_all.reshape(rows, 1)
    if bits == 4:
        full = dequantize_blockwise_4bit_ref(q2, s2, (rows * block,))
    else:
        full = dequantize_blockwise_ref(q2, s2, (rows * block,))
    out = full.reshape(-1)[:size].reshape(shape) / n
    return out.astype(dtype)


def bucket_plan(
    leaves: Sequence, bucket_bytes: int
) -> List[List[int]]:
    """Greedy contiguous grouping of flat leaf indices into
    size-bounded, dtype-homogeneous buckets (concatenation needs one
    dtype per bucket; flatten order is the tree's canonical leaf
    order, so the plan is deterministic for a given pytree).

    A single leaf larger than ``bucket_bytes`` gets a bucket of its
    own — leaves are never split, so the bound is soft for oversized
    leaves and hard for everything else. Works on anything with
    ``.shape``/``.dtype`` (arrays, tracers, ShapeDtypeStructs), so
    the plan can be computed abstractly for accounting/metrics."""
    plan: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i, leaf in enumerate(leaves):
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(
            leaf.dtype
        ).itemsize
        if cur and (
            cur_dtype != leaf.dtype
            or cur_bytes + nbytes > bucket_bytes
        ):
            plan.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = leaf.dtype
    if cur:
        plan.append(cur)
    return plan


def bucketed_psum_mean(
    tree,
    axis_name: str,
    bucket_bytes: int = 4 << 20,
    bits: Optional[int] = None,
    block: int = 1024,
    min_size: int = DEFAULT_MIN_SIZE,
):
    """Mean-reduce a whole gradient pytree over ``axis_name`` as a
    sequence of size-bounded flat buckets instead of one collective
    per leaf (or one monolithic flatten).

    Why buckets: each bucket's psum is an *independent* collective
    whose result is consumed only by the accumulator add, so XLA's
    latency-hiding scheduler can run bucket k's reduce behind the
    compute that produces bucket k+1 — and, inside a scan over
    microbatches, behind the NEXT microbatch's backward. Per-leaf
    reduces of tiny tensors are latency-bound; a monolithic reduce
    serializes the whole sync after the last gradient materializes.
    ``bits`` of 4/8 routes buckets through
    :func:`compressed_psum_mean` (quantized all-gather phase); None
    keeps the sync exact. Must run inside shard_map."""
    leaves, treedef = jax.tree.flatten(tree)
    plan = bucket_plan(leaves, bucket_bytes)
    out = [None] * len(leaves)
    for idxs in plan:
        if len(idxs) == 1:
            flat = leaves[idxs[0]].reshape(-1)
        else:
            flat = jnp.concatenate(
                [leaves[i].reshape(-1) for i in idxs]
            )
        if bits is None:
            red = jax.lax.pmean(flat, axis_name)
        else:
            red = compressed_psum_mean(
                flat, axis_name, bits=bits, block=block,
                min_size=min_size,
            )
        off = 0
        for i in idxs:
            n = int(np.prod(leaves[i].shape))
            out[i] = red[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)


def make_compressed_train_step(
    mesh: Mesh,
    loss_fn: Callable,
    optimizer,
    axis_name: str = "data",
    bits: Optional[int] = 8,
    block: int = 1024,
    min_size: int = DEFAULT_MIN_SIZE,
    donate: bool = True,
    overlap: bool = False,
    bucket_mb: float = 4.0,
    accum_steps: int = 1,
):
    """Data-parallel train step whose gradient sync all-gathers
    quantized shards (replicated-params regime: every leaf is
    replicated over ``axis_name``, the batch is sharded over it).

    Drop-in for trainer.step.make_train_step on a pure-data mesh;
    compose the optimizer OUTSIDE the sync so its state stays exact.

    ``overlap=True`` switches the sync schedule from "one collective
    per leaf after backward" to size-bounded bucketed reduces issued
    as each bucket's gradients finalize (see
    :func:`bucketed_psum_mean`); with ``accum_steps > 1`` the step
    takes ``[accum, batch, ...]`` inputs and issues each microbatch's
    bucketed reduce *inside* the accumulation scan, so microbatch k's
    collective overlaps microbatch k+1's backward instead of paying
    one monolithic reduce after the loop. ``bits=None`` keeps the
    sync exact (overlap without quantization)."""
    if bits is not None and bits not in (4, 8):
        raise ValueError("bits must be 4, 8, or None (exact sync)")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if accum_steps > 1 and not overlap:
        raise ValueError(
            "accum_steps > 1 requires overlap=True (the serial "
            "accumulate-then-reduce shape lives in "
            "trainer.elastic_trainer)"
        )
    batch_spec = (
        P(None, axis_name) if accum_steps > 1 else P(axis_name)
    )
    rep = P()
    bucket_bytes = int(bucket_mb * (1 << 20))

    def leaf_sync(g):
        if bits is None:
            return jax.lax.pmean(g, axis_name)
        return compressed_psum_mean(
            g, axis_name, bits=bits, block=block, min_size=min_size
        )

    def sharded_grads(params, tokens, targets):
        if not overlap:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, targets
            )
            grads = jax.tree.map(leaf_sync, grads)
            loss = jax.lax.pmean(loss, axis_name)
            return loss, grads
        # Overlapped: per-microbatch bucketed reduce inside the scan.
        mb_tok = tokens if accum_steps > 1 else tokens[None]
        mb_tgt = targets if accum_steps > 1 else targets[None]

        def micro(carry, batch):
            grad_acc, loss_acc = carry
            t, y = batch
            loss, grads = jax.value_and_grad(loss_fn)(params, t, y)
            reduced = bucketed_psum_mean(
                jax.tree.map(lambda g: g / accum_steps, grads),
                axis_name,
                bucket_bytes=bucket_bytes,
                bits=bits,
                block=block,
                min_size=min_size,
            )
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype), grad_acc, reduced
            )
            return (grad_acc, loss_acc + loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, loss_sum), _ = jax.lax.scan(
            micro, (zeros, 0.0), (mb_tok, mb_tgt)
        )
        loss = jax.lax.pmean(loss_sum / accum_steps, axis_name)
        return loss, grads

    grads_fn = shard_map(
        sharded_grads,
        mesh=mesh,
        in_specs=(rep, batch_spec, batch_spec),
        out_specs=(rep, rep),
        check_vma=False,
    )

    def step(params, opt_state, tokens, targets):
        loss, grads = grads_fn(params, tokens, targets)
        # Same metrics contract as trainer.step.make_train_step — a
        # caller reading metrics["grad_norm"] must not crash only when
        # the search picks an overlap/compressed strategy.
        gnorm = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_overlapped_train_step(
    mesh: Mesh,
    loss_fn: Callable,
    optimizer,
    axis_name: str = "data",
    accum_steps: int = 1,
    bucket_mb: float = 4.0,
    bits: Optional[int] = None,
    block: int = 1024,
    min_size: int = DEFAULT_MIN_SIZE,
    donate: bool = True,
):
    """Overlapped bucketed-reduce train step — the exact-sync (or,
    with ``bits``, compressed) schedule Strategy's ``overlap_reduce``
    knob selects. See :func:`make_compressed_train_step` with
    ``overlap=True``."""
    return make_compressed_train_step(
        mesh,
        loss_fn,
        optimizer,
        axis_name=axis_name,
        bits=bits,
        block=block,
        min_size=min_size,
        donate=donate,
        overlap=True,
        bucket_mb=bucket_mb,
        accum_steps=accum_steps,
    )


def sync_bytes_per_element(bits: Optional[int]) -> float:
    """Bytes moved per gradient element for a bf16 gradient sync —
    used by tests and capacity planning. Baseline allreduce = RS + AG
    at 2 B/el each = 4 B/el. Compressed: RS stays bf16 (2 B/el), AG
    drops to bits/8 B/el (+ per-block scales, amortized to ~0).
    ``bits=None`` is the exact sync: the 4 B/el baseline."""
    if bits is None:
        return 4.0
    return 2.0 + bits / 8.0


def overlap_sync_bytes_per_element(
    bits: Optional[int], accum_steps: int = 1
) -> float:
    """Per-gradient-element bytes one *optimizer step* of the
    overlapped schedule moves: every one of the ``accum_steps``
    per-microbatch reduces pays :func:`sync_bytes_per_element`
    (that volume multiplier is the price of hiding the latency behind
    backward compute — int8 at accum 2 costs 6 B/el vs the serial
    exact step's 4 B/el, and the tradeoff only wins when the hidden
    latency exceeds the extra wire time)."""
    return sync_bytes_per_element(bits) * accum_steps
