"""Pipeline parallelism over the ``pipe`` mesh axis.

Replaces the reference's PiPPy-based pipeline stack
(atorch/compilers/pipe_compiler/distributed_pippy_compiler.py,
PipelineStage.py:989LoC — FX-traced stage split, torch RPC mailboxes,
1F1B interleaving) with the TPU-idiomatic formulation: a GPipe
schedule written as a ``lax.scan`` inside ``shard_map``, stage hops as
``lax.ppermute`` over ICI neighbors. The schedule is differentiable —
``jax.grad`` through the scan yields the reversed pipeline (backward
microbatch schedule) without any hand-written 1F1B machinery, and
``jax.checkpoint`` on the stage body bounds activation memory the way
1F1B's eager backward does.

Layout contract: stage parameters are stacked on a leading axis of
size n_stages, logically named ``stage`` (sharding.py maps it to the
``pipe`` mesh axis), so each device holds exactly its stage's weights.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_body(
    stage_fn: Callable,
    params,  # per-device stage params (leading stage dim of size 1)
    microbatches,  # [M, mb, ...] (replicated across pipe)
    axis_name: str,
    remat: bool,
):
    """Runs inside shard_map. Returns [M, mb, ...] outputs (valid on
    every device after the final psum broadcast)."""
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    total_steps = M + n_stages - 1

    local_params = jax.tree.map(lambda p: p[0], params)
    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def step(carry, t):
        outputs, prev_out = carry
        # What flows into this stage at step t: stage 0 injects
        # microbatch t (zeros in the drain phase); others receive the
        # previous step's output from their left neighbor.
        recv = jax.lax.ppermute(prev_out, axis_name, fwd_perm)
        mb_idx = jnp.clip(t, 0, M - 1)
        injected = jax.lax.dynamic_index_in_dim(
            microbatches, mb_idx, axis=0, keepdims=False
        )
        x_in = jnp.where(stage == 0, injected, recv)
        y = fn(local_params, x_in)
        # Last stage finished microbatch t - (n_stages - 1) at step t.
        out_idx = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
        contribution = jnp.where(write, 1.0, 0.0).astype(y.dtype) * y
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jax.lax.dynamic_index_in_dim(
                outputs, jnp.clip(out_idx, 0, M - 1), 0, keepdims=False
            )
            + contribution,
            jnp.clip(out_idx, 0, M - 1),
            0,
        )
        return (outputs, y), None

    y_shape = jax.eval_shape(fn, local_params, microbatches[0])
    outputs0 = jnp.zeros((M,) + y_shape.shape, y_shape.dtype)
    prev0 = jnp.zeros(y_shape.shape, y_shape.dtype)
    (outputs, _), _ = jax.lax.scan(
        step, (outputs0, prev0), jnp.arange(total_steps)
    )
    # Only the last stage holds real outputs; broadcast them to every
    # stage so the loss is computable anywhere (GSPMD psum over pipe).
    return jax.lax.psum(
        jnp.where(stage == n_stages - 1, 1.0, 0.0).astype(outputs.dtype)
        * outputs,
        axis_name,
    )


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable,
    axis_name: str = "pipe",
    remat: bool = True,
    params_spec: Optional[Any] = None,
    batch_spec: P = P(),
):
    """Builds ``apply(stage_params, microbatches) -> outputs``.

    stage_fn(stage_local_params, x[mb, ...]) -> y[mb, ...] applies ONE
    stage. ``stage_params`` leaves are stacked [n_stages, ...] and get
    sharded over ``axis_name``; microbatches [M, mb, ...] are
    replicated over ``axis_name`` (shard batch dims over data/fsdp
    axes via ``batch_spec``).
    """
    n_stages = mesh.shape.get(axis_name, 1)
    if n_stages == 1:
        def apply_single(stage_params, microbatches):
            local = jax.tree.map(lambda p: p[0], stage_params)
            fn = jax.checkpoint(stage_fn) if remat else stage_fn
            return jax.lax.map(lambda mb: fn(local, mb), microbatches)

        return apply_single

    if params_spec is None:
        params_spec = P(axis_name)
    body = functools.partial(
        _pipeline_body,
        stage_fn,
        axis_name=axis_name,
        remat=remat,
    )
    mb_spec = P(None, *batch_spec)  # leading microbatch dim replicated
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# 1F1B / interleaved schedule
# ---------------------------------------------------------------------------
#
# Capability parity with the reference's 1F1B + interleaved pipeline
# (atorch PipelineStage.py:1-989, StageInterleaver.py), built the TPU
# way: a lockstep wave schedule inside shard_map where every wave does
# one forward chunk and one backward chunk per device, activations hop
# stages through a single circular ``ppermute``, and gradients are
# computed manually with per-chunk ``jax.vjp`` against a bounded
# ring-buffer stash of chunk inputs. JAX never differentiates the scan,
# so the stash — O(n_stages * v_chunks) microbatch activations — is the
# ONLY schedule memory; GPipe-via-grad stashes O(M) scan residuals.
#
# Schedule (devices d = 0..n-1, virtual chunks v = 0..V-1, logical
# stage l = v*n + d, microbatches processed in groups of n):
#   forward  of mb (g*n + r) at chunk (d, v) on wave  t = g*nV + v*n + r + d
#   backward of the same     at wave  t = (nV-1) + g*nV + (V-1-v)*n + r + (n-1-d)
# Both decompose uniquely per (device, wave) — one F and one B chunk
# per device per wave, outputs consumed exactly one wave later by the
# circular neighbor (forward d -> d+1 mod n, backward d -> d-1 mod n,
# the mod-n wrap carrying chunk v outputs into chunk v+1 inputs).
# V=1 is plain (non-interleaved) 1F1B; V>1 shrinks the pipeline bubble
# from ~2(n-1) stage-times toward ~n(1 + 1/V).


def _chunk_at(params, v, V):
    """Dynamic-index chunk ``v`` out of [V, ...]-stacked local leaves."""
    return jax.tree.map(
        lambda p: jax.lax.dynamic_index_in_dim(
            p, jnp.clip(v, 0, V - 1), 0, keepdims=False
        ),
        params,
    )


def _1f1b_body(
    stage_fn: Callable,
    loss_fn: Callable,
    params,        # local [1, V, ...] leaves
    microbatches,  # [M, mb, ...] replicated over pipe
    targets,       # [M, ...] replicated over pipe
    head_params,   # extra loss-side params (None = plain loss_fn)
    axis_name: str,
    V: int,
    n: int,
    batch_axes: tuple = (),
    collect_input_grads: bool = False,
    stage_aux: bool = False,
):
    d = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    if M % n:
        raise ValueError(
            f"microbatch count {M} must be a multiple of the "
            f"{axis_name} axis size {n}"
        )
    for p in jax.tree.leaves(params):
        if p.shape[1] != V:
            raise ValueError(
                f"stage params chunk dim {p.shape[1]} != v_chunks "
                f"{V}: stack with split_stages_interleaved(tree, "
                f"{n}, {V})"
            )
    nV = n * V
    G = M // n
    C = nV - 1  # backward wave offset
    total_waves = C + (G - 1) * nV + (V - 1) * n + 2 * (n - 1) + 1

    local_params = jax.tree.map(lambda p: p[0], params)  # [V, ...]
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]

    y_shape = jax.eval_shape(
        stage_fn, _chunk_at(local_params, jnp.int32(0), V),
        microbatches[0],
    )
    if stage_aux:
        y_shape = y_shape[0]
    # Ring buffer of stashed chunk inputs, per chunk. The in-flight
    # window per chunk is <= ~2n + n sawtooth slack; 4n+4 is safe and
    # still O(n), independent of M (the whole point vs GPipe).
    R = min(M, 4 * n + 4)

    def wave(carry, t):
        (y_prev, d_prev, stash, grad_acc, loss_acc,
         head_acc, dx_buf, aux_acc) = carry

        # ---- forward sub-step -----------------------------------------
        recv = jax.lax.ppermute(y_prev, axis_name, fwd_perm)
        u = t - d
        g_f = u // nV
        rem = u % nV
        v_f = rem // n
        r_f = rem % n
        mb_f = g_f * n + r_f
        valid_f = jnp.logical_and(u >= 0, mb_f < M)
        inject = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(mb_f, 0, M - 1), 0, keepdims=False
        )
        is_first = jnp.logical_and(d == 0, v_f == 0)
        x_in = jnp.where(is_first, inject, recv)
        if stage_aux:
            y, aux_f = stage_fn(_chunk_at(local_params, v_f, V), x_in)
            # where, not multiply: bubble waves compute aux on
            # garbage inputs and 0 * inf would poison the sum
            aux_acc = aux_acc + jnp.where(
                valid_f, aux_f.astype(jnp.float32), 0.0
            )
        else:
            y = stage_fn(_chunk_at(local_params, v_f, V), x_in)

        slot_f = jnp.clip(v_f, 0, V - 1) * R + mb_f % R
        old = jax.lax.dynamic_index_in_dim(
            stash, slot_f, 0, keepdims=False
        )
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(valid_f, x_in, old), slot_f, 0
        )

        # ---- backward sub-step ----------------------------------------
        recv_d = jax.lax.ppermute(d_prev, axis_name, bwd_perm)
        ub = t - C - (n - 1 - d)
        g_b = ub // nV
        remb = ub % nV
        v_b = (V - 1) - remb // n
        r_b = remb % n
        mb_b = g_b * n + r_b
        valid_b = jnp.logical_and(ub >= 0, mb_b < M)
        slot_b = jnp.clip(v_b, 0, V - 1) * R + mb_b % R
        x_b = jax.lax.dynamic_index_in_dim(
            stash, slot_b, 0, keepdims=False
        )
        chunk_p = _chunk_at(local_params, v_b, V)
        if stage_aux:
            (y_b, _aux_b), vjp_fn = jax.vjp(stage_fn, chunk_p, x_b)
        else:
            y_b, vjp_fn = jax.vjp(stage_fn, chunk_p, x_b)
        tgt = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.clip(mb_b, 0, M - 1), 0, keepdims=False
            ),
            targets,
        )
        is_last = jnp.logical_and(d == n - 1, v_b == V - 1)
        if head_params is None:
            loss_mb, dy_loss = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt)
            )(y_b)
            dhead = None
        else:
            # The head (norm + unembedding CE for a transformer) can
            # dwarf a single stage's FLOPs; lax.cond skips its
            # forward+backward entirely on non-last stages instead of
            # masking the result to zero afterwards.
            def _head_branch(args):
                yy, hp = args
                return jax.value_and_grad(
                    lambda y_, h_: loss_fn(y_, tgt, h_),
                    argnums=(0, 1),
                )(yy, hp)

            def _skip_branch(args):
                yy, hp = args
                return (
                    jnp.float32(0.0),
                    (
                        jnp.zeros_like(yy),
                        jax.tree.map(jnp.zeros_like, hp),
                    ),
                )

            loss_mb, (dy_loss, dhead) = jax.lax.cond(
                is_last, _head_branch, _skip_branch,
                (y_b, head_params),
            )
        dy = jnp.where(is_last, dy_loss, recv_d)
        if stage_aux:
            # aux cotangent 1 per VALID backward (un-meaned, same /M
            # as the grads below): d(total aux)/d(this chunk's aux)
            daux = jnp.where(valid_b, 1.0, 0.0).astype(jnp.float32)
            dp, dx = vjp_fn((dy, daux))
        else:
            dp, dx = vjp_fn(dy)
        # jnp.where, NOT multiply-by-mask: bubble waves run stage_fn
        # on garbage stash values, and 0 * inf = NaN would poison the
        # accumulator for the rest of the scan.
        grad_acc = jax.tree.map(
            lambda acc, g: jax.lax.dynamic_update_index_in_dim(
                acc,
                jax.lax.dynamic_index_in_dim(
                    acc, jnp.clip(v_b, 0, V - 1), 0, keepdims=False
                )
                + jnp.where(valid_b, g.astype(acc.dtype), 0.0),
                jnp.clip(v_b, 0, V - 1),
                0,
            ),
            grad_acc,
            dp,
        )
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(valid_b, is_last), loss_mb, 0.0
        )
        if head_acc is not None:
            take_head = jnp.logical_and(valid_b, is_last)
            head_acc = jax.tree.map(
                lambda acc, g: acc
                + jnp.where(take_head, g.astype(acc.dtype), 0.0),
                head_acc,
                dhead,
            )
        if dx_buf is not None:
            # Stage-0 chunk-0 backwards produce d(loss)/d(microbatch):
            # the caller differentiates its pre-pipeline compute
            # (e.g. the embedding) with these cotangents.
            is_first_b = jnp.logical_and(d == 0, v_b == 0)
            take_dx = jnp.logical_and(valid_b, is_first_b)
            slot = jnp.clip(mb_b, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(
                dx_buf, slot, 0, keepdims=False
            )
            dx_buf = jax.lax.dynamic_update_index_in_dim(
                dx_buf,
                jnp.where(take_dx, dx.astype(dx_buf.dtype), cur),
                slot,
                0,
            )
        d_prev_new = jnp.where(valid_b, dx, jnp.zeros_like(dx))
        return (
            y, d_prev_new, stash, grad_acc, loss_acc, head_acc,
            dx_buf, aux_acc,
        ), None

    y0 = jnp.zeros(y_shape.shape, y_shape.dtype)
    d0 = jnp.zeros(y_shape.shape, y_shape.dtype)
    stash0 = jnp.zeros((V * R,) + microbatches.shape[1:],
                       microbatches.dtype)
    grad0 = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), local_params
    )
    head0 = (
        None
        if head_params is None
        else jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), head_params
        )
    )
    dx0 = (
        jnp.zeros((M,) + y_shape.shape, jnp.float32)
        if collect_input_grads
        else None
    )
    (
        y_f, d_f, _, grads, loss, head_grads, dx_all, aux_sum
    ), _ = jax.lax.scan(
        wave,
        (
            y0, d0, stash0, grad0, jnp.float32(0.0), head0, dx0,
            jnp.float32(0.0),
        ),
        jnp.arange(total_waves),
    )
    # Mean over microbatches; loss lives on the last logical stage
    # only, grads on their own stage — psum the loss, keep grads local.
    loss = jax.lax.psum(loss, axis_name) / M
    if stage_aux:
        # every device accumulated its own chunks' aux; the total is
        # the cross-pipe sum, meaned over microbatches like the loss
        loss = loss + jax.lax.psum(aux_sum, axis_name) / M
    grads = jax.tree.map(lambda g: g / M, grads)
    if head_grads is not None:
        # Nonzero only on the last logical stage's device: replicate.
        head_grads = jax.tree.map(
            lambda g: jax.lax.psum(g, axis_name) / M, head_grads
        )
    if dx_all is not None:
        # Nonzero only on stage-0 devices: replicate across pipe.
        # Per-microbatch cotangents are NOT divided by M — the caller
        # applies the same 1/M mean when reducing its pre-pipeline
        # grads, keeping d(mean loss)/d(input) exact.
        dx_all = jax.lax.psum(dx_all, axis_name)
        if batch_axes:
            # loss_fn normalizes over the SHARD-LOCAL microbatch rows;
            # the global loss is the pmean over batch shards, so each
            # shard's input cotangent carries a 1/nshards factor (the
            # stage grads get this via their pmean below — dx stays
            # shard-local, so scale it directly).
            nshards = jax.lax.psum(1, batch_axes)
            dx_all = dx_all / nshards
    if batch_axes:
        # microbatches are sharded over these axes: each shard saw
        # only its slice, so loss/grads are shard-local means.
        loss = jax.lax.pmean(loss, batch_axes)
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(g, batch_axes), grads
        )
        if head_grads is not None:
            head_grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, batch_axes), head_grads
            )
        # dx_all stays shard-local: it is the cotangent of THIS
        # shard's microbatch slice.
    out_grads = jax.tree.map(lambda g: g[None], grads)  # [1, V, ...]
    if head_params is None and not collect_input_grads:
        return loss, out_grads
    return loss, out_grads, head_grads, dx_all


def pipeline_train(
    mesh: Mesh,
    stage_fn: Callable,
    loss_fn: Callable,
    axis_name: str = "pipe",
    v_chunks: int = 1,
    params_spec: Optional[Any] = None,
    batch_spec: P = P(),
    with_head: bool = False,
    collect_input_grads: bool = False,
    stage_aux: bool = False,
):
    """Builds a 1F1B (``v_chunks=1``) or interleaved-1F1B training
    step: ``step(stage_params, microbatches, targets) -> (loss,
    grads)``.

    * ``stage_params`` leaves are stacked ``[n_stages, v_chunks, ...]``
      (see :func:`split_stages_interleaved`); chunk ``(d, v)`` is
      logical pipeline stage ``v * n_stages + d``.
    * ``stage_fn(chunk_params, x[mb, ...]) -> y[mb, ...]`` applies one
      chunk; all chunk inputs/outputs share one activation shape.
    * ``loss_fn(y[mb, ...], target) -> scalar`` is evaluated per
      microbatch at the last logical stage; the returned ``loss`` and
      ``grads`` are means over all ``M`` microbatches.
    * ``M`` must be a multiple of the ``pipe`` axis size.

    Full-model hooks (how a transformer with an embedding and an
    unembedding head pipelines its uniform-activation middle):

    * ``with_head=True``: the step takes a fourth argument —
      replicated loss-side params — and ``loss_fn(y, target,
      head_params)``; the step returns their mean gradient (psum'd
      from the last logical stage) as a third output.
    * ``collect_input_grads=True``: the step also returns
      d(mean loss)/d(microbatches) * M, the per-microbatch cotangents
      flowing out of logical stage 0 — the caller backpropagates its
      pre-pipeline compute (embedding) with them and applies the same
      1/M mean itself.
    * ``stage_aux=True``: ``stage_fn`` returns ``(y, aux)`` with a
      scalar auxiliary loss per chunk (MoE router load-balancing);
      the step's loss adds the cross-stage, microbatch-meaned aux sum
      and differentiates through it (cotangent 1 per valid backward).

    Unlike :func:`pipeline_apply` + ``jax.grad`` (GPipe), activation
    stash is O(n_stages * v_chunks) microbatch inputs instead of O(M)
    scan residuals, and the backward schedule starts while forwards
    are still draining — the 1F1B property (ref PipelineStage.py).
    """
    n_stages = mesh.shape.get(axis_name, 1)
    if params_spec is None:
        params_spec = P(axis_name)
    plain = not with_head and not collect_input_grads

    if n_stages == 1:
        def step_single(stage_params, microbatches, targets,
                        head_params=None):
            local = jax.tree.map(lambda p: p[0], stage_params)

            def whole(params_, mbs, hp):
                def one(mb, tgt):
                    x = mb
                    aux_total = jnp.float32(0.0)
                    for v in range(v_chunks):
                        chunk = jax.tree.map(
                            lambda p: p[v], params_
                        )
                        if stage_aux:
                            x, aux = stage_fn(chunk, x)
                            aux_total = aux_total + aux
                        else:
                            x = stage_fn(chunk, x)
                    base = (
                        loss_fn(x, tgt, hp)
                        if with_head
                        else loss_fn(x, tgt)
                    )
                    return base + aux_total

                losses = jax.vmap(one)(mbs, targets)
                return jnp.mean(losses)

            argnums = (0,)
            if collect_input_grads:
                argnums += (1,)
            if with_head:
                argnums += (2,)
            loss, grad_parts = jax.value_and_grad(
                whole, argnums=argnums
            )(local, microbatches, head_params)
            parts = dict(zip(argnums, grad_parts))
            out = (loss, jax.tree.map(lambda g: g[None], parts[0]))
            if plain:
                return out
            M = microbatches.shape[0]
            return out + (
                parts.get(2) if with_head else None,
                # match the sharded path's un-meaned convention
                jax.tree.map(lambda g: g * M, parts[1])
                if collect_input_grads
                else None,
            )

        return step_single

    batch_axes: list = []
    for e in batch_spec:
        if e is None:
            continue
        batch_axes.extend(e if isinstance(e, tuple) else (e,))
    body = functools.partial(
        _1f1b_body,
        stage_fn,
        loss_fn,
        axis_name=axis_name,
        V=v_chunks,
        n=n_stages,
        batch_axes=tuple(batch_axes),
        collect_input_grads=collect_input_grads,
        stage_aux=stage_aux,
    )
    mb_spec = P(None, *batch_spec)
    if plain:
        def body_plain(params, microbatches, targets):
            return body(params, microbatches, targets, None)

        return shard_map(
            body_plain,
            mesh=mesh,
            in_specs=(params_spec, mb_spec, mb_spec),
            out_specs=(P(), P(axis_name)),
            check_vma=False,
        )

    def body_full(params, microbatches, targets, head_params):
        return body(params, microbatches, targets, head_params)

    sharded = shard_map(
        body_full,
        mesh=mesh,
        in_specs=(params_spec, mb_spec, mb_spec, P()),
        out_specs=(
            P(),
            P(axis_name),
            P() if with_head else None,
            mb_spec if collect_input_grads else None,
        ),
        check_vma=False,
    )

    def step(stage_params, microbatches, targets, head_params=None):
        return sharded(stage_params, microbatches, targets, head_params)

    return step


def split_stages_interleaved(tree, n_stages: int, v_chunks: int):
    """Reshape a scanned-layer tree [L, ...] into
    [n_stages, v_chunks, L/(n_stages*v_chunks), ...] where chunk
    (d, v) holds the layers of LOGICAL stage v*n_stages + d (the
    interleaved round-robin placement, ref StageInterleaver.py)."""
    nV = n_stages * v_chunks

    def reshape(p):
        L = p.shape[0]
        if L % nV:
            raise ValueError(
                f"layer count {L} not divisible by {nV} chunks"
            )
        # [V, n, L/nV, ...] -> transpose to [n, V, ...]: element
        # [d, v] = logical chunk v*n + d.
        q = p.reshape((v_chunks, n_stages, L // nV) + p.shape[1:])
        return jnp.swapaxes(q, 0, 1)

    return jax.tree.map(reshape, tree)


def split_stages(tree, n_stages: int):
    """Reshape a scanned-layer param tree [L, ...] into
    [n_stages, L // n_stages, ...] for pipeline stacking."""

    def reshape(p):
        L = p.shape[0]
        if L % n_stages:
            raise ValueError(
                f"layer count {L} not divisible by {n_stages} stages"
            )
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree.map(reshape, tree)
