"""Fused LayerNorm / RMSNorm (+ residual add) in Pallas, fwd + bwd.

Parity target: the reference integrates fused
``dropout_add_layer_norm`` CUDA kernels
(atorch/modules/transformer/layers.py:74) and a fused LayerNorm module
(atorch/normalization/) because norms sit on the HBM-bound residual
spine of every transformer block. The TPU version fuses the residual
add into the norm so the pre-norm branch point writes/reads HBM once:

    out, resid = fused_layer_norm(x, g, b, residual=res)
      resid = x + res   (the next branch point, saved for backward)
      out   = (resid - mu) * rsqrt(var + eps) * g + b

* one row-blocked kernel per pass; statistics in f32 at [rows, 1]
  (single lane), activations any float dtype;
* backward is a single kernel producing dx and per-row-block PARTIAL
  dg/db tiles (cross-row reductions), summed by XLA outside — the
  partials are tiny [n_blocks, E] f32;
* dropout is intentionally NOT fused: elastic-training configs run
  dropout 0 (nanoGPT parity, models/gpt.py), so the fusion the
  reference needs for torch dropout is dead weight here.

On non-TPU backends the kernels run in interpreter mode (same code
path, unit-testable on CPU) — but callers (models/gpt.py,
models/llama.py) auto-select the plain XLA norm off-TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import use_interpret, per_device

DEFAULT_BLOCK_ROWS = 256
# Per-ref VMEM budget for a [block_rows, E] f32 block. The backward
# kernel keeps ~6 such refs live per grid step, so 1 MiB/ref stays
# well under the ~16 MiB/core VMEM even before double-buffering.
_ROW_BLOCK_BYTE_BUDGET = 1 << 20


def pick_block_rows(e: int) -> int:
    """Default row-block for embedding width ``e``: the fixed
    DEFAULT_BLOCK_ROWS while a [rows, e] f32 block fits the byte
    budget, shrinking (multiples of 8) as ``e`` grows so wide models
    (e >= 1024) cannot overflow VMEM."""
    rows = _ROW_BLOCK_BYTE_BUDGET // (max(e, 1) * 4)
    return min(DEFAULT_BLOCK_ROWS, max(8, rows - rows % 8))


def _rows_pad(n: int, block: int) -> int:
    return (-n) % block


# -- forward kernels ----------------------------------------------------


def _fwd_kernel(x_ref, res_ref, g_ref, b_ref, out_ref, resid_ref,
                mu_ref, rstd_ref, *, eps, rms, add_residual):
    x = x_ref[...].astype(jnp.float32)
    if add_residual:
        x = x + res_ref[...].astype(jnp.float32)
    if add_residual:
        resid_ref[...] = x.astype(resid_ref.dtype)
    if rms:
        mu = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    out = xhat * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _bwd_kernel(dout_ref, resid_ref, g_ref, mu_ref, rstd_ref,
                dx_ref, dg_ref, db_ref, *, rms):
    dout = dout_ref[...].astype(jnp.float32)
    y = resid_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    rstd = rstd_ref[...]
    xhat = (y - mu) * rstd

    # dg/db partials: one (8, E) accumulator block shared by every
    # grid step (real TPU lowering requires block sublanes divisible
    # by 8 — a (1, E) row per step is not tileable). Sequential
    # "arbitrary" grid semantics keep the block resident, so
    # read-modify-write accumulation is sound (the flash kernel's dkv
    # uses the same pattern); rows reduce 8-wise here and the final
    # 8 -> 1 fold happens host-side.
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    r, e = dout.shape
    dg_ref[...] += jnp.sum(
        (dout * xhat).reshape(r // 8, 8, e), axis=0
    )
    if db_ref is not None:
        db_ref[...] += jnp.sum(dout.reshape(r // 8, 8, e), axis=0)
    wdout = dout * g
    c2 = jnp.mean(wdout * xhat, axis=-1, keepdims=True)
    if rms:
        dx = (wdout - xhat * c2) * rstd
    else:
        c1 = jnp.mean(wdout, axis=-1, keepdims=True)
        dx = (wdout - c1 - xhat * c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)


# -- host-side wrappers -------------------------------------------------


def _fwd(x2, res2, g, b, *, eps, rms, block_rows, interpret):
    n, e = x2.shape
    pad = _rows_pad(n, block_rows)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        if res2 is not None:
            res2 = jnp.pad(res2, ((0, pad), (0, 0)))
    rows = x2.shape[0]
    grid = (rows // block_rows,)
    row_spec = pl.BlockSpec((block_rows, e), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    gb_spec = pl.BlockSpec((1, e), lambda i: (0, 0))
    add_residual = res2 is not None

    in_specs = [row_spec]
    inputs = [x2]
    if add_residual:
        in_specs.append(row_spec)
        inputs.append(res2)
    in_specs.append(gb_spec)
    inputs.append(g.reshape(1, e))
    if b is not None:
        in_specs.append(gb_spec)
        inputs.append(b.reshape(1, e))

    kernel = functools.partial(
        _kernel_fwd_dispatch,
        eps=eps,
        rms=rms,
        add_residual=add_residual,
        has_bias=b is not None,
    )
    # The resid output only exists on the add path: callers of the
    # plain norm already hold x, so emitting x again would add a dead
    # full-tensor HBM write to the exact spine this kernel relieves.
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rows, e), x2.dtype)]
    if add_residual:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rows, e), x2.dtype))
    out_specs += [stat_spec, stat_spec]
    out_shape += [
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    ]
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="rms_norm_fwd" if rms else "layer_norm_fwd",
    )(*inputs)
    if add_residual:
        out, resid, mu, rstd = outs
        return out[:n], resid[:n], mu, rstd
    out, mu, rstd = outs
    return out[:n], None, mu, rstd


def _kernel_fwd_dispatch(*refs, eps, rms, add_residual, has_bias):
    """Unpack the variadic ref list into the named kernel args."""
    i = 0
    x_ref = refs[i]; i += 1
    res_ref = None
    if add_residual:
        res_ref = refs[i]; i += 1
    g_ref = refs[i]; i += 1
    b_ref = None
    if has_bias:
        b_ref = refs[i]; i += 1
    out_ref = refs[i]; i += 1
    resid_ref = None
    if add_residual:
        resid_ref = refs[i]; i += 1
    mu_ref, rstd_ref = refs[i:i + 2]
    _fwd_kernel(
        x_ref, res_ref, g_ref, b_ref, out_ref, resid_ref, mu_ref,
        rstd_ref, eps=eps, rms=rms, add_residual=add_residual,
    )


def _bwd(dout2, resid2, g, mu, rstd, *, rms, has_bias, block_rows,
         interpret):
    if block_rows % 8:
        raise ValueError(
            f"block_rows={block_rows} must be a multiple of 8 (the "
            "f32 sublane tile; the dg/db partial accumulator reduces "
            "rows 8-wise)"
        )
    n, e = dout2.shape
    pad = _rows_pad(n, block_rows)
    if pad:
        dout2 = jnp.pad(dout2, ((0, pad), (0, 0)))
        resid2 = jnp.pad(resid2, ((0, pad), (0, 0)))
        # rstd pad rows are zero -> their dx rows compute to 0.
        mu = jnp.pad(mu, ((0, pad), (0, 0)))
        rstd = jnp.pad(rstd, ((0, pad), (0, 0)))
    rows = dout2.shape[0]
    nblocks = rows // block_rows
    grid = (nblocks,)
    row_spec = pl.BlockSpec((block_rows, e), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    gb_spec = pl.BlockSpec((1, e), lambda i: (0, 0))
    # Every grid step accumulates into the SAME (8, e) partial block
    # (see _bwd_kernel): 8 sublanes is the minimum f32 tile height on
    # real TPU, so per-block (1, e) rows would not lower.
    part_spec = pl.BlockSpec((8, e), lambda i: (0, 0))

    out_specs = [row_spec, part_spec]
    out_shape = [
        jax.ShapeDtypeStruct((rows, e), dout2.dtype),
        jax.ShapeDtypeStruct((8, e), jnp.float32),
    ]
    if has_bias:
        out_specs.append(part_spec)
        out_shape.append(
            jax.ShapeDtypeStruct((8, e), jnp.float32)
        )

    def kernel(dout_ref, resid_ref, g_ref, mu_ref, rstd_ref, *outs):
        dx_ref = outs[0]
        dg_ref = outs[1]
        db_ref = outs[2] if has_bias else None
        _bwd_kernel(
            dout_ref, resid_ref, g_ref, mu_ref, rstd_ref,
            dx_ref, dg_ref, db_ref, rms=rms,
        )

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, gb_spec, stat_spec, stat_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="rms_norm_bwd" if rms else "layer_norm_bwd",
    )(dout2, resid2, g.reshape(1, e), mu, rstd)
    dx = outs[0][:n]
    dg = jnp.sum(outs[1], axis=0)
    db = jnp.sum(outs[2], axis=0) if has_bias else None
    return dx, dg, db


# -- public API (custom VJP) -------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm(x, g, b, eps, rms, block_rows, interpret):
    out, _ = _norm_fwd(x, g, b, eps, rms, block_rows, interpret)
    return out


def _norm_fwd(x, g, b, eps, rms, block_rows, interpret):
    shape = x.shape
    e = shape[-1]
    x2 = x.reshape(-1, e)
    n = x2.shape[0]
    out, _, mu, rstd = _fwd(
        x2, None, g, b, eps=eps, rms=rms, block_rows=block_rows,
        interpret=interpret,
    )
    saved = (x2, g, mu[:n], rstd[:n], b is not None, shape)
    return out.reshape(shape), saved


def _norm_bwd(eps, rms, block_rows, interpret, saved, dout):
    x2, g, mu, rstd, has_bias, shape = saved
    e = shape[-1]
    dx, dg, db = _bwd(
        dout.reshape(-1, e), x2, g, mu, rstd, rms=rms,
        has_bias=has_bias, block_rows=block_rows,
        interpret=interpret,
    )
    return (
        dx.reshape(shape),
        dg.astype(g.dtype),
        db.astype(g.dtype) if has_bias else None,
    )


_norm.defvjp(_norm_fwd, _norm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _add_norm(x, res, g, b, eps, rms, block_rows, interpret):
    outs, _ = _add_norm_fwd(
        x, res, g, b, eps, rms, block_rows, interpret
    )
    return outs


def _add_norm_fwd(x, res, g, b, eps, rms, block_rows, interpret):
    shape = x.shape
    e = shape[-1]
    out, resid2, mu, rstd = _fwd(
        x.reshape(-1, e), res.reshape(-1, e), g, b, eps=eps,
        rms=rms, block_rows=block_rows, interpret=interpret,
    )
    n = out.shape[0]
    saved = (resid2, g, mu[:n], rstd[:n], b is not None, shape)
    return (out.reshape(shape), resid2.reshape(shape)), saved


def _add_norm_bwd(eps, rms, block_rows, interpret, saved, cots):
    dout, dresid = cots
    resid2, g, mu, rstd, has_bias, shape = saved
    e = shape[-1]
    dy, dg, db = _bwd(
        dout.reshape(-1, e), resid2, g, mu, rstd, rms=rms,
        has_bias=has_bias, block_rows=block_rows,
        interpret=interpret,
    )
    # y = x + res feeds both the norm and (via the second output) the
    # rest of the network: total dy adds the downstream cotangent.
    dy = dy.reshape(shape) + dresid
    return (
        dy,
        dy,
        dg.astype(g.dtype),
        db.astype(g.dtype) if has_bias else None,
    )


_add_norm.defvjp(_add_norm_fwd, _add_norm_bwd)


def _norm_per_device(norm, activations, weights, static):
    """``_norm``/``_add_norm`` with the batch rows of the activations
    split over the ambient mesh and the weights whole on every device
    (flash_attention.per_device); a missing bias stays ``None``."""
    n = len(activations)
    return per_device(
        lambda *ops: norm(*ops, *static),
        *activations, *weights,
        split=(True,) * n + (False,) * len(weights),
    )


def fused_layer_norm(
    x: jax.Array,
    g: jax.Array,
    b: Optional[jax.Array] = None,
    eps: float = 1e-5,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """LayerNorm over the last axis, f32 statistics, any float input
    dtype. Differentiable (custom VJP, single fused backward kernel).
    """
    if interpret is None:
        interpret = use_interpret()
    if block_rows is None:
        block_rows = pick_block_rows(x.shape[-1])
    return _norm_per_device(
        _norm, (x,), (g, b), (eps, False, block_rows, interpret)
    )


def fused_rms_norm(
    x: jax.Array,
    g: jax.Array,
    eps: float = 1e-6,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """RMSNorm over the last axis (Llama family)."""
    if interpret is None:
        interpret = use_interpret()
    if block_rows is None:
        block_rows = pick_block_rows(x.shape[-1])
    return _norm_per_device(
        _norm, (x,), (g, None), (eps, True, block_rows, interpret)
    )


def fused_add_layer_norm(
    x: jax.Array,
    residual: jax.Array,
    g: jax.Array,
    b: Optional[jax.Array] = None,
    eps: float = 1e-5,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(norm(x + residual), x + residual) with the add fused into the
    norm kernel — the pre-norm residual branch point in one HBM pass
    (the reference's dropout_add_layer_norm at dropout 0,
    atorch/modules/transformer/layers.py:74). The second output is
    the input to the NEXT residual add.
    """
    if interpret is None:
        interpret = use_interpret()
    if block_rows is None:
        block_rows = pick_block_rows(x.shape[-1])
    return _norm_per_device(
        _add_norm, (x, residual), (g, b),
        (eps, False, block_rows, interpret),
    )


def fused_add_rms_norm(
    x: jax.Array,
    residual: jax.Array,
    g: jax.Array,
    eps: float = 1e-6,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(rmsnorm(x + residual), x + residual) — Llama residual spine."""
    if interpret is None:
        interpret = use_interpret()
    if block_rows is None:
        block_rows = pick_block_rows(x.shape[-1])
    return _norm_per_device(
        _add_norm, (x, residual), (g, None),
        (eps, True, block_rows, interpret),
    )
