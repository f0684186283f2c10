"""Blockwise quantization kernels (Pallas).

TPU-native replacement for the reference's CUDA quantization suite
(atorch/ops/csrc/quantization/{quantize,dequantize,swizzled_quantize,
quant_reduce}.cu and the fused quantized-state optimizer kernel,
pt_binding.cpp:152-176). Symmetric per-block quantization: each block
of ``block_size`` contiguous values shares one float32 scale. Two bit
widths, matching the reference kernels' 4/8-bit support:

* int8 (scale = absmax/127), 1 byte/value;
* packed int4 (two nibbles per uint8 byte), 0.5 bytes/value — signed
  levels -7..7 for sign-changing state, unsigned 0..15 for
  non-negative state like sqrt(v).

Backs the low-bit optimizer states of optim/low_bit.py. The kernels
run compiled on TPU and interpreted on CPU (tests). Shapes are
flattened to [num_blocks, block_size]; block_size should be a
multiple of 128 (lane width). jnp reference paths are exported as the
ground truth in tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.parallel.mesh import use_interpret

DEFAULT_BLOCK = 1024
# Rows of blocks processed per kernel grid step (sublane packing).
_ROWS = 8


# ---------------------------------------------------------------------------
# Shared host-side scaffolding (flatten -> block rows -> pallas grid)
# ---------------------------------------------------------------------------


def _to_block_rows(x, block_size):
    """x (any shape) -> (x2 [rows_padded, block], true rows, shape)."""
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.size // block_size
    x2 = flat.reshape(rows, block_size)
    row_pad = (-rows) % _ROWS
    if row_pad:
        x2 = jnp.pad(x2, ((0, row_pad), (0, 0)))
    return x2, rows, shape


def _row_spec(width):
    return pl.BlockSpec(
        (_ROWS, width), lambda i: (i, 0), memory_space=pltpu.VMEM
    )


def _quant_call(kernel, x2, out_width, out_dtype, name):
    """Run a quantize kernel over block rows -> (q, scales). ``name``
    is what the device trace calls it."""
    grid = x2.shape[0] // _ROWS
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[_row_spec(x2.shape[1])],
        out_specs=[_row_spec(out_width), _row_spec(1)],
        out_shape=[
            jax.ShapeDtypeStruct((x2.shape[0], out_width), out_dtype),
            jax.ShapeDtypeStruct((x2.shape[0], 1), jnp.float32),
        ],
        interpret=use_interpret(),
        name=name,
    )(x2)


def _dequant_call(kernel, q, scales, block_size, dtype, name):
    """Run a dequantize kernel -> values [rows_padded, block]."""
    rows = q.shape[0]
    row_pad = (-rows) % _ROWS
    if row_pad:
        q = jnp.pad(q, ((0, row_pad), (0, 0)))
        scales = jnp.pad(scales, ((0, row_pad), (0, 0)))
    grid = q.shape[0] // _ROWS
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[_row_spec(q.shape[1]), _row_spec(1)],
        out_specs=_row_spec(block_size),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], block_size), dtype),
        interpret=use_interpret(),
        name=name,
    )(q, scales)


def _unflatten(out, rows, shape):
    n = 1
    for s in shape:
        n *= s
    return out[:rows].reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# int8 kernels
# ---------------------------------------------------------------------------


def _quantize_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[:].astype(jnp.float32)  # (_ROWS, block)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 127.0
    safe = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x / safe), -127, 127)
    q_ref[:] = q.astype(jnp.int8)
    scale_ref[:] = scale


def _dequantize_kernel(q_ref, scale_ref, out_ref):
    out_ref[:] = (
        q_ref[:].astype(jnp.float32) * scale_ref[:]
    ).astype(out_ref.dtype)


def quantize_blockwise(
    x: jax.Array, block_size: int = DEFAULT_BLOCK
) -> Tuple[jax.Array, jax.Array, Tuple[int, ...]]:
    """x (any shape) -> (int8 values [n_blocks, block], f32 scales
    [n_blocks, 1], original shape). Tail is zero-padded (zero maps to
    zero exactly, so padding never perturbs scales of real data beyond
    the shared block — callers with hard accuracy needs should size
    params to block multiples)."""
    x2, rows, shape = _to_block_rows(x, block_size)
    q, scales = _quant_call(
        _quantize_kernel, x2, block_size, jnp.int8, "quantize_int8"
    )
    return q[:rows], scales[:rows], shape


def dequantize_blockwise(
    q: jax.Array,
    scales: jax.Array,
    shape: Tuple[int, ...],
    dtype=jnp.float32,
) -> jax.Array:
    rows, block_size = q.shape
    out = _dequant_call(
        _dequantize_kernel, q, scales, block_size, dtype, "dequantize_int8"
    )
    return _unflatten(out, rows, shape)


# ---------------------------------------------------------------------------
# 4-bit (packed) kernels — two nibbles per uint8 byte
# ---------------------------------------------------------------------------
#
# Packing layout pairs element i with element i + block/2 (first half
# of the block in the low nibble, second half in the high nibble) so
# the kernel slices are contiguous lane runs, not stride-2 gathers.


def _quantize4_kernel(x_ref, q_ref, scale_ref, *, signed: bool):
    x = x_ref[:].astype(jnp.float32)  # (_ROWS, block)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    levels = 7.0 if signed else 15.0
    scale = absmax / levels
    safe = jnp.maximum(scale, 1e-30)
    if signed:
        q = jnp.clip(jnp.round(x / safe), -7, 7) + 8.0  # 1..15
    else:
        q = jnp.clip(jnp.round(x / safe), 0, 15)
    q = q.astype(jnp.int32)
    half = q.shape[1] // 2
    packed = q[:, :half] | (q[:, half:] << 4)
    q_ref[:] = packed.astype(jnp.uint8)
    scale_ref[:] = scale


def _dequantize4_kernel(q_ref, scale_ref, out_ref, *, signed: bool):
    p = q_ref[:].astype(jnp.int32)
    lo = p & 15
    hi = (p >> 4) & 15
    if signed:
        lo = lo - 8
        hi = hi - 8
    vals = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    out_ref[:] = (vals * scale_ref[:]).astype(out_ref.dtype)


def quantize_blockwise_4bit(
    x: jax.Array,
    block_size: int = DEFAULT_BLOCK,
    signed: bool = True,
) -> Tuple[jax.Array, jax.Array, Tuple[int, ...]]:
    """x (any shape) -> (uint8 packed [n_blocks, block/2], f32 scales
    [n_blocks, 1], original shape). 0.5 bytes/value + scale. signed:
    levels -7..7 (scale absmax/7); unsigned: 0..15 (absmax/15 — twice
    the resolution for non-negative state)."""
    x2, rows, shape = _to_block_rows(x, block_size)
    q, scales = _quant_call(
        functools.partial(_quantize4_kernel, signed=signed),
        x2, block_size // 2, jnp.uint8, "quantize_4bit",
    )
    return q[:rows], scales[:rows], shape


def dequantize_blockwise_4bit(
    q: jax.Array,
    scales: jax.Array,
    shape: Tuple[int, ...],
    signed: bool = True,
    dtype=jnp.float32,
) -> jax.Array:
    rows, half = q.shape
    out = _dequant_call(
        functools.partial(_dequantize4_kernel, signed=signed),
        q, scales, half * 2, dtype, "dequantize_4bit",
    )
    return _unflatten(out, rows, shape)


# ---------------------------------------------------------------------------
# jnp references (ground truth for tests; also handle tiny arrays)
# ---------------------------------------------------------------------------


def quantize_blockwise_ref(x, block_size: int = DEFAULT_BLOCK):
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, block_size)
    scale = jnp.max(jnp.abs(x2), axis=1, keepdims=True) / 127.0
    safe = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x2 / safe), -127, 127).astype(jnp.int8)
    return q, scale, shape


def dequantize_blockwise_ref(q, scales, shape, dtype=jnp.float32):
    out = q.astype(jnp.float32) * scales
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


def quantize_blockwise_4bit_ref(
    x, block_size: int = DEFAULT_BLOCK, signed: bool = True
):
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, block_size)
    levels = 7.0 if signed else 15.0
    scale = jnp.max(jnp.abs(x2), axis=1, keepdims=True) / levels
    safe = jnp.maximum(scale, 1e-30)
    if signed:
        q = (jnp.clip(jnp.round(x2 / safe), -7, 7) + 8).astype(jnp.int32)
    else:
        q = jnp.clip(jnp.round(x2 / safe), 0, 15).astype(jnp.int32)
    half = block_size // 2
    packed = (q[:, :half] | (q[:, half:] << 4)).astype(jnp.uint8)
    return packed, scale, shape


def dequantize_blockwise_4bit_ref(
    q, scales, shape, signed: bool = True, dtype=jnp.float32
):
    p = q.astype(jnp.int32)
    lo, hi = p & 15, (p >> 4) & 15
    if signed:
        lo, hi = lo - 8, hi - 8
    vals = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    out = vals * scales
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
