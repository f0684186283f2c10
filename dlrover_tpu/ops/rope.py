"""Rotary position embedding on ``[B, T, H*D]``, the layout the
projections write, as one Pallas kernel.

``models/llama.apply_rope`` rotates a ``[B, T, H, D]`` view: slices of
half a head and a ``concatenate`` along the lanes. On the chip that
view is a copy of the whole array, and XLA writes a ``concatenate`` of
half-heads as padded halves and a second pass that adds them. Written
with slices of ``[B, T, H*D]`` instead it is worse: XLA makes a fusion
a head and an unfused ``concatenate`` of all of them (PERF.md section
6, PR 62). So where the attention operands keep one layout
(``ops/flash_attention.flash_attention_wide``) the rotation is this
kernel: one read and one write of the array where it lies.

A head's partner columns come by a rotation of the head's lanes
(``pltpu.roll``), the cosines and the signed sines as ``[T, D]`` rows
that every head shares (rounded to x's dtype as ``apply_rope`` rounds
its tables). The products and the sum are float32 and the result is
rounded once, which is what ``apply_rope`` is inside a compiled step,
where XLA fuses its multiplies and its subtraction and keeps the
excess precision between them (called op by op it rounds each product,
an ulp of x's dtype apart). The backward is the same kernel on the
cotangent with the sines negated.

Off the TPU the kernel runs interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import row_block
from dlrover_tpu.parallel.mesh import per_device, use_interpret

# Heads a grid step treats: 1024 rows x 4 heads of 128 is a megabyte
# of bf16 in and one out, double-buffered.
_HEADS = (4, 2, 1)


def _rotate_kernel(x_ref, c_ref, s_ref, y_ref, *, d, d2, heads):
    """``y = x * c + partner(x) * s`` a head, in float32, rounded
    once. ``c`` holds a head's cosines twice and ones behind them,
    ``s`` the sines negated, the sines, and zeros; a column's partner
    is ``d2`` lanes on in the first half of the rotated columns and
    ``d2`` back in the second."""
    c = c_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    for h in range(heads):
        cols = pl.ds(h * d, d)
        x = x_ref[0, :, cols].astype(jnp.float32)
        partner = pltpu.roll(x, d2, 1)  # column j holds x[j - d2]
        if 2 * d2 < d:
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            partner = jnp.where(lane < d2, pltpu.roll(x, d - d2, 1), partner)
        y = x * c + partner * s
        if 2 * d2 < d:  # the columns past the table pass through
            y = jnp.where(lane < 2 * d2, y, x)
        y_ref[0, :, cols] = y.astype(y_ref.dtype)


def _rotate(x, c, s, d, d2, interpret):
    b, t, e = x.shape
    rows = row_block(t)
    heads = next(n for n in _HEADS if (e // d) % n == 0)
    block = pl.BlockSpec((1, rows, heads * d), lambda b, i, h: (b, i, h))
    table = pl.BlockSpec((rows, d), lambda b, i, h: (i, 0))
    return pl.pallas_call(
        functools.partial(_rotate_kernel, d=d, d2=d2, heads=heads),
        # The heads innermost: a row block's tables are fetched once.
        grid=(b, t // rows, e // (heads * d)),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name="rope_wide",
    )(x, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rope(x, c, s, d, d2, interpret):
    return _rotate(x, c, s, d, d2, interpret)


def _rope_fwd(x, c, s, d, d2, interpret):
    return _rotate(x, c, s, d, d2, interpret), (c, s)


def _rope_bwd(d, d2, interpret, tables, g):
    # y = C x + S (R x) with R the swap of a head's halves, so
    # dx = C g + R (S g) = C g - S (R g): the same kernel, -S.
    c, s = tables
    return _rotate(g, c, -s, d, d2, interpret), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_wide(x, cos, sin, n_head, interpret=None):
    """x ``[B, T, H*D]`` rotated head by head as
    ``models/llama.apply_rope`` rotates its ``[B, T, H, D]`` view:
    split halves, a table narrower than half a head leaving the
    trailing columns as they are. ``cos`` / ``sin`` ``[T, rot/2]``.
    ``D`` is a multiple of 128 (a head is whole lanes). Under an
    ambient mesh each device rotates its own batch rows and heads."""
    if interpret is None:
        interpret = use_interpret()
    d = x.shape[-1] // n_head
    d2 = cos.shape[-1]
    if d % 128 or 2 * d2 > d or x.shape[-1] != n_head * d:
        raise ValueError(
            f"rope_wide rotates heads of whole lanes: {n_head} heads "
            f"of {x.shape[-1]} columns, a table of {d2}"
        )
    rest = (cos.shape[0], d - 2 * d2)
    c = jnp.concatenate([cos, cos, jnp.ones(rest, cos.dtype)], axis=-1)
    s = jnp.concatenate([-sin, sin, jnp.zeros(rest, sin.dtype)], axis=-1)
    c, s = c.astype(x.dtype), s.astype(x.dtype)
    return per_device(
        lambda x, c, s: _rope(x, c, s, d, d2, interpret),
        x, c, s, split=(True, False, False), heads_dim=2, head_size=d,
    )
