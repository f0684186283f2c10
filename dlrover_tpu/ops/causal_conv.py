"""A Mamba mixer's short convolution: a depthwise causal convolution
along the sequence, with bias, then SiLU, forward and backward as
one kernel each.

    pre[t] = bias + sum_k w[k] * x[t - (K-1-k)]          (zero before 0)
    y      = silu(pre)               float32, rounded once to x's dtype
    g      = dy * silu'(pre)                  (pre recomputed from x)
    dx[t]  = sum_k w[k] * g[t + (K-1-k)]      (zero beyond T)
    dw[k]  = sum_bt g[t] * x[t - (K-1-k)]
    dbias  = sum_bt g[t]

float32 throughout, each result rounded once to its input's dtype.

Why kernels: written as pad, shifted slices and multiply-adds, every
shifted slice of a [T, C] operand is a read of all of it from
wherever XLA placed it, and autodiff's transpose is four float32
[T, C] pads of ``g * w[k]``: 1.0 ms a layer backward at Granite's
widths for 107 MB of required traffic, and a forward whose time
follows the compiler's choice of memory space for a float32 copy of
``x`` (PERF.md section 6, PR 52). The kernels read ``x`` (and the
cotangent) once, shift rows in VMEM and write once.

``x`` is read in place: the convolution takes the columns
``[start, start + C)`` of a wider array (the mixer's projection
``[z | xBC | dt]``), so no copy of the slice is made for the custom
call. The residuals are the three inputs as they came: under
``remat="full"`` the wide array is the mixer's kept projection and
nothing new is held.

Grid: batch x column blocks x row tiles. A tile's neighbours (K-1
rows of ``x`` before it; in the backward K-1 of ``x`` and ``dy``
after it too) come as blocks of ``_HALO`` rows of the same arrays;
a kernel first lays the tile and its neighbours side by side in
float32 scratch, then walks it ``_CHUNK`` rows at a time, so that a
chunk's taps, pre-activation and ``g`` stay in registers. The
backward's row tiles run in turn and a column block's ``dw`` and
``dbias`` accumulate in its output block. Under an ambient mesh the
whole ``custom_vjp`` runs once per device on its batch rows
(``parallel.mesh.per_device``); interpreted off the TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu import obs
from dlrover_tpu.parallel.mesh import (
    batch_axes,
    per_device,
    use_interpret,
)

# Rows of the blocks that carry a tile's neighbours: bf16's sublane
# tile, two of float32's.
_HALO = 16
_ROWS = 512  # a tile's rows
_LANES = 256  # a column block's width, where the columns allow
_CHUNK = 64  # rows a pass of a kernel's loop holds in registers
_AHEAD = 8  # rows of g a chunk forms past its own: K-1 of them are read


def _round_up(n, to):
    return -(-n // to) * to


def _lane_block(channels, start):
    """The widest whole number of 128 lanes, at most ``_LANES``, that
    tiles both the convolution's columns and their offset; 0 if none."""
    both = math.gcd(channels, start)
    for lanes in range(_LANES, 0, -128):
        if both % lanes == 0:
            return lanes
    return 0


def _ahead(v, offset, rows):
    """``v[offset:offset + rows]`` of a float32 [n, lanes] value: the
    rows rotate along the sublanes where the offset is no whole tile."""
    if offset % 8:
        v = pltpu.roll(v, v.shape[0] - offset, 0)
        offset = 0
    return v[offset:offset + rows]


def _fill(scratch, at, block, blank):
    """A block's rows into the float32 scratch from row ``at``, zeros
    where the block lies outside the sequence."""
    rows = block.shape[1]
    scratch[at:at + rows] = jnp.where(
        blank, 0.0, block[0].astype(jnp.float32)
    )


def _groups_of_8(v):
    """[n, lanes] -> [8, lanes]: each sublane's rows summed, adds of
    whole registers; the eight are summed once a tile."""
    return sum(v[r:r + 8] for r in range(0, v.shape[0], 8))


def _fwd_kernel(x_lo, x, w, bias, y, xs, *, width):
    _fill(xs, 0, x_lo, pl.program_id(2) == 0)
    _fill(xs, _HALO, x, False)
    wf = w[...].astype(jnp.float32)
    bf = bias[...].astype(jnp.float32)

    def chunk(j, carry):
        r0 = pl.multiple_of(j * _CHUNK, _CHUNK)
        window = xs[pl.ds(r0, _HALO + _CHUNK), :]
        pre = bf
        for k in range(width):
            tap = _ahead(window, _HALO - (width - 1) + k, _CHUNK)
            pre = pre + tap * wf[k:k + 1]
        y[0, pl.ds(r0, _CHUNK), :] = (
            pre * jax.nn.sigmoid(pre)
        ).astype(y.dtype)
        return carry

    jax.lax.fori_loop(0, x.shape[1] // _CHUNK, chunk, 0)


def _bwd_kernel(
    x_lo, x, x_hi, dy, dy_hi, w, bias, dx, sums, xs, ds, *, width, tiles
):
    i = pl.program_id(2)
    first, last = i == 0, i == tiles - 1
    rows = x.shape[1]
    _fill(xs, 0, x_lo, first)
    _fill(xs, _HALO, x, False)
    _fill(xs, _HALO + rows, x_hi, last)
    _fill(ds, 0, dy, False)
    _fill(ds, rows, dy_hi, last)
    wf = w[...].astype(jnp.float32)
    bf = bias[...].astype(jnp.float32)
    ext = _CHUNK + _AHEAD  # g is formed K-1 rows past the chunk

    def chunk(j, acc):
        r0 = pl.multiple_of(j * _CHUNK, _CHUNK)
        window = xs[pl.ds(r0, _HALO + ext), :]
        # taps[k][r] = x[r0 + r - (K-1-k)]
        taps = [
            _ahead(window, _HALO - (width - 1) + k, ext)
            for k in range(width)
        ]
        pre = bf
        for k in range(width):
            pre = pre + taps[k] * wf[k:k + 1]
        s = jax.nn.sigmoid(pre)
        g = ds[pl.ds(r0, ext), :] * (s * (1.0 + pre * (1.0 - s)))
        out = _ahead(g, width - 1, _CHUNK) * wf[0:1]
        for k in range(1, width):
            out = out + _ahead(g, width - 1 - k, _CHUNK) * wf[k:k + 1]
        dx[0, pl.ds(r0, _CHUNK), :] = out.astype(dx.dtype)
        own = g[:_CHUNK]
        terms = [own * tap[:_CHUNK] for tap in taps] + [own]
        return tuple(a + _groups_of_8(v) for a, v in zip(acc, terms))

    zero = jnp.zeros((8, x.shape[2]), jnp.float32)
    acc = jax.lax.fori_loop(0, rows // _CHUNK, chunk, (zero,) * (width + 1))

    @pl.when(first)
    def _():
        sums[...] = jnp.zeros_like(sums)

    for k, a in enumerate(acc):
        sums[0, k:k + 1, :] += jnp.sum(a, axis=0, keepdims=True)


def _tile_rows(t):
    return min(_ROWS, _round_up(t, _CHUNK))


def _in_place(t, channels, start):
    """Whether whole tiles and blocks cover the convolution's rows and
    columns, so that the kernels read the wide array as it is."""
    return t % _tile_rows(t) == 0 and bool(_lane_block(channels, start))


def _layout(source, channels, start):
    """(the array the kernels read, the first column block of theirs
    in it, rows a tile, lanes a block, tiles): the wide array in
    place, or a padded copy of its slice (no cell's shape)."""
    t = source.shape[1]
    rows = _tile_rows(t)
    lanes = _lane_block(channels, start)
    if not _in_place(t, channels, start):
        source = jnp.pad(
            source[..., start:start + channels],
            ((0, 0), (0, _round_up(t, rows) - t), (0, 0)),
        )
        # Whole where no block of lanes divides them (a test's width).
        start, lanes = 0, lanes or channels
    return source, start // lanes, rows, lanes, source.shape[1] // rows


def _specs(first_block, rows, lanes, tiles, width):
    """Block specs over the grid (batch, column block, row tile): a
    tile of a [B, T, C] array, then of the wide array ``x`` is read
    from, ``x``'s ``_HALO`` rows before and after a tile, a [B, T, C]
    array's ``_HALO`` rows after a tile, and the weights'."""
    per_tile = rows // _HALO

    def before(i):
        return jnp.maximum(i * per_tile - 1, 0)

    def after(i):
        return jnp.minimum((i + 1) * per_tile, tiles * per_tile - 1)

    def in_x(block, row_block):
        return pl.BlockSpec(
            block, lambda b, c, i: (b, row_block(i), c + first_block)
        )

    return (
        pl.BlockSpec((1, rows, lanes), lambda b, c, i: (b, i, c)),
        in_x((1, rows, lanes), lambda i: i),
        in_x((1, _HALO, lanes), before),
        in_x((1, _HALO, lanes), after),
        pl.BlockSpec((1, _HALO, lanes), lambda b, c, i: (b, after(i), c)),
        [
            pl.BlockSpec((width, lanes), lambda b, c, i: (0, c)),
            pl.BlockSpec((1, lanes), lambda b, c, i: (0, c)),
        ],
    )


def _forward(source, w, bias, start, interpret):
    bsz, t, _ = source.shape
    width, channels = w.shape
    source, first_block, rows, lanes, tiles = _layout(source, channels, start)
    tile, x_tile, x_before, _, _, weights = _specs(
        first_block, rows, lanes, tiles, width
    )
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, width=width),
        grid=(bsz, channels // lanes, tiles),
        in_specs=[x_before, x_tile, *weights],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(
            (bsz, tiles * rows, channels), source.dtype
        ),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="conv_silu_fwd",
    )(source, source, w, bias.reshape(1, channels))
    return y[:, :t]


def _backward(source, w, bias, dy, start, interpret):
    bsz, t, wide = source.shape
    width, channels = w.shape
    x, first_block, rows, lanes, tiles = _layout(source, channels, start)
    dy = jnp.pad(dy, ((0, 0), (0, tiles * rows - t), (0, 0)))
    tile, x_tile, x_before, x_after, dy_after, weights = _specs(
        first_block, rows, lanes, tiles, width
    )
    sum_rows = _round_up(width + 1, 8)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, tiles=tiles),
        grid=(bsz, channels // lanes, tiles),
        in_specs=[x_before, x_tile, x_after, tile, dy_after, *weights],
        out_specs=[
            tile,
            pl.BlockSpec((1, sum_rows, lanes), lambda b, c, i: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(dy.shape, source.dtype),
            jax.ShapeDtypeStruct((bsz, sum_rows, channels), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2 * _HALO + rows, lanes), jnp.float32),
            pltpu.VMEM((_HALO + rows, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="conv_silu_bwd",
    )(x, x, x, dy, dy, w, bias.reshape(1, channels))
    sums = jnp.sum(sums, axis=0)
    # The columns beside the convolution's own had no part in it: the
    # pad is what autodiff gives a slice, and XLA folds it into the
    # concatenation that the cotangent's consumer reads.
    dsource = jnp.pad(
        dx[:, :t], ((0, 0), (0, 0), (start, wide - start - channels))
    )
    return (
        dsource, sums[:width].astype(w.dtype), sums[width].astype(bias.dtype)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_silu(source, w, bias, start, interpret):
    return _forward(source, w, bias, start, interpret)


def _conv_silu_fwd(source, w, bias, start, interpret):
    return _conv_silu(source, w, bias, start, interpret), (source, w, bias)


def _conv_silu_bwd(start, interpret, res, dy):
    return _backward(*res, dy, start, interpret)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x, w, bias, start: int = 0, interpret=None):
    """SiLU of the depthwise causal convolution, along T, of the
    columns ``[start, start + C)`` of x [B, T, >= start + C] with w
    [K, C] (``w[k]`` multiplies the input ``K - 1 - k`` tokens back)
    and bias [C]: [B, T, C] in x's dtype. Differentiable in x, w and
    bias; x's other columns get a zero cotangent."""
    if w.shape[0] - 1 > _AHEAD:
        raise ValueError(
            f"a convolution {w.shape[0]} wide reaches past the {_AHEAD} "
            "rows a chunk of the backward looks ahead"
        )
    if interpret is None:
        interpret = use_interpret()
    obs.event(
        "ssm.conv", width=w.shape[0], channels=w.shape[1], start=start,
        in_place=_in_place(x.shape[1], w.shape[1], start),
        residuals=["x", "w", "bias"],
        per_device=bool(batch_axes(x.shape[0])[0]),
    )
    return per_device(
        lambda x, w, bias: _conv_silu(x, w, bias, start, interpret),
        x, w, bias, split=(True, False, False),
    )
