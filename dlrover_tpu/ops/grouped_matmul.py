"""Grouped matrix products over ragged groups, as Pallas TPU kernels.

``gmm(lhs, rhs, group_sizes)``: ``lhs`` [m, k] holds ``g`` consecutive
groups of rows, group ``i`` of ``group_sizes[i]`` rows, and each group
is multiplied by its own ``rhs[i]`` [k, n] -> [m, n]. The expert layer
of a mixture-of-experts block is three of these (models/moe.py).

The shape is megablox's (Gale et al. 2022, "MegaBlocks"; the kernel of
that name in JAX's Pallas examples): rows are cut into tiles of ``tm``;
a tile that straddles a group boundary is visited once for every group
that has rows in it, each visit writing only its own rows; the list of
(group, row tile) visits is computed from ``group_sizes`` outside the
kernel and handed to it as scalar prefetch, so that the index maps can
fetch the right expert's matrix. ``k`` and ``n`` are not tiled: one
expert's whole matrix sits in VMEM while the kernel walks that group's
row tiles, so every expert matrix is read from HBM once.

``moe_gmm`` is the product and, with ``transpose_rhs``, its input
gradient; ``moe_tgmm`` is the weight gradient, ``lhs^T @ grad`` group
by group -> [g, k, n], accumulated in float32 over the group's row
tiles. ``gmm`` ties them together with a ``custom_vjp``. Off the TPU
the kernels are interpreted, like ops/flash_attention.py's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.parallel.mesh import use_interpret

# Rows a visit: the smaller the tile, the less of a boundary tile is
# computed for rows of another group, and the expert's matrix is
# resident whatever the tile. On a v5e at 131,072 x 2048 x 1024 in 64
# groups: 1024 rows 127 TFLOP/s, 512 147, 256 154-157 (PERF.md, PR 26).
ROW_TILE = 256


def _visits(group_sizes: jax.Array, m: int, tm: int, empty_too: bool):
    """The kernel's work list. Returns (offsets [g + 1], group_of
    [w], tile_of [w], n_visits []) with ``w = m // tm + g`` slots, the
    static upper bound; slots from ``n_visits`` on repeat the last
    visit and the kernel skips them. ``empty_too`` gives an empty
    group one visit (of a tile none of whose rows are its own), so
    that the weight-gradient kernel writes its zeros."""
    g = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32)
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    if empty_too:
        n_tiles = jnp.maximum(n_tiles, 1)
        first = jnp.minimum(first, tiles - 1)
    slots = tiles + g
    n_visits = jnp.sum(n_tiles)
    group_of = jnp.repeat(
        jnp.arange(g, dtype=jnp.int32), n_tiles, total_repeat_length=slots
    )
    visit_start = jnp.cumsum(n_tiles) - n_tiles
    slot = jnp.arange(slots, dtype=jnp.int32)
    tile_of = first[group_of] + slot - visit_start[group_of]
    # Unused slots: stay on the last real visit's blocks.
    last = jnp.maximum(n_visits - 1, 0)
    live = slot < n_visits
    group_of = jnp.where(live, group_of, group_of[last])
    tile_of = jnp.where(live, tile_of, tile_of[last])
    return (offsets, group_of.astype(jnp.int32), tile_of.astype(jnp.int32),
            n_visits.astype(jnp.int32).reshape(1))


def _own_rows(offsets_ref, group, tile, tm):
    """[tm, 1] mask: which rows of this tile belong to this group."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


def _vmem_limit(*block_bytes, scratch=0):
    """Double-buffered blocks, the float32 result tile Mosaic keeps
    beside them, and a margin; the v5e has 128 MiB."""
    need = 2 * sum(block_bytes) + scratch + (8 << 20)
    return min(need, 100 << 20)


def _pad_rows(x, tm):
    m = x.shape[0]
    pad = -m % tm
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), m


def _row_tile(m: int) -> int:
    return min(ROW_TILE, -(-m // 16) * 16)


def moe_gmm(lhs, rhs, group_sizes, transpose_rhs=False, interpret=None):
    """[m, k] x [g, k, n] (or [g, n, k] with ``transpose_rhs``) ->
    [m, n] in ``lhs``'s dtype."""
    if interpret is None:
        interpret = use_interpret()
    tm = _row_tile(lhs.shape[0])
    lhs, m = _pad_rows(lhs, tm)
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    offsets, group_of, tile_of, n_visits = _visits(
        group_sizes, lhs.shape[0], tm, empty_too=False
    )
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(offsets_ref, group_ref, tile_ref, n_ref, lhs_ref, rhs_ref, out_ref):
        w = pl.program_id(0)

        @pl.when(w < n_ref[0])
        def _():
            own = _own_rows(offsets_ref, group_ref[w], tile_ref[w], tm)
            acc = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[0], contract,
                preferred_element_type=jnp.float32,
            )
            out_ref[...] = jnp.where(own, acc.astype(out_ref.dtype), out_ref[...])

    item = lhs.dtype.itemsize
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(group_of.shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda w, o, g, t, c: (t[w], 0)),
                pl.BlockSpec((1,) + rhs.shape[1:], lambda w, o, g, t, c: (g[w], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda w, o, g, t, c: (t[w], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                tm * k * item, k * n * item, tm * n * item,
                scratch=tm * n * 4,
            ),
        ),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_of, tile_of, n_visits, lhs, rhs)
    return out[:m]


def moe_tgmm(lhs, grad, group_sizes, out_dtype=None, interpret=None):
    """[m, k]^T x [m, n], group by group -> [g, k, n]; an empty
    group's block is zeros."""
    if interpret is None:
        interpret = use_interpret()
    tm = _row_tile(lhs.shape[0])
    lhs, _ = _pad_rows(lhs, tm)
    grad, _ = _pad_rows(grad, tm)
    k, n, g = lhs.shape[1], grad.shape[1], group_sizes.shape[0]
    out_dtype = out_dtype or lhs.dtype
    offsets, group_of, tile_of, n_visits = _visits(
        group_sizes, lhs.shape[0], tm, empty_too=True
    )

    def kernel(offsets_ref, group_ref, tile_ref, n_ref, lhs_ref, grad_ref,
               out_ref, acc_ref):
        w = pl.program_id(0)
        group = group_ref[w]
        live = w < n_ref[0]
        first = (w == 0) | (group_ref[jnp.maximum(w - 1, 0)] != group)
        last = (w == n_ref[0] - 1) | (
            group_ref[jnp.minimum(w + 1, pl.num_programs(0) - 1)] != group
        )

        @pl.when(live & first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(live)
        def _():
            own = _own_rows(offsets_ref, group, tile_ref[w], tm)
            rows = jnp.where(own, lhs_ref[...], jnp.zeros_like(lhs_ref))
            acc_ref[...] += jax.lax.dot_general(
                rows, grad_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(live & last)
        def _():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)

    item = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(group_of.shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda w, o, g, t, c: (t[w], 0)),
                pl.BlockSpec((tm, n), lambda w, o, g, t, c: (t[w], 0)),
            ],
            out_specs=pl.BlockSpec((1, k, n), lambda w, o, g, t, c: (g[w], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                tm * k * item, tm * n * item,
                k * n * jnp.dtype(out_dtype).itemsize, scratch=2 * k * n * 4,
            ),
        ),
        interpret=interpret,
        name="moe_tgmm",
    )(offsets, group_of, tile_of, n_visits, lhs, grad)


@jax.custom_vjp
def gmm(lhs, rhs, group_sizes):
    """``lhs`` [m, k] in groups of ``group_sizes`` rows, each times its
    ``rhs[g]`` [k, n] -> [m, n]; differentiable in ``lhs`` and ``rhs``."""
    return moe_gmm(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return moe_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, grad):
    lhs, rhs, group_sizes = res
    d_lhs = moe_gmm(grad, rhs, group_sizes, transpose_rhs=True)
    d_rhs = moe_tgmm(lhs, grad, group_sizes, out_dtype=rhs.dtype)
    return d_lhs, d_rhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)
