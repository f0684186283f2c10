"""Rows summed back by token, as one Pallas TPU kernel.

``rows_sum(rows, token, weight, visits, n)``: ``rows`` [cap, D] is
the held expert path's buffer (models/moe.py), sorted by expert and,
inside an expert's group, ascending by token; ``token`` [cap] says
whose row each is. Returns [n, D] float32, the sum of every token's
rows (each times its ``weight`` [cap] float32, if given), zero for a
token with none.

What the order allows: a token stands at most once in a group, so for
a tile of ``TOKEN_TILE`` tokens each group holds one contiguous range
of rows, at most a tile long. The windows (lo, hi) [tiles, groups]
name those ranges (from ``pairs_before``, a cumulative count of the
0/1 choices); rows outside every range (the rows past the held pairs,
another block's) are never summed. The kernel walks ``visits``, a
list of (token tile, chunk of ``CHUNK`` rows), tile by tile,
a tile's float32 sums resident in VMEM the while: a range's chunks,
a chunk that several ranges share once for each. A visit picks its
rows by a product on the MXU, 0/1 ``[tile, chunk]`` by the rows
``[chunk, D]`` with float32 accumulation. One term a row of the
result is not zero (the token stands once in the window), so the
product is an exact selection whatever the rows' dtype holds; the
float32 weight, picked the same way on the VPU, multiplies it
afterwards: float32 products of the rows and the weights, added in
float32, as the plain form had them, group by group.

The list has ``cap / CHUNK + tiles x groups`` slots, its static upper
bound, and a slot past the counted visits does a visit's work on an
empty window: a call's time is the buffer's and the list's, whatever
the router sent (the same rule as ``moe._held_block``'s grouped
products; PERF.md section 6, PRs 53 and 60). Off the TPU the kernel
is interpreted, like ops/grouped_matmul.py's.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.grouped_matmul import _vmem_limit
from dlrover_tpu.parallel.mesh import use_interpret

# Tokens a tile and rows a chunk. The product's cost follows the tile
# (every chunk is multiplied by a whole tile's selector) and the list's
# length falls with it; on a v5e at the three held cells' shapes
# (PERF.md section 6, PR 60, the kernel-alone sweep).
TOKEN_TILE = 256
CHUNK = 128


def token_tile(n: int) -> int:
    """Tokens a tile for ``n`` tokens (whole sublanes)."""
    return min(TOKEN_TILE, -(-n // 8) * 8)


def row_chunk(cap: int) -> int:
    """Rows a chunk of a buffer of ``cap`` rows."""
    return min(CHUNK, cap)


def layout(n: int, cap: int, groups: int) -> Dict[str, int]:
    """The static sizes of a call: tokens a tile and the visits the
    kernel walks."""
    tile, chunk = token_tile(n), row_chunk(cap)
    return {
        "tile": tile, "visits": -(-cap // chunk) + -(-n // tile) * groups,
    }


def pairs_before(local, groups: int):
    """local [n, k] int32, each pair's group (``groups`` for none, a
    token in a group at most once) -> [tiles + 1, groups] int32: of
    each group, the pairs of the tokens before each tile of tokens,
    which is where the tile's range starts in the group's rows."""
    n = local.shape[0]
    tile = token_tile(n)
    tiles = -(-n // tile)
    chose = jnp.any(
        local[:, :, None] == jnp.arange(groups, dtype=local.dtype), axis=1
    )
    chose = _pad_rows(chose, tiles * tile).reshape(tiles, tile, groups)
    per_tile = jnp.sum(chose, axis=1, dtype=jnp.int32)
    return jnp.concatenate(
        [jnp.zeros((1, groups), jnp.int32), jnp.cumsum(per_tile, axis=0)]
    )


def visits(lo, hi, cap: int):
    """The kernel's work list from the windows (lo, hi) [tiles, groups]
    of a buffer of ``cap`` rows: (tile_of, chunk_of, lo_of, hi_of),
    each [cap / chunk + tiles x groups] int32, the list's static
    bound. A window's chunks in order, tile by tile; a tile with no
    row gets one visit of an empty window, so that its zeros are
    written; the slots past the counted visits stay on the last tile,
    windows empty, each on a chunk of its own (a chunk read again
    costs no copy, and a call's time would follow the count). Formed
    once a block's plan: the forward's call and the backward's walk
    the same list."""
    chunk = row_chunk(cap)
    chunks = -(-cap // chunk)
    slots = chunks + lo.size
    tiles, groups = lo.shape
    first = jnp.minimum(lo // chunk, chunks - 1)
    count = jnp.where(hi > lo, (hi - 1) // chunk - first + 1, 0)
    count = count.at[:, 0].add(jnp.sum(count, axis=1) == 0)
    lo, hi, first, count = (x.reshape(-1) for x in (lo, hi, first, count))
    start = jnp.cumsum(count) - count
    pair = jnp.repeat(
        jnp.arange(tiles * groups, dtype=jnp.int32), count,
        total_repeat_length=slots,
    )
    slot = jnp.arange(slots, dtype=jnp.int32)
    live = slot < jnp.sum(count)
    return tuple(x.astype(jnp.int32) for x in (
        jnp.where(live, pair // groups, tiles - 1),
        jnp.where(live, first[pair] + slot - start[pair], slot % chunks),
        jnp.where(live, lo[pair], 0),
        jnp.where(live, hi[pair], 0),
    ))


def _pad_rows(x, to: int):
    pad = to - x.shape[0]
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x


@functools.partial(
    jax.jit, static_argnames=("n", "tile", "chunk", "interpret")
)
def _call(rows, token, weight, visits, *, n, tile, chunk, interpret):
    cap, d = rows.shape
    tiles, chunks = -(-n // tile), -(-cap // chunk)
    rows = _pad_rows(rows, chunks * chunk)
    token = _pad_rows(token.astype(jnp.int32), chunks * chunk)[None]
    weighted = weight is not None
    operands = [token]
    if weighted:
        operands.append(
            _pad_rows(weight.astype(jnp.float32), chunks * chunk)[None]
        )
    # A float32 selection has to stay one: the MXU's default rounds
    # float32 operands to bf16 passes.
    exact = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None

    def kernel(tile_ref, chunk_ref, lo_ref, hi_ref, token_ref, *refs):
        rows_ref, out_ref = refs[-2:]
        s = pl.program_id(0)
        row = chunk_ref[s] * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1
        )
        inside = (row >= lo_ref[s]) & (row < hi_ref[s])
        whose = jnp.where(inside, token_ref[...], -1)  # [1, chunk]
        mine = tile_ref[s] * tile + jax.lax.broadcasted_iota(
            jnp.int32, (tile, chunk), 0
        )
        pick = mine == whose  # [tile, chunk], at most one a row
        scale = jnp.sum(
            jnp.where(pick, refs[0][...], 0.0), axis=1, keepdims=True
        ) if weighted else None
        pick = pick.astype(rows_ref.dtype)
        opens = (s == 0) | (tile_ref[s] != tile_ref[jnp.maximum(s - 1, 0)])

        def visit(add):
            # The product inside each branch: its result goes straight
            # into the tile's sums (a tenth faster than one product
            # ahead of both, PR 60's sweep).
            got = jnp.dot(
                pick, rows_ref[...],
                preferred_element_type=jnp.float32, precision=exact,
            )
            if weighted:
                got = got * scale
            out_ref[...] = out_ref[...] + got if add else got

        pl.when(opens)(lambda: visit(False))
        pl.when(jnp.logical_not(opens))(lambda: visit(True))

    by_chunk = lambda s, t, c, lo, hi: (0, c[s])
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tiles * tile, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=visits[0].shape,
            in_specs=[pl.BlockSpec((1, chunk), by_chunk)] * len(operands) + [
                pl.BlockSpec((chunk, d), lambda s, t, c, lo, hi: (c[s], 0)),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda s, t, c, lo, hi: (t[s], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # The chunk and the tile's sums, and a visit's product
            # beside them before it is added.
            vmem_limit_bytes=_vmem_limit(
                chunk * d * rows.dtype.itemsize, tile * d * 4,
                scratch=tile * d * 4,
            ),
        ),
        interpret=interpret,
        name="moe_rows_sum",
    )(*visits, *operands, rows)
    return out[:n]


def rows_sum(
    rows: jax.Array,  # [cap, D]
    token: jax.Array,  # [cap] int32
    weight: Optional[jax.Array],  # [cap] float32, or None
    visits: Tuple[jax.Array, ...],  # ``visits(lo, hi, cap)``
    n: int,
) -> jax.Array:
    """[n, D] float32: every token's rows summed. One jitted call, so
    that a stack of layers traces and lowers the kernel once a shape."""
    return _call(
        rows, token, weight, visits, n=n, tile=token_tile(n),
        chunk=row_chunk(rows.shape[0]), interpret=use_interpret(),
    )
