"""Kimi Delta Attention's rule in chunks: a gated delta rule whose
decay is a channel of the key (Kimi Linear, arXiv:2510.26692).

The recurrence, for one head with a float32 state ``S`` of shape
``[d_k, d_v]``, zero before the first token:

    S' = diag(a_t) S_{t-1}                 a_t = exp(g_t) in (0, 1]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Written a token at a time it is T steps of rank-one updates
(:func:`recurrence`, what the tests hold the chunked form to). In
chunks of ``C`` tokens, with ``G`` the running sum of ``g`` inside a
chunk (inclusive) and ``S0`` the state the chunk starts from:

    u_t = beta_t (v_t - S'^T_t k_t)        the rows of U solve
    (I + A) U = diag(beta) (V - (K * exp(G)) S0)
        A[t, j] = beta_t sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c]), j < t
    o  = (Q * exp(G)) S0 + B U
        B[t, j] = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c]),        j <= t
    S1 = diag(exp(G_C)) S0 + (K * exp(G_C - G))^T U

Two hazards shape the code.

* A decay is only ever formed as ``exp(G_t - G_j)`` with ``t >= j``.
  With the family's initial values ``g`` reaches -1.6 a token, so a
  chunk's ``G`` reaches -100 and ``exp(-G)`` overflows float32: ``A``
  and ``B`` cannot be ``(K exp(G)) (K exp(-G))^T``. The chunk is cut
  into sub-blocks of ``sub`` rows. A pair in two sub-blocks goes
  through the later one's first row ``R``: ``exp(G_t - R) exp(R - G_j)``,
  both factors at most 1, and is a matrix product. A pair inside one
  sub-block has no row between them to go through, and its decay is
  formed outright, ``sub`` x ``sub`` x ``d_k`` elementwise.
* ``(I + A)^-1`` is the inverse of a unit lower triangle. Forward
  substitution inside diagonal blocks of 16 rows, then the inverse of
  a block lower triangle twice over (16 -> 32 -> 64), products on the
  MXU in float32. The shorter form ``(I + N)(I + N^2)(I + N^4)...``
  of the nilpotent ``N = -A`` is not stable (``_unit_lower_inverse``
  says where it failed).

Kernels ``kda_fwd`` and ``kda_bwd`` behind one ``jax.custom_vjp``,
where the head sizes are whole lanes (multiples of 128) and the chunk
is 64: grid (batch, block of heads, chunk), the chunk axis sequential
with every head's float32 state (forward) or its cotangent (backward,
chunks in reverse) in VMEM scratch, transposed ``[d_v, d_k]`` so that a
chunk's decay scales it along the lanes. A step takes 8 heads (all of
them where 8 does not divide them) in a rolled loop, a head's q, k, v
(the dtype they come in) and g (float32) as ``[64, 128]`` blocks of the
``[B, T, H*d]`` views where they lie, beta a head a lane-dense row;
the running sum of ``g`` (a product with a triangle of ones, ``g`` as
three bf16 pieces that add up to it, so exact), both pair matrices,
the inverse, ``u`` and the output never leave VMEM. The pairs inside a
sub-block go a column of the four diagonal blocks a step: the decayed
rows against row ``j`` of their own sub-block as one product on the
MXU; the 16 steps stand in line (rolled, the kernels took twice the
time on the chip), columns 8 to 15 on the sub-blocks' lower halves
alone. The inverse is the substitution in the four diagonal blocks
stacked, then the two merges on the blocks themselves, float32
products. Here ``u`` is solved in one:
``U = T beta (V - (K exp(G)) S0)``. q, k, v enter products in their
dtype and sum in float32 (:func:`_dot`), the state as its high and low
halves (:func:`_dot_state`), the inverse's merges, ``u`` and ``K^T U``
into the state in float32 in fact (:func:`_dot32`). The forward writes
every chunk's incoming state beside ``o``; they and the five operands
are all the backward takes, named for ``remat="full"``
(accelerate/remat.py ``KDA_O``, ``KDA_STATES``) so that a
rematerialised block does not run ``kda_fwd`` again. ``kda_bwd`` forms
the chunk's matrices again from the operands and the kept state,
walks the cotangents back through the solve (``d A = -(T^T dU) U^T``),
the pair matrices and the decays (a decay ``exp(G_t - G_j)`` scales
what row t reads and divides what row j writes, so over the pairs
``dG = q dq + k (dk_later - dk_earlier)``), and sums ``dG`` back up
the chunk to ``dg`` itself; its products on the cotangents' way take
their operands in the data's dtype, as autodiff's of the plain form's
do, the solve's transpose in float32. Off the TPU the kernels are
interpreted.

The plain form, ``jax.numpy`` under XLA, is the path for every other
shape (:func:`kda` chooses by the shapes alone; event ``kda.scan``
says ``kernel``) and what the benchmark's controls take apart.
:func:`kda_wide` is the same rule for a caller whose operands stay
``[B, T, H*d]`` (models/kimi_linear.py): they reach the kernels as
they lie and ``kda.scan`` says ``wide``; :func:`kda` views its
``[B, T, H, d]`` operands so and views the output back. There
what is sequential is the state alone (``_chunk_states``): a
``lax.scan`` over the chunks of two products a step, with a backward
rule of its own, the same two products a step on the states'
cotangents in reverse; everything else is batched over the chunks and
differentiated by JAX. Its stages stand under scopes of their own
(``kda_chunks``, ``kda_pairs``, ``kda_solve``, ``kda_states``,
``kda_out``) for whoever reads a profile by ``op_name``; the
benchmark reads the caller's ``kda_scan`` around either form. Under an
ambient mesh the call runs once per device on its batch rows
(``parallel.mesh.per_device``), as the package's kernels do.
"""

from __future__ import annotations

import functools

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

CHUNK = 64
SUB_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _f32_product(spec, a, b):
    """A float32 product in fact: the TPU's default would round both
    sides to bf16 first."""
    return jnp.einsum(
        spec, a, b, precision=_HIGHEST, preferred_element_type=jnp.float32
    )


def _product(spec, a, b, dtype):
    """``a`` and ``b`` rounded to the dtype the data came in (bf16 on
    the training path, where the MXU takes them in one pass), summed
    in float32."""
    if dtype == jnp.float32:
        return _f32_product(spec, a, b)
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def _state_product(spec, a, state, dtype):
    """``a`` against a float32 state: in bf16 the state enters as its
    high and low halves, two products, so that no product sees it
    rounded to bf16 (as ops/ssd.py's kernels do)."""
    if dtype == jnp.float32:
        return _f32_product(spec, a, state)
    hi = state.astype(dtype)
    lo = (state - hi.astype(jnp.float32)).astype(dtype)
    a = a.astype(dtype)
    return (
        jnp.einsum(spec, a, hi, preferred_element_type=jnp.float32)
        + jnp.einsum(spec, a, lo, preferred_element_type=jnp.float32)
    )


def recurrence(q, k, v, g, beta):
    """The rule a token at a time, float32: q, k, g [B, T, H, d_k],
    v [B, T, H, d_v], beta [B, T, H] -> o [B, T, H, d_v]."""
    b, t, h, dk = q.shape
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        decayed = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", decayed, k_t, precision=_HIGHEST)
        delta = beta_t[..., None] * (v_t - read)
        state = decayed + k_t[..., None] * delta[..., None, :]
        return state, jnp.einsum(
            "bhkv,bhk->bhv", state, q_t, precision=_HIGHEST
        )

    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, zero, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# The sequential part: the state every chunk starts from
# ---------------------------------------------------------------------------


def _scan_states(w, kdl, dlast, u0, dtype):
    def step(state, xs):
        w_n, kdl_n, d_n, u0_n = xs
        u = u0_n - _state_product("bhcd,bhdv->bhcv", w_n, state, dtype)
        nxt = d_n[..., None] * state + _f32_product(
            "bhcd,bhcv->bhdv", kdl_n, u
        )
        return nxt, state

    b, h = w.shape[1], w.shape[2]
    zero = jnp.zeros((b, h, w.shape[-1], u0.shape[-1]), jnp.float32)
    _, states = jax.lax.scan(step, zero, (w, kdl, dlast, u0))
    return states


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunk_states(w, kdl, dlast, u0, dtype):
    """The state each chunk starts from, [N, B, H, d_k, d_v] float32,
    for ``S1 = dlast * S0 + kdl^T (u0 - w S0)`` from a zero state.
    Operands chunk-major: w, kdl [N, B, H, C, d_k], dlast
    [N, B, H, d_k], u0 [N, B, H, C, d_v]."""
    from dlrover_tpu.accelerate.remat import KDA_STATES, keep

    # Named here too: the forward rule is traced only later, under
    # differentiation, and ``remat.kept`` reads the names while the
    # block is traced.
    return keep(_scan_states(w, kdl, dlast, u0, dtype), KDA_STATES)


def _chunk_states_fwd(w, kdl, dlast, u0, dtype):
    from dlrover_tpu.accelerate.remat import KDA_STATES, keep

    states = keep(_scan_states(w, kdl, dlast, u0, dtype), KDA_STATES)
    return states, (w, kdl, dlast, u0, states)


def _chunk_states_bwd(dtype, res, d_states):
    """The cotangents go back through the chunks in reverse, the same
    two products a step; what each chunk owes its operands is then
    batched over the chunks."""
    w, kdl, dlast, u0, states = res

    def step(lam_next, xs):
        w_n, kdl_n, d_n, ds_n = xs
        du = _state_product("bhcd,bhdv->bhcv", kdl_n, lam_next, dtype)
        lam = ds_n + d_n[..., None] * lam_next - _f32_product(
            "bhcd,bhcv->bhdv", w_n, du
        )
        return lam, lam_next

    _, lam_next = jax.lax.scan(
        step, jnp.zeros_like(states[0]), (w, kdl, dlast, d_states),
        reverse=True,
    )
    du = _f32_product("nbhcd,nbhdv->nbhcv", kdl, lam_next)
    u = u0 - _f32_product("nbhcd,nbhdv->nbhcv", w, states)
    d_w = -_f32_product("nbhcv,nbhdv->nbhcd", du, states)
    d_kdl = _f32_product("nbhcv,nbhdv->nbhcd", u, lam_next)
    d_dlast = jnp.sum(states * lam_next, axis=-1)
    return d_w, d_kdl, d_dlast, du


_chunk_states.defvjp(_chunk_states_fwd, _chunk_states_bwd)


# ---------------------------------------------------------------------------
# Inside a chunk
# ---------------------------------------------------------------------------


_SUBSTITUTED = 16  # rows of the diagonal blocks inverted row by row


def _substituted_inverse(blocks):
    """``(I + blocks)^-1`` for strictly lower triangular ``blocks``
    [n, r, r] by forward substitution, a column at a time: from the
    identity, step j takes ``blocks[i, j]`` times row j (final by
    then) off every row i below it. The blocks lie along the lanes
    ([r, r, n]) while they are formed, so that a step is one dense
    elementwise pass, r - 1 of them."""
    r = blocks.shape[-1]
    by_lane = jnp.transpose(blocks, (1, 2, 0))  # [row, col, n]
    inverse = jnp.broadcast_to(
        jnp.eye(r, dtype=jnp.float32)[:, :, None], by_lane.shape
    )
    for j in range(r - 1):
        # blocks[i, j] is zero for i <= j: the rows above stay.
        inverse = inverse - by_lane[:, j, None, :] * inverse[j][None]
    return jnp.transpose(inverse, (2, 0, 1))


def _merged_inverse(a, size):
    """``(I + a)^-1`` of [n, size, size] blocks: the halves' inverses
    ``P`` and ``R`` and, below the diagonal, ``-R a21 P`` (the inverse
    of a block lower triangle), down to blocks that are substituted."""
    if size <= _SUBSTITUTED:
        return _substituted_inverse(a)
    half = size // 2
    p = _merged_inverse(a[:, :half, :half], half)
    r = _merged_inverse(a[:, half:, half:], half)
    below = -_f32_product(
        "nij,njk->nik",
        _f32_product("nij,njk->nik", r, a[:, half:, :half]), p,
    )
    top = jnp.concatenate([p, jnp.zeros_like(below)], axis=2)
    return jnp.concatenate(
        [top, jnp.concatenate([below, r], axis=2)], axis=1
    )


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C],
    C a power of two times at most 16. NOT the product
    ``(I + n)(I + n^2)(I + n^4)...`` of the nilpotent ``n = -a``: its
    partial products grow as the binomials of C where keys repeat
    (a run of one token gives ``a`` near ``beta`` x ones; at beta 0.9
    the product's float32 error is 1e9 for an inverse whose entries
    are at most 1), and a step of training on the benchmark's stream
    reached it (PERF.md section 6, PR 53). Forward substitution inside
    blocks of 16 rows and the block inverse above them are stable: the
    halves' inverses are as well conditioned as the whole. The
    backward takes the inverse alone from the forward:
    ``d a = -inv^T g inv^T``."""
    size = a.shape[-1]
    return _merged_inverse(
        a.reshape((-1, size, size)), size
    ).reshape(a.shape)


def _unit_lower_inverse_fwd(a):
    inverse = _unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    left = _f32_product("...ji,...jk->...ik", inverse, g)
    return (-_f32_product("...ik,...lk->...il", left, inverse),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _pair_decays(q, k, cum, sub, dtype):
    """``sum_c x_t[c] k_j[c] exp(G_t[c] - G_j[c])`` for x = k and
    x = q, [..., C, C] float32 each, zero above the diagonal. Operands
    [..., C, d_k] float32, ``cum`` the running sum of the log decays."""
    lead, (size, dk) = q.shape[:-2], q.shape[-2:]
    blocks = size // sub
    in_blocks = lambda x: x.reshape(lead + (blocks, sub, dk))
    cum_b, q_b, k_b = in_blocks(cum), in_blocks(q), in_blocks(k)
    # Pairs in two sub-blocks, through the later one's first row.
    first = cum_b[..., :1, :]  # [..., blocks, 1, d_k]
    up = jnp.exp(cum_b - first)
    down = jnp.exp(jnp.minimum(first - cum[..., None, :, :], 0.0))
    k_down = k[..., None, :, :] * down  # [..., blocks, C, d_k]
    earlier = (
        jnp.arange(size)[None, :] < (jnp.arange(blocks) * sub)[:, None]
    )[:, None, :]  # [blocks, 1, C]: column j lies before block i

    def across(x_b):
        pairs = _product("...isc,...ijc->...isj", x_b * up, k_down, dtype)
        return jnp.where(earlier, pairs, 0.0).reshape(lead + (size, size))

    # Pairs inside one sub-block, the decay formed outright.
    row = jnp.arange(sub)
    lower = (row[:, None] >= row[None, :])[..., None]
    decay = jnp.exp(jnp.where(
        lower, cum_b[..., :, None, :] - cum_b[..., None, :, :], -jnp.inf
    ))  # [..., blocks, sub, sub, d_k]
    own = jnp.eye(blocks, dtype=jnp.float32)[:, None, :, None]

    def within(x_b):
        pairs = jnp.sum(
            x_b[..., :, None, :] * k_b[..., None, :, :] * decay, axis=-1
        )  # [..., blocks, sub, sub]
        return (pairs[..., :, :, None, :] * own).reshape(lead + (size, size))

    return across(k_b) + within(k_b), across(q_b) + within(q_b)


def _chunked(q, k, v, g, beta, chunk, sub):
    """[B, T, H, d] operands, T a multiple of ``chunk`` -> o
    [B, T, H, d_v] in q's dtype."""
    from dlrover_tpu.accelerate.remat import KDA_O, keep

    dtype = q.dtype
    b, t, h, dk = q.shape
    n = t // chunk

    def chunks(x):  # [B, T, H, d] -> [N, B, H, C, d] float32
        x = x.astype(jnp.float32).reshape(b, n, chunk, h, -1)
        return jnp.transpose(x, (1, 0, 3, 2, 4))

    with jax.named_scope("kda_chunks"):
        q, k, v, g = map(chunks, (q, k, v, g))
        beta = chunks(beta[..., None])  # [N, B, H, C, 1]
        cum = jnp.cumsum(g, axis=-2)
    with jax.named_scope("kda_pairs"):
        kk, qk = _pair_decays(q, k, cum, sub, dtype)
    with jax.named_scope("kda_solve"):
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        inverse = _unit_lower_inverse(jnp.where(strict, beta * kk, 0.0))
        decayed = jnp.exp(cum)
        w = _f32_product("...ts,...sd->...td", inverse, beta * k * decayed)
        u0 = _f32_product("...ts,...sv->...tv", inverse, beta * v)
    with jax.named_scope("kda_states"):
        last = cum[..., -1:, :]
        states = _chunk_states(
            w, k * jnp.exp(last - cum), jnp.exp(last[..., 0, :]), u0, dtype
        )
    with jax.named_scope("kda_out"):
        u = u0 - _state_product("...cd,...dv->...cv", w, states, dtype)
        o = _state_product(
            "...cd,...dv->...cv", q * decayed, states, dtype
        ) + _product("...ts,...sv->...tv", qk, u, dtype)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, h, -1)
    return keep(o.astype(dtype), KDA_O)


# ---------------------------------------------------------------------------
# The kernels: a chunk in VMEM from operands to output
# ---------------------------------------------------------------------------

# What the kernels may take of the chip's 128 MiB of VMEM: blocks of
# 8 heads x 64 tokens x 128 channels, double-buffered (4 MB in the
# backward with its outputs), 8 states in scratch and 8 more in each
# of two buffers of the states' block (1.5 MB), a head's temporaries.
_VMEM_LIMIT = 32 << 20
_HALF = SUB_BLOCK // 2  # a float32 tile's rows

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_NN = (((1,), (0,)), ((), ()))  # a @ b


def heads_per_step(heads: int) -> int:
    """Heads one grid step treats: 8 (beta's rows fill a float32
    tile), or all of them where 8 does not divide them."""
    return 8 if heads % 8 == 0 else heads


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot32(a, b, dims):
    """A float32 product in fact inside a kernel."""
    return jax.lax.dot_general(
        a, b, dims, precision=_HIGHEST, preferred_element_type=jnp.float32
    )


def _dot16(a, b, dims):
    """A product of bf16 operands summed in float32, one pass whatever
    ``jax.default_matmul_precision`` the trace is under (Mosaic
    refuses any other of bf16 operands)."""
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _dot(a, b, dims, mxu):
    """Both sides in the dtype the data came in, summed in float32
    (:func:`_product`)."""
    if mxu == jnp.float32:
        return _dot32(a, b, dims)
    return _dot16(a.astype(mxu), b.astype(mxu), dims)


def _dot_state(a, state, dims, mxu):
    """``a`` against a float32 state as its high and low halves
    (:func:`_state_product`)."""
    if mxu == jnp.float32:
        return _dot32(a, state, dims)
    hi = state.astype(mxu)
    lo = (state - hi.astype(jnp.float32)).astype(mxu)
    a = a.astype(mxu)
    return _dot16(a, hi, dims) + _dot16(a, lo, dims)


def _dot_ones(ones, x, dims):
    """A product with a matrix of zeros and ones, exact: ``x`` enters
    as three bf16 pieces that add up to it, a pass each."""
    total = None
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        x = x - piece.astype(jnp.float32)
        got = _dot16(ones, piece, dims)
        total = got if total is None else total + got
    return total


def _running_sum(g, reverse=False):
    """The inclusive running sum of ``g`` [C, d] down the rows (up
    them with ``reverse``): a product with a triangle of ones."""
    size = g.shape[0]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    triangle = (row <= col) if reverse else (row >= col)
    return _dot_ones(triangle.astype(jnp.bfloat16), g, _NN)


def _row_sums(x):
    """The sums along the rows of ``x`` [C, n] as a lane-dense (1, C)
    row: a product with ones, not C reductions into a column."""
    return _dot_ones(jnp.ones((8, x.shape[1]), jnp.bfloat16), x, _NT)[:1]


def _column(row):
    """A (1, C) row as a (C, 1) column: the diagonal of its broadcast,
    summed along the lanes."""
    size = row.shape[1]
    diagonal = _iota((size, size), 0) == _iota((size, size), 1)
    return jnp.sum(
        jnp.where(diagonal, jnp.broadcast_to(row, (size, size)), 0.0),
        axis=1, keepdims=True,
    )


def _blocks(size):
    return range(size // SUB_BLOCK)


def _sub_rows(x, j, rows=SUB_BLOCK):
    """Row ``j`` of every sub-block of ``x`` [C, d], each over
    ``rows`` rows: [C / 16 * rows, d]."""
    return jnp.concatenate([
        jnp.broadcast_to(
            x[s * SUB_BLOCK + j: s * SUB_BLOCK + j + 1], (rows, x.shape[1])
        )
        for s in _blocks(x.shape[0])
    ], axis=0)


def _lower_halves(x):
    """Rows 8 to 15 of every sub-block of ``x`` [C, d]: [C / 2, d]."""
    return jnp.concatenate([
        x[s * SUB_BLOCK + _HALF:(s + 1) * SUB_BLOCK]
        for s in _blocks(x.shape[0])
    ], axis=0)


def _from_lower_halves(x):
    """:func:`_lower_halves` undone, zeros in the upper halves."""
    zero = jnp.zeros((_HALF, x.shape[1]), x.dtype)
    return jnp.concatenate([
        piece for s in range(x.shape[0] // _HALF)
        for piece in (zero, x[s * _HALF:(s + 1) * _HALF])
    ], axis=0)


def _diagonal_blocks(a):
    """The four diagonal 16 x 16 blocks of ``a`` [C, C] stacked:
    [C, 16]."""
    return jnp.concatenate([
        a[s * SUB_BLOCK:(s + 1) * SUB_BLOCK, s * SUB_BLOCK:(s + 1) * SUB_BLOCK]
        for s in _blocks(a.shape[0])
    ], axis=0)


def _column_in(size, rows=SUB_BLOCK):
    """For [C / 16 * rows, C]: a column's place in the diagonal block
    of its row (a row's sub-block is its index over ``rows``)."""
    shape = (size // SUB_BLOCK * rows, size)
    return _iota(shape, 1) - _iota(shape, 0) // rows * SUB_BLOCK


def _within_phases():
    """The 16 steps over the columns of the diagonal blocks in two
    phases, as (rows a sub-block, of the rows, the columns): columns 0
    to 7 meet all 16 rows of a sub-block, columns 8 to 15 only its
    lower half, which is a tile of its own."""
    return (
        (SUB_BLOCK, lambda x: x, range(_HALF)),
        (_HALF, _lower_halves, range(_HALF, SUB_BLOCK)),
    )


def _decay_within(cum, cum_rows, j, rows):
    """``exp(G_t - G_j)`` for the rows ``cum_rows`` against row ``j``
    of their own sub-block. A row before ``j`` reads 1 (its difference
    is positive, log decays being at most 0) and is masked where it
    lands."""
    return jnp.exp(jnp.minimum(cum_rows - _sub_rows(cum, j, rows), 0.0))


def _within(q, k, cum, mxu):
    """The pairs inside one sub-block, their decays formed outright:
    column ``j`` of all four diagonal blocks a step, as one product of
    the decayed rows with row ``j`` of their own sub-block. Returns
    the k-k and q-k pairs [C, C]; only the diagonal blocks' lower
    triangles mean anything."""
    size = cum.shape[0]
    found = []
    for rows, of, columns in _within_phases():
        k_rows, q_rows, cum_rows = of(k), of(q), of(cum)
        column_in = _column_in(size, rows)
        kk = qk = jnp.zeros(column_in.shape, jnp.float32)
        for j in columns:
            decay = _decay_within(cum, cum_rows, j, rows)
            got = _dot(
                jnp.concatenate([k_rows * decay, q_rows * decay], axis=0),
                _sub_rows(k, j), _NT, mxu,
            )
            here = column_in == j
            kk = jnp.where(here, got[:kk.shape[0]], kk)
            qk = jnp.where(here, got[kk.shape[0]:], qk)
        found.append((kk, qk))
    (kk, qk), (kk_half, qk_half) = found
    return kk + _from_lower_halves(kk_half), qk + _from_lower_halves(qk_half)


def _across_factors(cum, i):
    """Sub-block ``i``'s factors through its first row: its rows, their
    decay down from it, and every row's decay up to it (1 from it on,
    where the mask of the earlier columns falls)."""
    rows = slice(i * SUB_BLOCK, (i + 1) * SUB_BLOCK)
    first = cum[i * SUB_BLOCK: i * SUB_BLOCK + 1]
    return rows, jnp.exp(cum[rows] - first), jnp.exp(
        jnp.minimum(first - cum, 0.0)
    )


def _across(q, k, cum, mxu):
    """The pairs in two sub-blocks, through the later one's first
    row: a product a sub-block. k-k and q-k pairs [C, C]."""
    size = cum.shape[0]
    column = _iota((2 * SUB_BLOCK, size), 1)
    kk = [jnp.zeros((SUB_BLOCK, size), jnp.float32)]
    qk = [kk[0]]
    for i in range(1, size // SUB_BLOCK):
        rows, up, down = _across_factors(cum, i)
        got = _dot(
            jnp.concatenate([k[rows] * up, q[rows] * up], axis=0),
            k * down, _NT, mxu,
        )
        got = jnp.where(column < i * SUB_BLOCK, got, 0.0)
        kk.append(got[:SUB_BLOCK])
        qk.append(got[SUB_BLOCK:])
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)


def _merged(upper, lower, between):
    """The inverse of a block lower triangle from its halves'
    inverses: ``[[P, 0], [-R a21 P, R]]``."""
    below = -_dot32(_dot32(lower, between, _NN), upper, _NN)
    return jnp.concatenate([
        jnp.concatenate([upper, jnp.zeros_like(below)], axis=1),
        jnp.concatenate([below, lower], axis=1),
    ], axis=0)


def _inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [C, C]:
    forward substitution in the diagonal blocks of 16 rows, the four
    of them stacked, then the block merges 16 -> 32 -> 64
    (:func:`_unit_lower_inverse`)."""
    size = a.shape[0]
    stacked = _diagonal_blocks(a)
    shape = stacked.shape
    x = (_iota(shape, 0) % SUB_BLOCK == _iota(shape, 1)).astype(jnp.float32)
    for j in range(SUB_BLOCK - 1):
        x = x - stacked[:, j:j + 1] * _sub_rows(x, j)
    inverses = [x[s * SUB_BLOCK:(s + 1) * SUB_BLOCK] for s in _blocks(size)]
    merged = SUB_BLOCK
    while merged < size:
        inverses = [
            _merged(
                inverses[i], inverses[i + 1],
                a[(i + 1) * merged:(i + 2) * merged,
                  i * merged:(i + 1) * merged],
            )
            for i in range(0, len(inverses), 2)
        ]
        merged *= 2
    return inverses[0]


def _chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, h):
    """Head ``h``'s chunk of the operands' blocks, and what both
    kernels form of it before the state comes in."""
    mxu = q_ref.dtype
    hb = beta_ref.shape[2]
    dk, dv = q_ref.shape[2] // hb, v_ref.shape[2] // hb
    keys = pl.ds(pl.multiple_of(h * dk, 128), dk)
    values = pl.ds(pl.multiple_of(h * dv, 128), dv)
    f32 = jnp.float32
    q, k = q_ref[0, :, keys].astype(f32), k_ref[0, :, keys].astype(f32)
    v = v_ref[0, :, values].astype(f32)
    cum = _running_sum(g_ref[0, :, keys])
    beta = _column(beta_ref[0, 0, pl.ds(h, 1), :])
    kk_in, qk_in = _within(q, k, cum, mxu)
    kk_out, qk_out = _across(q, k, cum, mxu)
    size = cum.shape[0]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    kk = jnp.where(row > col, kk_in + kk_out, 0.0)
    return dict(
        mxu=mxu, keys=keys, values=values, q=q, k=k, v=v, cum=cum,
        beta=beta, kk=kk, qk=jnp.where(row >= col, qk_in + qk_out, 0.0),
        inverse=_inverse(beta * kk), from_start=jnp.exp(cum),
        last=cum[size - 1:size],
    )


def _fwd_kernel(
    q_ref,     # (1, C, hb*dk)
    k_ref,     # (1, C, hb*dk)
    v_ref,     # (1, C, hb*dv)
    g_ref,     # (1, C, hb*dk) f32
    beta_ref,  # (1, 1, hb, C) f32, a head a row
    o_ref,     # (1, C, hb*dv)
    st_ref,    # (1, 1, hb, dv, dk) f32: the state each head enters with
    state_scr,  # (hb, dv, dk) f32: every head's running state, transposed
):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state_scr[...] = jnp.zeros_like(state_scr)

    def head(h, carry):
        c = _chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, h)
        mxu, size = c["mxu"], c["cum"].shape[0]
        state = state_scr[h]
        st_ref[0, 0, h] = state
        read = _dot_state(
            jnp.concatenate(
                [c["k"] * c["from_start"], c["q"] * c["from_start"]], axis=0
            ),
            state, _NT, mxu,
        )
        u = _dot32(c["inverse"], c["beta"] * (c["v"] - read[:size]), _NN)
        o = read[size:] + _dot(c["qk"], u, _NN, mxu)
        o_ref[0, :, c["values"]] = o.astype(o_ref.dtype)
        to_end = c["k"] * jnp.exp(c["last"] - c["cum"])
        state_scr[h] = jnp.exp(c["last"]) * state + _dot32(u, to_end, _TN)
        return carry

    jax.lax.fori_loop(0, beta_ref.shape[2], head, 0)


def _layout(q, k, v, g, beta):
    """The kernels' operands: q, k, v, g ``[B, T, H*d]`` as they
    come, beta a head a row of its chunk, ``[B, N, H, C]``."""
    b, t, h = beta.shape
    n = t // CHUNK
    rows = beta.astype(jnp.float32).reshape(b, n, CHUNK, h)
    return dict(b=b, t=t, h=h, dk=q.shape[-1] // h, dv=v.shape[-1] // h,
                n=n, hb=heads_per_step(h)), (
        q, k, v, g.astype(jnp.float32), jnp.transpose(rows, (0, 1, 3, 2)),
    )


def _specs(dims, chunk_of):
    """Block specs of the five operands, of a ``[B, T, H*dv]`` array
    and of the states; ``chunk_of(grid index)`` is the chunk a step
    treats."""
    hb, dk, dv = dims["hb"], dims["dk"], dims["dv"]
    wide = lambda d: pl.BlockSpec(
        (1, CHUNK, hb * d), lambda i, j, n: (i, chunk_of(n), j)
    )
    rows = pl.BlockSpec(
        (1, 1, hb, CHUNK), lambda i, j, n: (i, chunk_of(n), j, 0)
    )
    state = pl.BlockSpec(
        (1, 1, hb, dv, dk), lambda i, j, n: (i, chunk_of(n), j, 0, 0)
    )
    keys, values = wide(dk), wide(dv)
    return [keys, keys, values, keys, rows], keys, values, rows, state


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _forward(q, k, v, g, beta, interpret):
    """(o [B, T, H*dv], states [B, N, H, dv, dk] f32: the state every
    chunk starts from, transposed)."""
    dims, operands = _layout(q, k, v, g, beta)
    in_specs, _, values, _, state = _specs(dims, lambda n: n)
    b, t, h, dk, dv, n, hb = (
        dims[x] for x in ("b", "t", "h", "dk", "dv", "n", "hb")
    )
    o, states = pl.pallas_call(
        _fwd_kernel,
        grid=(b, h // hb, n),
        in_specs=in_specs,
        out_specs=[values, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * dv), q.dtype),
            jax.ShapeDtypeStruct((b, n, h, dv, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_fwd",
    )(*operands)
    return o, states


def _within_bwd(c, d_kk, d_qk, dkc_scr):
    """What the pairs inside a sub-block owe q and k, a column of the
    diagonal blocks a step as :func:`_within` forms them: (dq, dk for
    k as a pair's later row) [C, dk]; what k gets as a pair's earlier
    row goes to ``dkc_scr``, a row a sub-block a step."""
    q, k, cum = c["q"], c["k"], c["cum"]
    found = []
    for rows, of, columns in _within_phases():
        k_rows, q_rows, cum_rows = of(k), of(q), of(cum)
        of_q, of_k = of(_diagonal_blocks(d_qk)), of(_diagonal_blocks(d_kk))
        dq = dk = jnp.zeros_like(cum_rows)
        for j in columns:
            decay = _decay_within(cum, cum_rows, j, rows)
            to_q, to_k = of_q[:, j:j + 1], of_k[:, j:j + 1]
            earlier = _sub_rows(k, j, rows) * decay
            later = (to_q * q_rows + to_k * k_rows) * decay
            for s in _blocks(cum.shape[0]):
                dkc_scr[s * SUB_BLOCK + j: s * SUB_BLOCK + j + 1, :] = (
                    jnp.sum(
                        later[s * rows:(s + 1) * rows], axis=0, keepdims=True
                    )
                )
            dq, dk = dq + to_q * earlier, dk + to_k * earlier
        found.append((dq, dk))
    (dq, dk), (dq_half, dk_half) = found
    return dq + _from_lower_halves(dq_half), dk + _from_lower_halves(dk_half)


def _across_bwd(c, d_kk, d_qk):
    """What the pairs in two sub-blocks owe q and k: (dq, dk as the
    later row, dk as the earlier row) [C, dk]."""
    q, k, cum, mxu = c["q"], c["k"], c["cum"], c["mxu"]
    size = cum.shape[0]
    column = _iota((2 * SUB_BLOCK, size), 1)
    zero = jnp.zeros((SUB_BLOCK, cum.shape[1]), jnp.float32)
    dq, dk_later, dk_earlier = [zero], [zero], jnp.zeros_like(cum)
    for i in range(1, size // SUB_BLOCK):
        rows, up, down = _across_factors(cum, i)
        d_got = jnp.where(
            column < i * SUB_BLOCK,
            jnp.concatenate([d_kk[rows], d_qk[rows]], axis=0), 0.0,
        )
        d_rows = _dot(d_got, k * down, _NN, mxu)
        dk_later.append(up * d_rows[:SUB_BLOCK])
        dq.append(up * d_rows[SUB_BLOCK:])
        dk_earlier += down * _dot(
            d_got, jnp.concatenate([k[rows] * up, q[rows] * up], axis=0),
            _TN, mxu,
        )
    return (
        jnp.concatenate(dq, axis=0), jnp.concatenate(dk_later, axis=0),
        dk_earlier,
    )


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref,
    st_ref,     # (1, 1, hb, dv, dk) f32
    do_ref,     # (1, C, hb*dv)
    dq_ref,     # (1, C, hb*dk)
    dk_ref,     # (1, C, hb*dk)
    dv_ref,     # (1, C, hb*dv)
    dg_ref,     # (1, C, hb*dk) f32
    dbeta_ref,  # (1, 1, hb, C) f32
    dstate_scr,  # (hb, dv, dk) f32: the cotangent of each head's state
    dkc_scr,     # (C, dk) f32
):
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    def head(h, carry):
        c = _chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, h)
        mxu, size = c["mxu"], c["cum"].shape[0]
        q, k, cum, beta = c["q"], c["k"], c["cum"], c["beta"]
        state, d_next = st_ref[0, 0, h], dstate_scr[h]
        d_o = do_ref[0, :, c["values"]]
        # The forward again, from the kept state.
        from_start = c["from_start"]
        decayed = jnp.concatenate([k * from_start, q * from_start], axis=0)
        kept = c["v"] - _dot_state(decayed[:size], state, _NT, mxu)
        u = _dot32(c["inverse"], beta * kept, _NN)
        to_end = jnp.exp(c["last"] - cum)
        k_end = k * to_end
        whole = jnp.exp(c["last"])
        # Back through the output and the state the chunk leaves.
        row, col = _iota((size, size), 0), _iota((size, size), 1)
        d_qk = jnp.where(row >= col, _dot(d_o, u, _NT, mxu), 0.0)
        d_u = _dot(c["qk"], d_o, _TN, mxu) + _dot_state(
            k_end, d_next, _NT, mxu
        )
        d_k_end = _dot_state(u, d_next, _NN, mxu)
        d_whole = jnp.sum(d_next * state, axis=0, keepdims=True)
        # Back through the solve.
        d_rhs = _dot32(c["inverse"], d_u, _TN)
        d_a = -_dot(d_rhs, u, _NT, mxu)
        d_kept = beta * d_rhs
        dv_ref[0, :, c["values"]] = d_kept.astype(dv_ref.dtype)
        dbeta_ref[0, 0, pl.ds(h, 1), :] = _row_sums(d_rhs * kept) + _row_sums(
            d_a * c["kk"]
        )
        # Back through the two reads of the state.
        reads = jnp.concatenate([-d_kept, d_o.astype(jnp.float32)], axis=0)
        d_decayed = _dot_state(reads, state, _NN, mxu)
        dstate_scr[h] = whole * d_next + _dot(reads, decayed, _TN, mxu)
        # Back through the pair matrices.
        d_kk = jnp.where(row > col, beta * d_a, 0.0)
        dq_in, dk_in = _within_bwd(c, d_kk, d_qk, dkc_scr)
        dq_out, dk_out, dk_earlier = _across_bwd(c, d_kk, d_qk)
        dq_pairs, dk_later = dq_in + dq_out, dk_in + dk_out
        dk_earlier += dkc_scr[...]
        d_kd, d_qd = d_decayed[:size], d_decayed[size:]
        keys = c["keys"]
        dq_ref[0, :, keys] = (from_start * d_qd + dq_pairs).astype(
            dq_ref.dtype
        )
        dk_ref[0, :, keys] = (
            from_start * d_kd + to_end * d_k_end + dk_later + dk_earlier
        ).astype(dk_ref.dtype)
        # A decay exp(G_t - G_j) scales what row t reads and divides
        # what row j writes; the chunk's last row also scales all that
        # reaches the next chunk. g gets the sums from its row on.
        d_cum = (
            q * dq_pairs + k * (dk_later - dk_earlier)
            + decayed[size:] * d_qd + decayed[:size] * d_kd
            - k_end * d_k_end
        )
        at_last = jnp.sum(k_end * d_k_end, axis=0, keepdims=True) + (
            whole * d_whole
        )
        d_cum += jnp.where(_iota(cum.shape, 0) == size - 1, at_last, 0.0)
        dg_ref[0, :, keys] = _running_sum(d_cum, reverse=True)
        return carry

    jax.lax.fori_loop(0, beta_ref.shape[2], head, 0)


def _backward(q, k, v, g, beta, states, d_o, interpret):
    dims, operands = _layout(q, k, v, g, beta)
    last = dims["n"] - 1
    in_specs, keys, values, rows, state = _specs(dims, lambda n: last - n)
    b, t, h, dk, dv, n, hb = (
        dims[x] for x in ("b", "t", "h", "dk", "dv", "n", "hb")
    )
    dq, d_k, d_v, dg, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(b, h // hb, n),
        in_specs=in_specs + [state, values],
        out_specs=[keys, keys, values, keys, rows],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * dk), q.dtype),
            jax.ShapeDtypeStruct((b, t, h * dk), k.dtype),
            jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
            jax.ShapeDtypeStruct((b, t, h * dk), jnp.float32),
            jax.ShapeDtypeStruct((b, n, h, CHUNK), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, dv, dk), jnp.float32),
            pltpu.VMEM((CHUNK, dk), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_bwd",
    )(*operands, states, d_o)
    dbeta = jnp.transpose(dbeta, (0, 1, 3, 2)).reshape(b, t, h)
    return dq, d_k, d_v, dg.astype(g.dtype), dbeta.astype(beta.dtype)


def _kept(o, states):
    from dlrover_tpu.accelerate.remat import KDA_O, KDA_STATES, keep

    return keep(o, KDA_O), keep(states, KDA_STATES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernels(q, k, v, g, beta, interpret):
    # Named here too: the forward rule is traced only later, under
    # differentiation, and ``remat.kept`` reads the names while the
    # block is traced.
    return _kept(*_forward(q, k, v, g, beta, interpret))[0]


def _kernels_fwd(q, k, v, g, beta, interpret):
    # The primal output and the residuals are the kept values, so a
    # block under remat="full" hands them to the backward as they are
    # and does not run the forward kernel again.
    o, states = _kept(*_forward(q, k, v, g, beta, interpret))
    return o, (q, k, v, g, beta, states)


def _kernels_bwd(interpret, res, d_o):
    return _backward(*res, d_o, interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)

# One jitted call, so that a stack's KDA layers trace and lower the
# kernels once.
_rule = jax.jit(_kernels, static_argnums=(5,))


def _sequences(q, k, v, g, beta, chunk, sub_block, wide):
    """The rule on ``[B, T, H*d]`` operands, beta ``[B, T, H]`` ->
    o ``[B, T, H*d_v]``; ``wide`` says the caller holds them so."""
    b, t, h = beta.shape
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    chunk = min(chunk, -(-t // sub_block) * sub_block)
    sub = min(sub_block, chunk)
    pad = -t % chunk
    # The kernels are written for one tile: chunks of 64 rows in
    # sub-blocks of 16, head sizes whole lanes.
    kernel = (chunk, sub) == (CHUNK, SUB_BLOCK) and not (dk % 128 or dv % 128)
    from dlrover_tpu.accelerate.remat import KDA_O, KDA_STATES

    engaged = dict(
        heads_per_step=heads_per_step(h), kept=(KDA_O, KDA_STATES)
    ) if kernel else {}
    obs.event(
        "kda.scan", chunk=chunk, chunks=(t + pad) // chunk,
        heads=h, sub_block=sub, state_dtype="float32",
        states_kept=True, per_device=bool(batch_axes(b)[0]),
        kernel=kernel, wide=wide, **engaged,
    )
    interpret = use_interpret()

    def call(q, k, v, g, beta):
        if pad:
            rows = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            q, k, v, g, beta = map(rows, (q, k, v, g, beta))
        if kernel:
            return _rule(q, k, v, g, beta, interpret)[:, :t]
        heads = lambda x: x.reshape(x.shape[:2] + (h, -1))
        o = _chunked(heads(q), heads(k), heads(v), heads(g), beta, chunk, sub)
        return o.reshape(o.shape[:2] + (h * dv,))[:, :t]

    return per_device(call, q, k, v, g, beta, split=(True,) * 5)


def kda(q, k, v, g, beta, chunk: int = CHUNK, sub_block: int = SUB_BLOCK):
    """The rule over whole sequences from a zero state.

    q, k [B, T, H, d_k] (the caller has normalised them and scaled q),
    v [B, T, H, d_v], g [B, T, H, d_k] the log decays (float32, at
    most 0), beta [B, T, H] in (0, 1). Returns o [B, T, H, d_v] in
    q's dtype. A sequence that is not whole chunks is padded with tokens
    that neither decay nor write. Differentiable in every argument."""
    b, t, h, _ = q.shape
    wide = lambda x: x.reshape(b, t, -1)
    o = _sequences(
        wide(q), wide(k), wide(v), wide(g), beta, chunk, sub_block,
        wide=False,
    )
    return o.reshape(b, t, h, -1)


_KDA = kda


def kda_wide(q, k, v, g, beta, chunk: int = CHUNK,
             sub_block: int = SUB_BLOCK):
    """:func:`kda` on operands that stay as a convolution writes them
    and the kernels read them: q, k, g [B, T, H*d_k], v [B, T, H*d_v],
    a head's channels side by side, the head count beta's [B, T, H];
    o [B, T, H*d_v]. Nothing is reshaped at the kernels' edge: on the
    chip no 4-D layout is a bitcast of the 3-D tiling, and a view is a
    copy of the whole array (PERF.md section 6, PR 56).

    ``kda`` is where the benchmark's controls put a broken rule while
    a loss is traced (benchmark/controls/kimi_linear.py): whatever
    stands in its place is called, on 4-D views."""
    if kda is not _KDA:
        b, t, h = beta.shape
        heads = lambda x: x.reshape(b, t, h, -1)
        o = kda(heads(q), heads(k), heads(v), heads(g), beta, chunk, sub_block)
        return o.reshape(b, t, -1)
    return _sequences(q, k, v, g, beta, chunk, sub_block, wide=True)
