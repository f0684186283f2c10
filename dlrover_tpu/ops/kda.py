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

What is sequential is the state alone (``_chunk_states``): a
``lax.scan`` over the chunks of two products a step, with a backward
rule of its own, the same two products a step on the states'
cotangents in reverse; everything else is batched over the chunks and
differentiated by JAX. The states every chunk starts from are what
that rule takes from the forward, named for ``remat="full"``
(accelerate/remat.py ``KDA_STATES``) with the rule's output ``o``
(``KDA_O``).

Plain ``jax.numpy`` under XLA: no Pallas kernel is in this file (see
CHANGES.md, PR 53). The stages stand under scopes of their own
(``kda_chunks``, ``kda_pairs``, ``kda_solve``, ``kda_states``,
``kda_out``) for whoever reads a profile by ``op_name``; the
benchmark reads the caller's ``kda_scan`` around them. Under an ambient mesh the call runs once per
device on its batch rows (``ops.flash_attention.per_device``), as the
package's kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu import obs
from dlrover_tpu.ops.flash_attention import batch_axes, per_device

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


def kda(q, k, v, g, beta, chunk: int = CHUNK, sub_block: int = SUB_BLOCK):
    """The rule over whole sequences from a zero state.

    q, k [B, T, H, d_k] (the caller has normalised them and scaled q),
    v [B, T, H, d_v], g [B, T, H, d_k] the log decays (float32, at
    most 0), beta [B, T, H] in (0, 1). Returns o [B, T, H, d_v] in
    q's dtype. A sequence that is not whole chunks is padded with tokens
    that neither decay nor write. Differentiable in every argument."""
    t = q.shape[1]
    chunk = min(chunk, -(-t // sub_block) * sub_block)
    sub = min(sub_block, chunk)
    pad = -t % chunk
    obs.event(
        "kda.scan", chunk=chunk, chunks=(t + pad) // chunk,
        heads=q.shape[2], sub_block=sub, state_dtype="float32",
        states_kept=True, per_device=bool(batch_axes(q.shape[0])[0]),
    )

    def call(q, k, v, g, beta):
        if pad:
            rows = lambda x: jnp.pad(
                x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
            )
            q, k, v, g, beta = map(rows, (q, k, v, g, beta))
        return _chunked(q, k, v, g, beta, chunk, sub)[:, :t]

    return per_device(call, q, k, v, g, beta, split=(True,) * 5)
