"""Chunked state-space-duality scan (Mamba-2's SSD) for TPU in Pallas,
forward and backward.

The recurrence, for one head with scalar decay ``A < 0`` and skip
``D``, state ``S`` of shape ``[P, N]`` (head size x state size):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

(Dao & Gu 2024, "Transformers are SSMs"). Written one token at a time
it is a scan of T steps of rank-one updates; in chunks of ``L`` tokens
it is matrix products. With ``cs_t`` the running sum of ``dt A`` inside
a chunk (inclusive), for the tokens of one chunk:

    y   = ((C B^T) * decay) (dt x)  +  exp(cs) * (C S0^T)  +  D x
    S1  = exp(cs_L) S0  +  ((dt x) * exp(cs_L - cs))^T B
    decay[t, s] = exp(cs_t - cs_s) for s <= t, else 0

where ``S0`` is the state the previous chunk left and ``S1`` the one
this chunk leaves. ``C B^T`` is shared by the heads of a B/C group.

Kernels ``ssd_fwd`` and ``ssd_bwd``: grid (batch, chunk, block of
heads), the chunk axis sequential with every head's running state
(forward) or its cotangent (backward, chunks in reverse) in VMEM
scratch, the head-block axis innermost so that ``C B^T`` is formed
once a group and ``dB`` / ``dC`` accumulate in their output blocks.
Decays, states and accumulation are float32; ``x``, ``B``, ``C``,
``y`` stay in the dtype they come in (bf16 on the training path) and
feed the MXU in it. A float32 state enters a product as two bf16
halves (high and low), so no product sees it rounded to bf16.

The running sums ``cs`` are formed outside the kernels by XLA (a
``[B, T, H]`` float32 array, 1 MB a layer at the published widths),
and so is what the backward owes them: the kernel returns ``d cs``
and XLA sums it back to ``d dt`` and ``d A``.

The forward writes every chunk's incoming state ``S0`` beside ``y``:
that and ``y`` are what the backward takes from the forward, named
for ``remat="full"`` (accelerate/remat.py ``SSD_Y``, ``SSD_STATES``)
so that a rematerialised block does not run ``ssd_fwd`` again.

Under an ambient mesh the whole ``custom_vjp`` runs once per device
on that device's batch rows (``parallel.mesh.per_device``): a
Mosaic kernel cannot be partitioned by XLA. Off the TPU the kernels
are interpreted.
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

_NEG = -1e30
# What the kernels may take of the chip's 128 MiB of VMEM. The blocks
# of 8 heads x 256 tokens (double-buffered), every head's state
# (2 MiB at 64 heads of 64 x 128) and the [L, L] float32 temporaries
# of the unrolled loop over heads pass Mosaic's 16 MiB default in the
# backward at the published widths (reckoned from the shapes, not
# read off the compiler).
_VMEM_LIMIT = 48 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_NN = (((1,), (0,)), ((), ()))  # a @ b


def heads_per_step(heads_per_group: int) -> int:
    """Heads one grid step treats: the largest divisor of a group's
    heads up to 8 (8 float32 sublanes; 8 heads of 64 are 512 lanes)."""
    return max(n for n in (8, 4, 2, 1) if heads_per_group % n == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot_state(a, state, dims):
    """``a`` (in the MXU's dtype) against a float32 state: in bf16 the
    state enters as its high and low halves, two products."""
    if a.dtype == jnp.float32:
        return _dot(a, state, dims)
    hi = state.astype(a.dtype)
    lo = (state - hi.astype(jnp.float32)).astype(a.dtype)
    return _dot(a, hi, dims) + _dot(a, lo, dims)


def _last_row(column):
    """The last element of an (L, 1) column as a scalar: Mosaic
    broadcasts a scalar anywhere, a (1, 1) vector along lanes or
    along sublanes but not both."""
    rows = jax.lax.broadcasted_iota(jnp.int32, column.shape, 0)
    return jnp.sum(jnp.where(rows == column.shape[0] - 1, column, 0.0))


def _causal(length):
    row = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    return row >= col


def _fwd_kernel(
    x_ref,    # (1, L, hb*P)
    dt_ref,   # (1, 1, L, hb)  dt, a head a column
    csc_ref,  # (1, 1, L, hb)  cs, a head a column
    csr_ref,  # (1, hb, L)     cs, a head a row
    b_ref,    # (1, L, N)
    c_ref,    # (1, L, N)
    d_ref,    # (1, hb*P)      D, repeated over a head's lanes
    y_ref,    # (1, L, hb*P)
    st_ref,   # (1, 1, hb, P, N)  the state each head enters the chunk with
    state_scr,  # (H, P, N) f32: every head's running state
    g_scr,      # (L, L) f32: C B^T of the current group
    *, hb: int, p: int, steps_per_group: int,
):
    chunk = pl.program_id(1)
    j = pl.program_id(2)
    length = x_ref.shape[1]
    mxu = x_ref.dtype

    @pl.when(chunk == 0)
    def _first_chunk():
        state_scr[pl.ds(j * hb, hb)] = jnp.zeros(
            (hb,) + state_scr.shape[1:], jnp.float32
        )

    @pl.when(j % steps_per_group == 0)
    def _new_group():
        g_scr[:] = _dot(c_ref[0], b_ref[0], _NT)

    g = g_scr[:]
    causal = _causal(length)
    bm, cm = b_ref[0], c_ref[0]
    for h in range(hb):
        lanes = slice(h * p, (h + 1) * p)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        dt = dt_ref[0, 0, :, h:h + 1]
        csc = csc_ref[0, 0, :, h:h + 1]
        csr = csr_ref[0, h:h + 1, :]
        last = csc_ref[0, 0, length - 1:length, h:h + 1]
        decay = jnp.exp(jnp.where(causal, csc - csr, _NEG))
        xdt = x * dt
        s0 = state_scr[j * hb + h]
        st_ref[0, 0, h] = s0
        y = _dot((g * decay).astype(mxu), xdt.astype(mxu), _NN)
        from_start = jnp.exp(csc)
        y += from_start * _dot_state(cm, s0, _NT)
        y += x * d_ref[:, lanes]
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        into_state = (xdt * jnp.exp(last - csc)).astype(mxu)
        state_scr[j * hb + h] = _last_row(from_start) * s0 + _dot(
            into_state, bm, _TN
        )


def _bwd_kernel(
    x_ref, dt_ref, csc_ref, csr_ref, b_ref, c_ref, d_ref,
    y_ref,    # (1, L, hb*P)  the forward's output
    dy_ref,   # (1, L, hb*P)
    st_ref,   # (1, 1, hb, P, N)
    dx_ref,   # (1, L, hb*P)
    ddt_ref,  # (1, 1, L, hb)  what dt gets through dt x
    dcs_ref,  # (1, 1, L, hb)  what cs gets
    db_ref,   # (1, L, N) f32, accumulated over a group's heads
    dc_ref,   # (1, L, N) f32
    dstate_scr,  # (H, P, N) f32: the cotangent of each head's state
    g_scr,       # (L, L) f32
    dg_scr,      # (L, L) f32: d(C B^T), summed over a group's heads
    *, hb: int, p: int, steps_per_group: int,
):
    chunk = pl.program_id(1)  # counts from the last chunk backwards
    j = pl.program_id(2)
    length = x_ref.shape[1]
    mxu = x_ref.dtype

    @pl.when(chunk == 0)
    def _last_chunk():
        dstate_scr[pl.ds(j * hb, hb)] = jnp.zeros(
            (hb,) + dstate_scr.shape[1:], jnp.float32
        )

    @pl.when(j % steps_per_group == 0)
    def _new_group():
        g_scr[:] = _dot(c_ref[0], b_ref[0], _NT)
        dg_scr[:] = jnp.zeros_like(dg_scr)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    g = g_scr[:]
    causal = _causal(length)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (length, 1), 0) == length - 1
    bm, cm = b_ref[0], c_ref[0]
    for h in range(hb):
        lanes = slice(h * p, (h + 1) * p)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        dy = dy_ref[0, :, lanes]
        dy32 = dy.astype(jnp.float32)
        d = d_ref[:, lanes]
        dt = dt_ref[0, 0, :, h:h + 1]
        csc = csc_ref[0, 0, :, h:h + 1]
        csr = csr_ref[0, h:h + 1, :]
        last = csc_ref[0, 0, length - 1:length, h:h + 1]
        decay = jnp.exp(jnp.where(causal, csc - csr, _NEG))
        to_end = jnp.exp(last - csc)
        from_start = jnp.exp(csc)
        xdt = x * dt
        s0 = st_ref[0, 0, h]
        ds1 = dstate_scr[j * hb + h]

        # d(dt x): through the chunk's own products and through the
        # state the chunk leaves.
        through_state = to_end * _dot_state(bm, ds1, _NT)
        dxdt = _dot((g * decay).astype(mxu), dy, _TN) + through_state
        x_dxdt = jnp.sum(x * dxdt, axis=1, keepdims=True)
        dx_ref[0, :, lanes] = (dt * dxdt + d * dy32).astype(dx_ref.dtype)
        ddt_ref[0, 0, :, h:h + 1] = x_dxdt

        # d(C B^T) of this head; decay is 0 above the diagonal.
        dg_scr[:] += _dot(dy, xdt.astype(mxu), _NT) * decay

        # d cs: a row's decay scales what it reads (y without the skip)
        # and is divided out of what it writes; the chunk's last cs
        # also scales everything that reaches the next chunk.
        y_scan = y_ref[0, :, lanes].astype(jnp.float32) - d * x
        dcs = jnp.sum(dy32 * y_scan, axis=1, keepdims=True) - dt * x_dxdt
        whole_chunk = _last_row(from_start)
        reaches_next = (
            jnp.sum(xdt * through_state) + whole_chunk * jnp.sum(ds1 * s0)
        )
        dcs_ref[0, 0, :, h:h + 1] = dcs + jnp.where(is_last, reaches_next, 0.0)

        dc_ref[0] += from_start * _dot_state(dy, s0, _NN)
        db_ref[0] += _dot_state((xdt * to_end).astype(mxu), ds1, _NN)
        dstate_scr[j * hb + h] = whole_chunk * ds1 + _dot(
            (dy32 * from_start).astype(mxu), cm, _TN
        )

    @pl.when(j % steps_per_group == steps_per_group - 1)
    def _group_done():
        dg = dg_scr[:].astype(mxu)
        dc_ref[0] += _dot(dg, bm, _NN)
        db_ref[0] += _dot(dg, cm, _TN)


def _prepare(x, dt, a, b, c, d, chunk):
    """The kernels' operands from the scan's: shapes, the running sums
    of ``dt A`` inside each chunk, and the per-head scalars laid out
    as the kernels read them."""
    bsz, t, heads = dt.shape
    p = x.shape[-1] // heads
    groups, n = b.shape[2], b.shape[3]
    if t % chunk:
        raise ValueError(f"sequence {t} is not a multiple of chunk {chunk}")
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    hb = heads_per_step(heads // groups)
    nhb = heads // hb
    dt = dt.astype(jnp.float32)
    cs = jnp.cumsum(
        (dt * a.astype(jnp.float32)).reshape(bsz, t // chunk, chunk, heads),
        axis=2,
    ).reshape(bsz, t, heads)

    def columns(v):  # [B, T, H] -> [B, H/hb, T, hb]
        return v.reshape(bsz, t, nhb, hb).transpose(0, 2, 1, 3)

    dims = dict(bsz=bsz, t=t, heads=heads, p=p, groups=groups, n=n,
                hb=hb, nhb=nhb, chunk=chunk, chunks=t // chunk)
    operands = (
        x, columns(dt), columns(cs), cs.transpose(0, 2, 1),
        b.reshape(bsz, t, groups * n), c.reshape(bsz, t, groups * n),
        jnp.repeat(d.astype(jnp.float32), p)[None],
    )
    return dims, operands


def _specs(dims, chunk_of):
    """Block specs of the seven operands both kernels read;
    ``chunk_of(grid chunk index)`` is the chunk a step treats."""
    hb, p, n, length = dims["hb"], dims["p"], dims["n"], dims["chunk"]
    per_group = dims["heads"] // dims["groups"] // hb
    wide = pl.BlockSpec(
        (1, length, hb * p), lambda i, k, j: (i, chunk_of(k), j)
    )
    column = pl.BlockSpec(
        (1, 1, length, hb), lambda i, k, j: (i, j, chunk_of(k), 0)
    )
    group = pl.BlockSpec(
        (1, length, n), lambda i, k, j: (i, chunk_of(k), j // per_group)
    )
    state = pl.BlockSpec(
        (1, 1, hb, p, n), lambda i, k, j: (i, chunk_of(k), j, 0, 0)
    )
    in_specs = [
        wide, column, column,
        pl.BlockSpec((1, hb, length), lambda i, k, j: (i, j, chunk_of(k))),
        group, group,
        pl.BlockSpec((1, hb * p), lambda i, k, j: (0, j)),
    ]
    return in_specs, wide, column, group, state, per_group


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _forward(x, dt, a, b, c, d, chunk, interpret):
    """(y [B, T, H*P], states [B, chunks, H, P, N] f32)."""
    dims, operands = _prepare(x, dt, a, b, c, d, chunk)
    in_specs, wide, _, _, state, per_group = _specs(dims, lambda k: k)
    bsz, t, heads, p, n = (dims[k] for k in ("bsz", "t", "heads", "p", "n"))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, hb=dims["hb"], p=p, steps_per_group=per_group
        ),
        grid=(bsz, dims["chunks"], dims["nhb"]),
        in_specs=in_specs,
        out_specs=[wide, state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, heads * p), x.dtype),
            jax.ShapeDtypeStruct(
                (bsz, dims["chunks"], heads, p, n), jnp.float32
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, p, n), jnp.float32),
            pltpu.VMEM((chunk, chunk), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_fwd",
    )(*operands)


def _backward(x, dt, a, b, c, d, y, states, dy, chunk, interpret):
    dims, operands = _prepare(x, dt, a, b, c, d, chunk)
    last = dims["chunks"] - 1
    in_specs, wide, column, group, state, per_group = _specs(
        dims, lambda k: last - k
    )
    bsz, t, heads, p, n = (dims[k] for k in ("bsz", "t", "heads", "p", "n"))
    groups, hb, nhb = dims["groups"], dims["hb"], dims["nhb"]
    by_column = jax.ShapeDtypeStruct((bsz, nhb, t, hb), jnp.float32)
    by_group = jax.ShapeDtypeStruct((bsz, t, groups * n), jnp.float32)
    dx, ddt, dcs, db, dc = pl.pallas_call(
        functools.partial(
            _bwd_kernel, hb=hb, p=p, steps_per_group=per_group
        ),
        grid=(bsz, dims["chunks"], nhb),
        in_specs=in_specs + [wide, wide, state],
        out_specs=[wide, column, column, group, group],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            by_column, by_column, by_group, by_group,
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, p, n), jnp.float32),
            pltpu.VMEM((chunk, chunk), jnp.float32),
            pltpu.VMEM((chunk, chunk), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(*operands, y, dy, states)

    def rows(v):  # [B, H/hb, T, hb] -> [B, T, H]
        return v.transpose(0, 2, 1, 3).reshape(bsz, t, heads)

    # cs is the running sum of dt A inside a chunk: what dt A gets is
    # the sum of d cs from its token to the chunk's end.
    dcs = rows(dcs).reshape(bsz, dims["chunks"], chunk, heads)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcs, 2), axis=2), 2).reshape(
        bsz, t, heads
    )
    dt32, a32 = dt.astype(jnp.float32), a.astype(jnp.float32)
    ddt = rows(ddt) + da * a32
    d_a = jnp.sum(da * dt32, axis=(0, 1))
    d_d = jnp.sum(
        (dy.astype(jnp.float32) * x.astype(jnp.float32)).reshape(
            bsz, t, heads, p
        ),
        axis=(0, 1, 3),
    )
    return (
        dx, ddt.astype(dt.dtype), d_a.astype(a.dtype),
        db.reshape(b.shape).astype(b.dtype),
        dc.reshape(c.shape).astype(c.dtype), d_d.astype(d.dtype),
    )


def _kept(y, states):
    from dlrover_tpu.accelerate.remat import SSD_STATES, SSD_Y, keep

    return keep(y, SSD_Y), keep(states, SSD_STATES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a, b, c, d, chunk, interpret):
    # Named here too: the forward rule is traced only later, under
    # differentiation, and ``remat.kept`` reads the names while the
    # block is traced.
    return _kept(*_forward(x, dt, a, b, c, d, chunk, interpret))[0]


def _ssd_fwd(x, dt, a, b, c, d, chunk, interpret):
    # The primal output and the residuals are the kept values, so a
    # block under remat="full" hands them to the backward as they are
    # and does not run the forward kernel again.
    y, states = _kept(*_forward(x, dt, a, b, c, d, chunk, interpret))
    return y, (x, dt, a, b, c, d, y, states)


def _ssd_bwd(chunk, interpret, res, dy):
    return _backward(*res, dy, chunk, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D, chunk: int = 256, interpret=None):
    """The SSD scan over whole sequences from a zero state.

    x: [batch, T, heads * head_size]; dt: [batch, T, heads] (positive,
    after the softplus); A: [heads] (negative); B, C: [batch, T,
    groups, state] (a group's heads share them); D: [heads]. Returns
    y like x. ``T`` must be a multiple of ``chunk``. Differentiable in
    every array argument."""
    if interpret is None:
        interpret = use_interpret()
    obs.event(
        "ssd.scan", chunk=chunk, chunks=x.shape[1] // chunk,
        heads=dt.shape[-1], state=B.shape[-1],
        per_device=bool(batch_axes(x.shape[0])[0]),
    )
    return per_device(
        lambda x, dt, b, c, a, d: _ssd(x, dt, a, b, c, d, chunk, interpret),
        x, dt, B, C, A, D,
        split=(True, True, True, True, False, False),
    )
