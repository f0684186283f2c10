"""The selective scan of a Mamba-1 mixer (Gu & Dao 2023, "Mamba:
Linear-Time Sequence Modeling with Selective State Spaces"): a state
for every (channel, state) pair with a decay of its own,

    s_t = exp(dt_t[:, None] * A) * s_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :]
    y_t = s_t C_t + D * x_t                       (s is channels x states)

from a zero state, ``dt`` a channel and ``B``, ``C`` shared by the
channels. The decay differs by channel AND state, so the recurrence is
element-wise: it has no form as the products of ops/ssd.py (one scalar
decay a head), and all of it runs on the vector unit.

Plain ``jax.numpy`` behind one ``jax.custom_vjp``; no kernel yet
(ROADMAP.md, "What the program cannot run": the kernel is a later
PR's, and this file's cell is what it will be judged in). The
sequence is cut into chunks that run in turn (``lax.scan``), the
state carried in float32 as ``[states, channels]`` (the channels
along the lanes). Inside a chunk the decays ``exp(dt A)`` and the
driving terms ``dt x B`` of all its tokens are formed at once, the
recurrence itself is one token a step (a second ``lax.scan``: a
multiply and an add on the carried state, every token's state
written out), and ``y``, like every sum of the backward, is formed
from the chunk's states at once. It is the recurrence as written:
no product or logarithm of decays is formed, nothing is divided, a
decay that underflows to zero is the zero it stands for, and there is
no range of ``dt A`` outside which it is wrong
(tests/test_selective_scan.py holds it to the token-by-token
recurrence with ``dt * A`` under -100 inside a chunk). The chunk is
a schedule: what is live at once is a few ``[chunk, states,
channels]`` float32 arrays, and on a v5e a step of 64 tokens (21 MB an
array at 5,120 channels of 16 states) runs the scan in 10.3 ms
forward and backward where 256 takes 51.7 and a
``lax.associative_scan`` inside the chunk 19.5 at 32 tokens, 28.8 at
64 and 172 at 256 (PERF.md section 6, PR 64).

What the backward takes from the forward is the six operands and the
state every chunk starts from, ``[batch, chunks, states, channels]``
float32; it forms a chunk's states again from that, chunks in reverse,
with the state's cotangent carried the other way (the same
recurrence in reverse, over the decays shifted by one token). Under
``remat="full"`` the output and those states are kept by name
(accelerate/remat.py ``SELSCAN_Y``, ``SELSCAN_STATES``: 42 MB and 21 MB
a layer at 4,096 tokens of 5,120 channels in chunks of 64, not the
1.3 GB of every token's state), so a layer formed again in the
backward does not run the forward scan again. Event ``selscan.scan`` says once a traced call
what ran.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu import obs

KEPT = ("y", "chunk_states")


def _chunk(s0, x, dt, a_t, b):
    """One chunk from the state ``s0`` [N, Di] it starts from: the
    decays ``a`` and every token's state ``s``, both [L, N, Di]
    float32. x, dt [L, Di]; a_t [N, Di] (A transposed); b [L, N]."""
    a = jnp.exp(dt[:, None, :] * a_t[None])
    u = (dt * x)[:, None, :] * b[:, :, None]

    def token(s, decay_and_drive):
        s = decay_and_drive[0] * s + decay_and_drive[1]
        return s, s

    _, s = jax.lax.scan(token, s0, (a, u))
    return a, s


def _cotangents(a, direct):
    """The cotangent of every token's state, [L, N, Di]: ``G_t =
    direct_t + a_{t+1} G_{t+1}``, from nothing beyond the chunk's end
    (``direct`` holds what the next chunk hands back in its last
    row)."""
    a_next = jnp.concatenate([a[1:], jnp.ones_like(a[:1])])

    def token(g_next, decay_and_direct):
        g = decay_and_direct[1] + decay_and_direct[0] * g_next
        return g, g

    _, g = jax.lax.scan(
        token, jnp.zeros_like(a[0]), (a_next, direct), reverse=True
    )
    return g


def _f32(*arrays):
    return tuple(v.astype(jnp.float32) for v in arrays)


def _by_chunk(v, chunk):
    return v.reshape((v.shape[0] // chunk, chunk) + v.shape[1:])


def _forward_one(x, dt, a_t, b, c, d, chunk):
    """One sequence: y [T, Di] float32 and the chunks' first states
    [chunks, N, Di]."""

    def step(s0, inputs):
        x_c, dt_c, b_c, c_c = inputs
        _, s = _chunk(s0, x_c, dt_c, a_t, b_c)
        y = jnp.einsum("lnd,ln->ld", s, c_c) + d * x_c
        return s[-1], (y, s0)

    s0 = jnp.zeros(a_t.shape, jnp.float32)
    _, (y, starts) = jax.lax.scan(
        step, s0, tuple(_by_chunk(v, chunk) for v in (x, dt, b, c))
    )
    return y.reshape(x.shape), starts


def _backward_one(x, dt, a_t, b, c, d, starts, dy, chunk):
    """One sequence's cotangents of (x, dt, a_t, b, c, d), float32."""

    def step(carry, inputs):
        ds_next, da_t, dd = carry
        x_c, dt_c, b_c, c_c, s0, dy_c = inputs
        a, s = _chunk(s0, x_c, dt_c, a_t, b_c)
        # The cotangent of every token's state: what y_t takes of it,
        # and what the next token's state does, decayed; the chunk's
        # last state also feeds the next chunk.
        direct = dy_c[:, None, :] * c_c[:, :, None]
        direct = direct.at[-1].add(ds_next)
        g = _cotangents(a, direct)
        s_prev = jnp.concatenate([s0[None], s[:-1]])
        dlog = g * s_prev * a  # of dt_t[:, None] * A
        du = jnp.einsum("lnd,ln->ld", g, b_c)  # of dt_t * x_t
        ddt = jnp.sum(dlog * a_t[None], axis=1) + du * x_c
        dx = du * dt_c + d * dy_c
        db = jnp.einsum("lnd,ld->ln", g, dt_c * x_c)
        dc = jnp.einsum("lnd,ld->ln", s, dy_c)
        carry = (
            a[0] * g[0],
            da_t + jnp.einsum("lnd,ld->nd", dlog, dt_c),
            dd + jnp.sum(dy_c * x_c, axis=0),
        )
        return carry, (dx, ddt, db, dc)

    zeros = jnp.zeros(a_t.shape, jnp.float32)
    (_, da_t, dd), (dx, ddt, db, dc) = jax.lax.scan(
        step, (zeros, zeros, jnp.zeros(d.shape, jnp.float32)),
        tuple(_by_chunk(v, chunk) for v in (x, dt, b, c)) + (
            starts, _by_chunk(dy, chunk),
        ),
        reverse=True,
    )
    flat = lambda v: v.reshape((-1,) + v.shape[2:])
    return flat(dx), flat(ddt), da_t, flat(db), flat(dc), dd


_OVER_BATCH = (0, 0, None, 0, 0, None)


def _forward(x, dt, a, b, c, d, chunk):
    x32, dt32, a32, b32, c32, d32 = _f32(x, dt, a, b, c, d)
    y, starts = jax.vmap(
        functools.partial(_forward_one, chunk=chunk), in_axes=_OVER_BATCH
    )(x32, dt32, a32.T, b32, c32, d32)
    return y.astype(x.dtype), starts


def _kept(y, starts):
    from dlrover_tpu.accelerate.remat import (
        SELSCAN_STATES, SELSCAN_Y, keep,
    )

    return keep(y, SELSCAN_Y), keep(starts, SELSCAN_STATES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    # Named here too: the forward rule is traced only later, under
    # differentiation, and ``remat.kept`` reads the names while the
    # block is traced.
    return _kept(*_forward(x, dt, a, b, c, d, chunk))[0]


def _scan_fwd(x, dt, a, b, c, d, chunk):
    # The primal output and the residuals are the kept values, so a
    # block under remat="full" hands them to the backward as they are
    # and does not run the forward scan again.
    y, starts = _kept(*_forward(x, dt, a, b, c, d, chunk))
    return y, (x, dt, a, b, c, d, starts)


def _scan_bwd(chunk, res, dy):
    x, dt, a, b, c, d, starts = res
    x32, dt32, a32, b32, c32, d32, dy32 = _f32(x, dt, a, b, c, d, dy)
    dx, ddt, da_t, db, dc, dd = jax.vmap(
        functools.partial(_backward_one, chunk=chunk),
        in_axes=_OVER_BATCH + (0, 0),
    )(x32, dt32, a32.T, b32, c32, d32, starts, dy32)
    return (
        dx.astype(x.dtype), ddt.astype(dt.dtype),
        jnp.sum(da_t, axis=0).T.astype(a.dtype),
        db.astype(b.dtype), dc.astype(c.dtype),
        jnp.sum(dd, axis=0).astype(d.dtype),
    )


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(xs, dt, A, B, C, D, chunk: int = 64):
    """The selective scan over whole sequences from a zero state.

    xs: [batch, T, channels]; dt: [batch, T, channels] (positive,
    after the softplus); A: [channels, states] (negative); B, C:
    [batch, T, states] (the channels share them); D: [channels].
    Returns y like xs, the ``D`` skip in it. ``T`` must be a multiple
    of ``chunk``. Differentiable in every array argument; the state,
    the decays and every sum are float32 whatever the operands'."""
    t = xs.shape[1]
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole chunks of {chunk}")
    obs.event(
        "selscan.scan", channels=A.shape[0], states=A.shape[1],
        chunk=chunk, chunks=t // chunk, kept=list(KEPT),
    )
    return _scan(xs, dt, A, B, C, D, chunk)
