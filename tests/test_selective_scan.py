"""ops/selective_scan.py against the recurrence written one token at a
time: forward and every gradient over four chunks (three boundaries),
with steps and decays as a trained mixer has them, with ``dt * A`` so
large that a decay underflows to zero inside a chunk, and with the
same token repeated; what the backward keeps; what bf16 operands
leave; a second optimizer step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.selective_scan import selective_scan

ARGS = ("xs", "dt", "A", "B", "C", "D")
DI, N, CHUNK, T, BSZ = 24, 4, 8, 32, 2


def recurrence(xs, dt, a, b, c, d):
    """s_t = exp(dt_t[:, None] A) s_{t-1} + (dt_t x_t)[:, None] B_t[None];
    y_t = s_t C_t + D x_t: a ``lax.scan`` step a token, from s = 0."""

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, Di], [B, Di], [B, N], [B, N]
        state = (
            jnp.exp(dt_t[..., None] * a) * state
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.einsum("bdn,bn->bd", state, c_t) + d * x_t

    state = jnp.zeros((xs.shape[0],) + a.shape, jnp.float32)
    by_token = tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, c))
    _, y = jax.lax.scan(token, state, by_token)
    return jnp.moveaxis(y, 0, 1)


def operands(kind, seed=0):
    """Seeded operands and a seeded cotangent. ``trained``: steps
    around 0.1 and A = -(1..N), so some states forget within a chunk
    and others carry across all four. ``underflow``: a third of the
    channels with steps around 120, whose every decay is a float32
    zero, a third around 4, whose decays are not but whose products
    over a chunk are, and a third that carry across the chunks.
    ``repeated``: one token's operands at every position."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    xs = jax.random.normal(ks[0], (BSZ, T, DI))
    shift = {
        "trained": -2.0, "repeated": -1.0,
        "underflow": jnp.asarray([-2.0, 4.0, 120.0] * (DI // 3)),
    }[kind]
    dt = jax.nn.softplus(2.0 * jax.random.normal(ks[1], (BSZ, T, DI)) + shift)
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (DI, N)) * jnp.exp(
        0.1 * jax.random.normal(ks[2], (DI, N))
    )
    b = 0.5 * jax.random.normal(ks[3], (BSZ, T, N))
    c = 0.5 * jax.random.normal(ks[4], (BSZ, T, N))
    d = 1.0 + 0.3 * jax.random.normal(ks[5], (DI,))
    if kind == "repeated":
        xs, dt, b, c = (
            jnp.broadcast_to(v[:, :1], v.shape) for v in (xs, dt, b, c)
        )
    return (xs, dt, a, b, c, d), jax.random.normal(ks[6], xs.shape)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("kind", ["trained", "underflow", "repeated"])
def test_forward_and_every_gradient_are_the_recurrence_s(kind):
    args, dy = operands(kind)
    if kind == "underflow":
        decay = jnp.exp(args[1][..., None] * args[2])
        assert float(jnp.mean(decay == 0.0)) > 0.25  # float32 zeros
        over_a_chunk = jnp.prod(decay[:, :CHUNK], axis=1)
        assert float(jnp.mean(over_a_chunk == 0.0)) > 0.4
        assert float(jnp.max(over_a_chunk)) > 0.1  # and some carry
    scan = functools.partial(selective_scan, chunk=CHUNK)
    got, vjp = jax.vjp(scan, *args)
    want, ref_vjp = jax.vjp(recurrence, *args)
    # float32 sums in another order: the chunked form adds a chunk's
    # terms pairwise, the recurrence one at a time.
    assert _rel(got, want) < 2e-6
    for name, g, w in zip(ARGS, vjp(dy), ref_vjp(dy)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) < 1e-5, name


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_the_chunk_is_a_schedule_and_not_the_mathematics(chunk):
    args, _ = operands("trained", seed=1)
    want = selective_scan(*args, chunk=CHUNK)
    assert _rel(selective_scan(*args, chunk=chunk), want) < 2e-6


def test_a_bf16_state_would_not_pass():
    """The tolerance above is float32's: the same recurrence with its
    state rounded to bf16 a token is a thousand times further off."""
    args, _ = operands("trained")
    xs, dt, a, b, c, d = args

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (
            jnp.exp(dt_t[..., None] * a) * state
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        ).astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bdn,bn->bd", state, c_t) + d * x_t

    state = jnp.zeros((BSZ, DI, N), jnp.float32)
    _, y = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, c))
    )
    assert _rel(jnp.moveaxis(y, 0, 1), recurrence(*args)) > 1e-3


def test_the_backward_keeps_the_chunks_first_states_and_no_token_s():
    from jax._src.ad_checkpoint import saved_residuals

    args, _ = operands("trained")
    kept = saved_residuals(
        lambda *a: jnp.sum(selective_scan(*a, chunk=CHUNK)), *args
    )
    shapes = sorted(tuple(aval.shape) for aval, _ in kept)
    operand_shapes = sorted(tuple(v.shape) for v in args)
    assert (BSZ, T // CHUNK, N, DI) in shapes
    shapes.remove((BSZ, T // CHUNK, N, DI))
    # Nothing else but the six operands (and the output's own shape,
    # if the sum's rule lists it): no [T, N, Di] of every token.
    assert all(s in operand_shapes for s in shapes), shapes
    assert all(np.prod(s) < BSZ * T * N * DI for s in shapes)


def test_bf16_operands_give_bf16_back_and_a_float32_state():
    args, dy = operands("trained")
    xs, dt, a, b, c, d = args
    half = lambda v: v.astype(jnp.bfloat16)
    mixed = (half(xs), dt, a, half(b), half(c), d)
    got, vjp = jax.vjp(functools.partial(selective_scan, chunk=CHUNK), *mixed)
    assert got.dtype == jnp.bfloat16
    grads = vjp(half(dy))
    assert [g.dtype for g in grads] == [v.dtype for v in mixed]
    # Against the recurrence on the same rounded operands, in float32:
    # only the output's rounding is left.
    want = recurrence(*(v.astype(jnp.float32) for v in mixed))
    assert _rel(got.astype(jnp.float32), want) < 1e-2


def test_tokens_that_are_not_whole_chunks_are_refused():
    args, _ = operands("trained")
    with pytest.raises(ValueError, match="whole chunks"):
        selective_scan(*args, chunk=5)


def test_a_second_step_is_finite_where_decays_underflow():
    """A shortcut that is exact only at the first weights shows at the
    second (PR 53's did): three steps of gradient descent on every
    operand, each a thousandth of the operand's size; at every step
    the loss and the gradients are finite and the recurrence's."""
    args, dy = operands("underflow")

    def loss_of(scan):
        return lambda args: jnp.mean(jnp.square(scan(*args) - dy))

    step = jax.jit(jax.value_and_grad(
        loss_of(functools.partial(selective_scan, chunk=CHUNK))
    ))
    ref_step = jax.jit(jax.value_and_grad(loss_of(recurrence)))
    for _ in range(3):
        value, grads = step(args)
        want, ref = ref_step(args)
        assert bool(jnp.isfinite(value))
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
        for name, g, w in zip(ARGS, grads, ref):
            assert bool(jnp.all(jnp.isfinite(g))), name
            assert _rel(g, w) < 1e-4, name
        args = tuple(
            v - 1e-3 * jnp.max(jnp.abs(v)) * g / jnp.max(jnp.abs(g))
            for v, g in zip(args, grads)
        )
        # dt stays a step, A a decay.
        args = (args[0], jnp.abs(args[1]), -jnp.abs(args[2])) + args[3:]


def test_a_trace_says_what_ran():
    from dlrover_tpu import obs

    args, _ = operands("trained")
    tracer = obs.configure_tracer()
    try:
        jax.jit(functools.partial(selective_scan, chunk=CHUNK)).lower(*args)
        (said,) = [e for e in tracer.events() if e["name"] == "selscan.scan"]
    finally:
        obs.disable_tracer()
    assert (said["channels"], said["states"]) == (DI, N)
    assert (said["chunk"], said["chunks"]) == (CHUNK, T // CHUNK)
    assert said["kept"] == ["y", "chunk_states"]
