"""ops/ssd.py in interpret mode against the state-space recurrence
written one token at a time: forward and every gradient, at a length
of several chunks, with one B/C group shared by every head (as
Granite 4.0-H publishes) and with two; what bf16 operands leave; the
split over a host-device mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.ssd import heads_per_step, ssd

ARGS = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t,
    a ``lax.scan`` step a token, from S = 0."""
    bsz, t, heads = dt.shape
    p = x.shape[-1] // heads
    per_group = heads // b.shape[2]
    b = jnp.repeat(b, per_group, axis=2)
    c = jnp.repeat(c, per_group, axis=2)

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + d[:, None] * x_t

    by_token = (
        x.reshape(bsz, t, heads, p).transpose(1, 0, 2, 3),
        dt.transpose(1, 0, 2), b.transpose(1, 0, 2, 3), c.transpose(1, 0, 2, 3),
    )
    state = jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(token, state, by_token)
    return y.transpose(1, 0, 2, 3).reshape(bsz, t, heads * p)


def operands(groups, heads=8, p=16, n=32, t=96, bsz=2, seed=0):
    """Seeded operands with steps and decays spread so that some heads
    forget within a chunk and others carry their state across all of
    them, and a seeded cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (bsz, t, heads * p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, heads)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b = 0.5 * jax.random.normal(ks[3], (bsz, t, groups, n))
    c = 0.5 * jax.random.normal(ks[4], (bsz, t, groups, n))
    d = 1.0 + 0.3 * jax.random.normal(ks[5], (heads,))
    return (x, dt, a, b, c, d), jax.random.normal(ks[6], x.shape)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# Float32 on both sides: the same sums in another order (chunks of 32
# against one token at a time). Read on these tests: the output 6e-7,
# the gradients at most 1.3e-6 of each one's largest element.
F32_TOL = 5e-6


@pytest.mark.parametrize("groups,heads", [(1, 8), (1, 16), (2, 8), (2, 16)])
def test_scan_and_gradients_agree_with_the_recurrence(groups, heads):
    """Three chunks of 32; 16 heads in one group make two grid steps
    of 8 heads that share one ``C B^T``; two groups of 4 make a grid
    step a group."""
    args, w = operands(groups, heads=heads)
    with jax.default_matmul_precision("highest"):
        got = ssd(*args, chunk=32)
        want = recurrence(*args)
        assert _rel(got, want) < F32_TOL
        g_got = jax.grad(
            lambda *a: jnp.sum(ssd(*a, chunk=32) * w), argnums=range(6)
        )(*args)
        g_want = jax.grad(
            lambda *a: jnp.sum(recurrence(*a) * w), argnums=range(6)
        )(*args)
    for name, a, b in zip(ARGS, g_got, g_want):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) < F32_TOL, name


def test_one_chunk_and_many_give_the_same_scan():
    """The chunk is a way to compute, not a part of the function."""
    args, _ = operands(1)
    with jax.default_matmul_precision("highest"):
        whole = ssd(*args, chunk=96)
        assert _rel(ssd(*args, chunk=16), whole) < F32_TOL
        assert _rel(ssd(*args, chunk=48), whole) < F32_TOL


def test_the_state_crosses_chunk_boundaries():
    """A scan that dropped the carried state would agree on the first
    chunk and nowhere after it."""
    args, _ = operands(1)
    with jax.default_matmul_precision("highest"):
        got = ssd(*args, chunk=32)
        x, dt, a, b, c, d = args
        alone = jnp.concatenate([
            ssd(x[:, s:s + 32], dt[:, s:s + 32], a, b[:, s:s + 32],
                c[:, s:s + 32], d, chunk=32)
            for s in (0, 32, 64)
        ], axis=1)
    assert _rel(got[:, :32], alone[:, :32]) < F32_TOL
    assert _rel(got[:, 32:], alone[:, 32:]) > 1e-2


def test_bf16_operands_fail_the_float32_tolerance():
    """x, B, C in bf16 feed the MXU in bf16 (states and decays stay
    float32): against the recurrence in float32 on the same values the
    output is off by bf16's rounding, 2e-3 here, not by float32's."""
    args, _ = operands(1)
    x, dt, a, b, c, d = args
    x16, b16, c16 = (v.astype(jnp.bfloat16) for v in (x, b, c))
    got = ssd(x16, dt, a, b16, c16, d, chunk=32)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = recurrence(
            x16.astype(jnp.float32), dt, a, b16.astype(jnp.float32),
            c16.astype(jnp.float32), d,
        )
    err = _rel(got.astype(jnp.float32), want)
    assert F32_TOL < err < 1e-2, err


def test_length_must_be_whole_chunks():
    args, _ = operands(1)
    with pytest.raises(ValueError, match="multiple of chunk"):
        ssd(*args, chunk=64)


def test_heads_per_step():
    assert [heads_per_step(n) for n in (64, 32, 8, 4, 6, 1)] == [8, 8, 8, 4, 2, 1]


@pytest.mark.parametrize("axes", [{"data": 2}, {"data": 2, "fsdp": 2}])
def test_scan_splits_itself_over_a_mesh(axes):
    """Under an ambient mesh each device scans its own batch rows;
    the per-head parameters' gradients are summed over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, under_mesh

    size = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:size])
    args, w = operands(1, bsz=4, t=64)

    def loss(*a):
        return jnp.sum(ssd(*a, chunk=32) * w)

    rows = NamedSharding(mesh, P(tuple(axes)))
    whole = NamedSharding(mesh, P())
    placed = [
        jax.device_put(v, rows if v.ndim > 1 else whole) for v in args
    ]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(loss, argnums=range(6)))(*args)
        got = jax.jit(
            jax.value_and_grad(under_mesh(loss, mesh), argnums=range(6))
        )(*placed)
    # A sum of 16,384 terms of either sign, 30 in all: the order of
    # the sum shows in the sixth digit.
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for name, a, b in zip(ARGS, got[1], want[1]):
        assert _rel(a, b) < F32_TOL, name
