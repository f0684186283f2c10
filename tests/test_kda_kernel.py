"""ops/kda.py's kernels (``kda_fwd`` / ``kda_bwd``), interpreted on
the CPU at the tile they are written for (head size 128, chunks of
64): ``kda`` on the kernel path, which the ``kda.scan`` event has to
say, against the recurrence a token at a time and its autodiff, for
the output and all five gradients, at tests/test_kimi_linear.py's
tolerances; the two hazards at the kernels' shape; padding, a batch
of sequences of one chunk; the plain form on the same operands; the
head size that stays on the plain form; and ``kda_wide``, the entry
for operands that stay ``[B, T, H*d]``, against ``kda`` on both
paths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.ops import kda

D = 128


def _operands(seed, dtype, b=1, t=128, heads=1, d=D, weakest=1e-3,
              strongest=1.6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, heads, d)))
    v = jax.random.normal(ks[2], (b, t, heads, d))
    g = -jnp.exp(jax.random.uniform(
        ks[3], (b, t, heads, d), minval=np.log(weakest),
        maxval=np.log(strongest),
    ))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, heads)))
    w = jax.random.normal(ks[5], (b, t, heads, d))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta), w


def _scans(operands, rule=None):
    """The ``kda.scan`` events of one trace of ``kda`` (or ``rule``)
    on ``operands``."""
    tracer = obs.configure_tracer()
    try:
        # A function of its own a call: ``eval_shape`` keeps traces.
        jax.eval_shape(lambda *a: (rule or kda.kda)(*a), *operands)
        return [e for e in tracer.events() if e["name"] == "kda.scan"]
    finally:
        obs.disable_tracer()


@functools.lru_cache(maxsize=None)
def _jitted(rule):
    def both(operands, w):
        def loss(*a):
            o = rule(*a)
            return jnp.sum(o.astype(jnp.float32) * w), o

        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True
        )(*operands)
        return o, grads

    return jax.jit(both)


def _both(rule, operands, w):
    """(o, the five gradients of sum(o * w)) of ``rule``, one program
    a rule and a shape."""
    return _jitted(rule)(operands, w)


def _plain(*operands):
    return kda._chunked(*operands, kda.CHUNK, kda.SUB_BLOCK)


def _wide(*operands):
    """``kda_wide`` on the 4-D operands' ``[B, T, H*d]`` views."""
    *wide, beta = operands
    b, t, h = beta.shape
    o = kda.kda_wide(*(x.reshape(b, t, -1) for x in wide), beta)
    return o.reshape(b, t, h, -1)


def _assert_close(got, want, tol):
    (o, grads), (o_want, grads_want) = got, want
    named = zip(
        "o q k v g beta".split(), (o,) + tuple(grads),
        (o_want,) + tuple(grads_want),
    )
    for name, a, b in named:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() < tol * np.abs(b).max(), (
            name, np.abs(a - b).max() / np.abs(b).max()
        )


def _assert_kernel(operands):
    scans, heads = _scans(operands), operands[0].shape[2]
    assert scans and all(e["kernel"] for e in scans), scans
    assert scans[0]["heads_per_step"] == kda.heads_per_step(heads)
    assert scans[0]["kept"] == ("kda_o", "kda_states")
    return scans[0]


# One program a shape and a rule (the interpreted kernels compile for
# seconds): the later tests come back to the first two shapes.
@pytest.mark.parametrize("dtype,tol,t,heads,b", [
    (jnp.float32, 2e-5, 128, 1, 1),
    (jnp.bfloat16, 2e-2, 256, 2, 1),
    (jnp.float32, 2e-5, 72, 1, 1),   # padded to two chunks
    (jnp.float32, 2e-5, 64, 1, 4),   # a chunk a sequence: no_carry's shape
])
def test_kernels_are_the_recurrence(dtype, tol, t, heads, b):
    operands, w = _operands(1, dtype, b=b, t=t, heads=heads)
    scan = _assert_kernel(operands)
    assert scan["chunks"] == -(-t // 64) and scan["chunk"] == 64
    _assert_close(
        _both(kda.kda, operands, w), _both(kda.recurrence, operands, w), tol
    )


def test_decays_beyond_float32_stay_finite_in_the_kernels():
    """Log decays near -1.6 a token: a chunk's running sum passes -89,
    where ``exp(-G)`` is beyond float32."""
    operands, w = _operands(
        2, jnp.float32, t=128, weakest=1.4, strongest=1.6
    )
    assert float(jnp.min(jnp.cumsum(operands[3][:, :64], axis=1))) < -89.0
    _assert_kernel(operands)
    _assert_close(
        _both(kda.kda, operands, w), _both(kda.recurrence, operands, w), 2e-5
    )


@pytest.mark.parametrize("beta", [0.5, 0.9, 0.99])
def test_a_run_of_one_token_stays_finite_in_the_kernels(beta):
    """Identical keys and values along the sequence, weak decay: the
    chunk's ``A`` is beta x ones below the diagonal, where the
    nilpotent product's error reached 1e9; output and gradients are
    the recurrence's."""
    t = 128
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, D))
    k = jnp.broadcast_to(k / jnp.linalg.norm(k), (1, t, 1, D))
    operands = (
        k * D ** -0.5, k, jnp.ones((1, t, 1, D)),
        jnp.full((1, t, 1, D), -1e-3), jnp.full((1, t, 1), beta),
    )
    w = jnp.ones((1, t, 1, D))
    _assert_kernel(operands)
    got = _both(kda.kda, operands, w)
    want = _both(kda.recurrence, operands, w)
    for a, b in zip((got[0],) + got[1], (want[0],) + want[1]):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("other,heads,tol", [
    (_plain, 1, 1e-5),
    # The same two kernels on the same operands: the same numbers.
    (_wide, 2, 0.0),
])
def test_kernels_and_another_form_agree(other, heads, tol):
    dtype = jnp.float32 if other is _plain else jnp.bfloat16
    operands, w = _operands(3, dtype, t=128 * heads, heads=heads)
    got, want = _both(kda.kda, operands, w), _both(other, operands, w)
    if tol:
        return _assert_close(got, want, tol)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


@pytest.mark.parametrize("rule,wide", [(kda.kda, False), (_wide, True)])
def test_the_event_says_whether_the_operands_came_wide(rule, wide):
    operands, _ = _operands(3, jnp.bfloat16, t=256, heads=2)
    (scan,) = _scans(operands, rule)
    assert scan["kernel"] is True and scan["wide"] is wide
    assert scan["heads"] == 2 and scan["chunks"] == 4


@pytest.mark.parametrize("rule", [kda.kda, _wide])
def test_head_size_16_takes_the_plain_form(rule):
    """tests/test_kimi_linear.py holds that path's numbers; the wide
    entry reshapes there and gives ``kda``'s, padding and all."""
    operands, w = _operands(4, jnp.float32, t=72, heads=2, d=16)
    (scan,) = _scans(operands, rule)
    assert scan["kernel"] is False and scan["wide"] is (rule is _wide)
    assert "heads_per_step" not in scan and "kept" not in scan
    if rule is _wide:
        _assert_close(
            _both(_wide, operands, w), _both(kda.kda, operands, w), 1e-6
        )


def test_a_rule_in_kda_s_place_is_the_wide_entry_s_too(monkeypatch):
    """benchmark/controls/kimi_linear.py puts a broken 4-D rule in
    ``kda``'s place while a loss is traced: the mixer's entry calls
    it, on 4-D views, whatever the head size."""
    seen = []

    def broken(q, k, v, g, beta, chunk, sub_block):
        seen.append((q.shape, k.shape, v.shape, g.shape, beta.shape))
        return jnp.zeros_like(v)

    monkeypatch.setattr(kda, "kda", broken)
    operands, _ = _operands(5, jnp.bfloat16, t=64, heads=2)
    shape = jax.eval_shape(_wide, *operands)
    assert seen == [((1, 64, 2, D),) * 4 + ((1, 64, 2),)]
    assert shape.shape == (1, 64, 2, D)
