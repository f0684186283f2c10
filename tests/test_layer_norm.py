"""The models' one norm: ``models/gpt._layer_norm`` and
``models/llama._rms_norm``, the plain functions XLA fuses and every
cell runs, against float64 ``numpy`` (no ``jax.numpy`` in the
references).

Both compute in float32 and cast back once: for bfloat16 inputs the
output is held to the exact result rounded one time (half a unit in
the last place of bfloat16), which a product or a mean taken in
bfloat16 on the way would miss by several.
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from dlrover_tpu.models.gpt import _layer_norm
from dlrover_tpu.models.llama import _rms_norm

EPS = 1e-5
ROWS = (3, 70)
# Relative bounds from the dtype's significand (24 and 8 bits): a few
# units of float32 for a result computed in float32, half a unit of
# bfloat16 for that result rounded once, a whole unit where a
# gradient's cotangent was itself a rounded output.
F32 = 2.0**-20
BF16_HALF_ULP = 2.0**-8
BF16_ULP = 2.0**-7


def f64(a):
    return np.asarray(a).astype(np.float64)


def ref_forward(kind, x, g, b):
    if kind == "layer":
        mu = x.mean(-1, keepdims=True)
        xhat = (x - mu) / np.sqrt(x.var(-1, keepdims=True) + EPS)
        return xhat * g + b
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * g


def ref_grads(kind, x, g, b, y):
    """Gradients of ``sum(y ** 2)`` to the input, the gain and (layer)
    the bias, from the norm's own derivative; ``y`` is the output the
    squares were taken of (the rounded one for bfloat16)."""
    dy = 2.0 * y
    rows = tuple(range(x.ndim - 1))
    if kind == "layer":
        mu = x.mean(-1, keepdims=True)
        rstd = 1.0 / np.sqrt(x.var(-1, keepdims=True) + EPS)
        xhat = (x - mu) * rstd
        dxhat = dy * g
        dx = rstd * (
            dxhat
            - dxhat.mean(-1, keepdims=True)
            - xhat * (dxhat * xhat).mean(-1, keepdims=True)
        )
        return dx, (dy * xhat).sum(rows), dy.sum(rows)
    s = 1.0 / np.sqrt((x * x).mean(-1, keepdims=True) + EPS)
    dxs = dy * g
    dx = s * dxs - x * s**3 * (dxs * x).mean(-1, keepdims=True)
    return dx, (dy * x * s).sum(rows)


def norm(kind):
    if kind == "layer":
        return lambda x, g, b: _layer_norm(x, g, b, EPS)
    return lambda x, g, b: _rms_norm(x, g, EPS)


def close(got, want, rel):
    """Within ``rel`` of each value, and of the array's typical
    magnitude where a value is near zero by cancellation."""
    got, want = f64(got), f64(want)
    bound = rel * (np.abs(want) + np.abs(want).mean())
    worst = np.max(np.abs(got - want) - bound)
    assert worst <= 0, (worst, np.max(np.abs(got - want)))


@pytest.mark.parametrize("what", ["forward", "grads"])
@pytest.mark.parametrize("width", [768, 4096])
@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"]
)
@pytest.mark.parametrize("kind", ["layer", "rms"])
def test_norm_matches_float64_reference(kind, dtype, width, what):
    rng = np.random.default_rng(width)
    # off-centre rows, so the mean LayerNorm subtracts is not ~0
    x = jnp.asarray(rng.normal(0.3, 1.5, ROWS + (width,)), dtype)
    g = jnp.asarray(1.0 + rng.normal(0, 1, width), dtype)
    b = jnp.asarray(0.1 * rng.normal(0, 1, width), dtype)
    fn = norm(kind)
    # the references see the inputs as the function does: a bfloat16
    # value is a float64 value
    x64, g64, b64 = f64(x), f64(g), f64(b)
    y64 = ref_forward(kind, x64, g64, b64)
    bf16 = dtype == jnp.bfloat16

    if what == "forward":
        got = jax.jit(fn)(x, g, b)
        assert got.dtype == dtype and got.shape == x.shape
        # bfloat16: the float32 result rounded once is within half a
        # unit of the exact one (and float32's own error)
        close(got, y64, BF16_HALF_ULP + F32 if bf16 else F32)
        return

    # The squares are summed in float32 so that the cotangent into the
    # norm is twice its (rounded) output, exactly.
    def loss(x, g, b):
        return jnp.sum(fn(x, g, b).astype(jnp.float32) ** 2)

    argnums = (0, 1, 2) if kind == "layer" else (0, 1)
    got = jax.jit(jax.grad(loss, argnums))(x, g, b)
    y_out = f64(y64.astype(ml_dtypes.bfloat16)) if bf16 else y64
    want = ref_grads(kind, x64, g64, b64, y_out)
    assert len(got) == len(want)
    for a, w, like in zip(got, want, (x, g, b)):
        assert a.dtype == dtype and a.shape == like.shape
        close(a, w, BF16_ULP if bf16 else F32 * 16)
