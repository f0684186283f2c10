"""Static TPU tiling-floor audit of every Pallas kernel (VERDICT r4
weak #2, the (1, E) lesson).

Interpret-mode CPU tests execute kernels without enforcing Mosaic's
tiling constraints — the round-3 fused-norm backward shipped three
rounds of green tests while uncompilable on real TPU because its
dg/db partials used (1, E) blocks, below the 8-sublane f32 floor
(docs/ROOFLINE.md epilogue). Real-chip compilation
(tools/tpu_kernel_smoke.py, tests/test_tpu_compile_kernels.py) is the
ground truth; this audit catches the same bug CLASS at every kernel call by
intercepting ``pl.pallas_call`` and checking every BlockSpec against
the floors that bit us:

* second-minor (sublane) block dim: unless it spans the full array
  dim, it must be a positive multiple of the dtype's sublane tile
  (f32: 8, bf16: 16, int8/fp8: 32) — the (1, E) bug and the
  "unloweable 23-row block" case;
* minor (lane) block dim: unless it spans the full array dim, a
  multiple of 128.

The audit drives each public kernel entry (forward AND backward, f32
and bf16) at the same shape families the on-chip smoke uses, plus the
known-awkward shapes (odd sequence lengths, short suffixes).
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from tests.test_flash_attention import _rand_qkv


def _sublane_floor(dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def _check_block(name, block_shape, full_shape, dtype, violations):
    if block_shape is None or len(full_shape) < 2:
        return
    bs = tuple(block_shape)
    if len(bs) < 2:
        return
    sub, minor = bs[-2], bs[-1]
    fsub, fminor = full_shape[-2], full_shape[-1]
    floor = _sublane_floor(dtype)
    if sub is not None and sub != fsub and (sub < 1 or sub % floor):
        violations.append(
            f"{name}: sublane block dim {sub} (full {fsub}, "
            f"{jnp.dtype(dtype).name}) not a multiple of {floor}"
        )
    if minor is not None and minor != fminor and minor % 128:
        violations.append(
            f"{name}: lane block dim {minor} (full {fminor}) not a "
            "multiple of 128"
        )


@contextlib.contextmanager
def record_violations():
    """Patch pl.pallas_call to audit every BlockSpec against the
    arrays actually passed at call time. Yields the violation list;
    its ``.audited`` attribute counts inspected BlockSpecs so tests
    can assert the interception actually fired (a silently-broken
    patch would otherwise pass everything)."""

    class _Violations(list):
        audited = 0

    violations = _Violations()
    orig = pl.pallas_call

    def patched(kernel, **kw):
        inner = orig(kernel, **kw)
        in_specs = kw.get("in_specs")
        if "grid_spec" in kw and in_specs is None:
            # Specs carried inside a grid_spec object are invisible to
            # this audit; fail loudly so the audit is extended rather
            # than silently skipping the kernel (the failure mode this
            # file exists to prevent).
            violations.append(
                "pallas_call used grid_spec=...; the tiling audit "
                "cannot see its BlockSpecs — extend record_violations"
            )
        kname = getattr(kernel, "__name__", str(kernel))
        # functools.partial kernels: name of the wrapped fn.
        if isinstance(kernel, functools.partial):
            kname = getattr(kernel.func, "__name__", kname)

        def call(*args):
            if in_specs is not None:
                flat_specs = jax.tree.leaves(
                    in_specs,
                    is_leaf=lambda s: s is None
                    or isinstance(s, pl.BlockSpec),
                )
                flat_args = list(args)
                for i, (spec, arg) in enumerate(
                    zip(flat_specs, flat_args)
                ):
                    if not isinstance(spec, pl.BlockSpec):
                        continue
                    violations.audited += 1
                    _check_block(
                        f"{kname}[in{i}]", spec.block_shape,
                        arg.shape, arg.dtype, violations,
                    )
            out_shape = kw.get("out_shape")
            out_specs = kw.get("out_specs")
            if out_specs is not None and out_shape is not None:
                flat_out = jax.tree.leaves(
                    out_specs,
                    is_leaf=lambda s: s is None
                    or isinstance(s, pl.BlockSpec),
                )
                flat_shapes = jax.tree.leaves(
                    out_shape,
                    is_leaf=lambda s: hasattr(s, "shape"),
                )
                for i, (spec, sds) in enumerate(
                    zip(flat_out, flat_shapes)
                ):
                    if not isinstance(spec, pl.BlockSpec):
                        continue
                    violations.audited += 1
                    _check_block(
                        f"{kname}[out{i}]", spec.block_shape,
                        sds.shape, sds.dtype, violations,
                    )
            return inner(*args)

        return call

    pl.pallas_call = patched
    try:
        yield violations
    finally:
        pl.pallas_call = orig


def _qkv(b, t, h, d, dtype):
    # Shared fixture from the flash tests; cast AFTER generation so
    # f32 and bf16 runs audit the same value distribution.
    return tuple(
        x.astype(dtype)
        for x in _rand_qkv(jax.random.PRNGKey(0), b, t, h, d)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [256, 520])
def test_flash_square_fwd_bwd_blocks(dtype, t):
    from dlrover_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(1, t, 2, 64, dtype)
    with record_violations() as viol:
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, interpret=True
                ).astype(jnp.float32) ** 2
            )

        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not viol, "\n".join(viol)
    assert viol.audited > 0, "pallas_call interception never fired"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq,tk,off", [(23, 96, 0), (32, 160, 128)])
def test_flash_rect_fwd_bwd_blocks(dtype, tq, tk, off):
    from dlrover_tpu.ops.flash_attention import flash_attention_rect

    q = _qkv(1, tq, 2, 64, dtype)[0]
    _, k, v = _qkv(1, tk, 2, 64, dtype)
    with record_violations() as viol:
        def loss(q, k, v):
            return jnp.sum(
                flash_attention_rect(
                    q, k, v, causal=True, q_offset=off,
                    interpret=True,
                ).astype(jnp.float32) ** 2
            )

        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not viol, "\n".join(viol)
    assert viol.audited > 0, "pallas_call interception never fired"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_windowed_blocks(dtype):
    from dlrover_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(1, 512, 2, 64, dtype)
    with record_violations() as viol:
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, window=100, interpret=True
                ).astype(jnp.float32) ** 2
            )

        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not viol, "\n".join(viol)
    assert viol.audited > 0, "pallas_call interception never fired"


def test_prefix_lm_blocks():
    from dlrover_tpu.ops.prefix_lm import prefix_lm_attention

    q, k, v = _qkv(1, 128, 2, 64, jnp.float32)
    with record_violations() as viol:
        def loss(q, k, v):
            return jnp.sum(
                prefix_lm_attention(
                    q, k, v, prefix_len=37, interpret=True
                ) ** 2
            )

        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not viol, "\n".join(viol)
    assert viol.audited > 0, "pallas_call interception never fired"


def test_quantization_blocks():
    from dlrover_tpu.ops.quantization import (
        dequantize_blockwise,
        dequantize_blockwise_4bit,
        quantize_blockwise,
        quantize_blockwise_4bit,
    )

    x = jax.random.normal(jax.random.PRNGKey(2), (4096,))
    with record_violations() as viol:
        qv, scale, shape = quantize_blockwise(x)
        dequantize_blockwise(qv, scale, shape)
        q4, s4, shape4 = quantize_blockwise_4bit(x)
        dequantize_blockwise_4bit(q4, s4, shape4)
    assert not viol, "\n".join(viol)
    assert viol.audited > 0, "pallas_call interception never fired"


def test_audit_catches_the_r4_bug_shape():
    """Meta-test: the recorder must actually flag the (1, E) block
    that slipped through three rounds of interpret-green tests."""
    viol: list = []
    _check_block(
        "dg_db[out0]", (1, 768), (16384, 768), jnp.float32, viol
    )
    assert viol and "sublane block dim 1" in viol[0]
    # ... and accept the (8, E) fix.
    ok: list = []
    _check_block(
        "dg_db[out0]", (8, 768), (16384, 768), jnp.float32, ok
    )
    assert not ok
