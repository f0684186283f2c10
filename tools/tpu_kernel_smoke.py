"""Compile + parity-check every Pallas kernel on the REAL TPU.

The round-4 lesson: interpret-mode tests (the CPU suite) do not
enforce TPU tiling constraints — the round-3 fused-norm backward
(a kernel since deleted) shipped three rounds of green CPU tests
while being uncompilable on hardware (its (1, E) dg partials sat
below the 8-sublane tile floor).
This tool is the guard: one run lowers and executes every kernel
variant on the live chip and checks numerics against the XLA
reference. ``chip_smoke.py`` runs it as part of its kernel phase
(``run(small=False)``); ``tests/test_tpu_compile_kernels.py`` asks the
same of the chip's compiler without a chip.

Run on a TPU host:  python tools/tpu_kernel_smoke.py
Exit code is the number of failing kernels (0 = all good); off the
TPU the full run refuses to start.

``--small`` shrinks shapes so the harness itself can be validated on
CPU in interpret mode in seconds — that run checks the TOOL, not the
hardware lowering (which is the entire point of the full run).
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys
import time

import _repo_path  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = []
# Shapes: small keeps CPU interpret runs tractable; full exercises
# the shipped 1024x1024 tiles. Set by run().
SMALL = False
SEQ = 1024  # seq for flash checks
XENT_V = 50304  # rows for xent
_ERRS = []  # max-abs errors of the _close calls of the running check
_LIMITS = []  # the relative limits its _close_rel calls held them to


def check(name, fn):
    t0 = time.time()
    _ERRS.clear()
    _LIMITS.clear()
    try:
        fn()
        RESULTS.append(
            {"kernel": name, "ok": True,
             "max_abs_err": max(_ERRS, default=None),
             "limit": max(_LIMITS, default=None),
             "seconds": round(time.time() - t0, 1)}
        )
        print(f"ok   {name} max_abs_err={max(_ERRS, default=None)} "
              f"limit={max(_LIMITS, default=None)} "
              f"({time.time() - t0:.1f}s)")
    except Exception as exc:  # noqa: BLE001
        RESULTS.append(
            {"kernel": name, "ok": False,
             "error": f"{type(exc).__name__}: {str(exc)[:300]}"}
        )
        print(f"FAIL {name}: {type(exc).__name__}: {str(exc)[:200]}")


def _close(a, b, atol, rtol=1e-3):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    _ERRS.append(float(np.max(np.abs(a - b))))
    np.testing.assert_allclose(a, b, atol=atol, rtol=rtol)


def _prec(tag):
    """True-f32 parity needs both sides pinned to exact-f32 matmuls.

    Under the TPU default (single-pass bf16 on the MXU), the
    near-cancelling rows of attention backward (dp - delta ~= 0 for
    near-deterministic softmax rows) leave ~4e-3 * |dp| rounding
    residue, and kernel vs reference round differently — the first
    r5 hardware smoke measured 0.054 max-abs spread against the 2e-2
    f32 tolerance in exactly those rows, in BOTH the causal and
    windowed variants (same early rows, same data). The f32 checks
    validate the math, so they trace (kernel AND reference) under
    HIGHEST — multi-pass, f32-exact; all three then pass on chip.

    bf16 checks must stay at the production default: Mosaic rejects
    HIGHEST with bf16 operands (r5 smoke: remote-compile crash), and
    default single-pass is what training runs anyway.
    """
    return (jax.default_matmul_precision("highest") if tag == "f32"
            else contextlib.nullcontext())


def flash_checks():
    from dlrover_tpu.ops.flash_attention import flash_attention
    from dlrover_tpu.ops.prefix_lm import (
        prefix_lm_attention,
        prefix_lm_attention_reference,
    )

    # The canonical XLA reference the flash kernel must agree with —
    # the repo's own non-flash fallback, not a local re-derivation
    # that could drift.
    from dlrover_tpu.models.gpt import _default_attention as dense

    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (2, SEQ, 4, 64), jnp.float32)
        for kk in jax.random.split(key, 3)
    )

    def grad_check(f_kernel, f_ref, *args, atol):
        """Full parity: dq AND dk AND dv (a wrong dkv accumulation
        must not exit 0 from a 'parity-check' tool)."""
        argnums = tuple(range(len(args)))
        gk = jax.jit(jax.grad(
            lambda *a: jnp.sum(f_kernel(*a).astype(jnp.float32) ** 2),
            argnums=argnums,
        ))
        gr = jax.jit(jax.grad(
            lambda *a: jnp.sum(f_ref(*a).astype(jnp.float32) ** 2),
            argnums=argnums,
        ))
        for got, want in zip(gk(*args), gr(*args)):
            _close(got, want, atol)

    # One dtype/tolerance table for every dtype-parametrized check —
    # TPU sublane tile floors are dtype-dependent (8 for f32, 16 for
    # bf16), so the production bf16 path needs its own lowering check
    # everywhere, at its own (looser) parity tolerance.
    DTYPES = (
        (jnp.float32, "f32", 2e-2), (jnp.bfloat16, "bf16", 0.5),
    )

    # fwd+bwd (dq/dk/dv), causal and full, at the shipped 1024x1024
    # tiles, in f32 AND bf16.
    for dt, tag, atol in DTYPES:
        qd, kd, vd = q.astype(dt), k.astype(dt), v.astype(dt)
        with _prec(tag):
            check(
                f"flash_causal_fwd_bwd_{tag}",
                functools.partial(
                    grad_check,
                    lambda q_, k_, v_: flash_attention(
                        q_, k_, v_, causal=True
                    ),
                    lambda q_, k_, v_: dense(q_, k_, v_, True),
                    qd, kd, vd, atol=atol,
                ),
            )
    with _prec("f32"):
        check(
            "flash_full_fwd_bwd",
            lambda: grad_check(
                lambda q_, k_, v_: flash_attention(
                    q_, k_, v_, causal=False
                ),
                lambda q_, k_, v_: dense(q_, k_, v_, False),
                q, k, v, atol=2e-2,
            ),
        )
    # The row statistics themselves: lse, and gradients with a
    # cotangent on lse (folded into delta, the backward kernel's other
    # row operand). No other check reads lse, and how the backward's
    # key-major tiles take a [1, block_q] row is Mosaic's, which
    # interpret mode does not run.
    def dense_lse(q_, k_, v_):
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q_, k_,
            preferred_element_type=jnp.float32,
        ) / (q_.shape[-1] ** 0.5)
        pos = jnp.arange(q_.shape[1])
        s = jnp.where(pos[:, None] >= pos[None, :], s, -1e30)
        return dense(q_, k_, v_, True), jax.nn.logsumexp(s, axis=-1)

    def flash_lse(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, return_lse=True)

    def with_lse_cotangent(f):
        def out(*a):
            o, lse = f(*a)
            return o + jnp.sin(lse).transpose(0, 2, 1)[..., None]
        return out

    def lse_check():
        _close(flash_lse(q, k, v)[1], dense_lse(q, k, v)[1], 2e-3)
        grad_check(
            with_lse_cotangent(flash_lse), with_lse_cotangent(dense_lse),
            q, k, v, atol=2e-2,
        )

    with _prec("f32"):
        check("flash_lse_fwd_bwd", lse_check)
    # Sliding window (Mistral band) + non-1024 sequence (512 tiles),
    # gradients included (the banded bwd has its own dispatch) — in
    # bf16 too (the production decode dtype; its tile floors are 2x
    # the f32 ones).
    half = SEQ // 2
    qs, ks, vs = q[:, :half], k[:, :half], v[:, :half]
    for dt, tag, atol in DTYPES:
        with _prec(tag):
            check(
                f"flash_sliding_window_fwd_bwd_{tag}",
                functools.partial(
                    grad_check,
                    lambda q_, k_, v_: flash_attention(
                        q_, k_, v_, causal=True, window=half // 4
                    ),
                    lambda q_, k_, v_: dense(
                        q_, k_, v_, True, window=half // 4
                    ),
                    qs.astype(dt), ks.astype(dt), vs.astype(dt),
                    atol=atol,
                ),
            )
    # Odd length -> internal padding path.
    odd = SEQ // 2 + 8
    qo, ko, vo = q[:, :odd], k[:, :odd], v[:, :odd]
    with _prec("f32"):
        check(
            "flash_padded_t520",
            lambda: _close(
                flash_attention(qo, ko, vo, causal=True),
                dense(qo, ko, vo, True), 2e-3,
            ),
        )
        # GLM prefix-LM composition (square prefix + rectangular
        # causal suffix) — exercises flash_attention_rect's lowering.
        check(
            "prefix_lm_composition",
            lambda: _close(
                prefix_lm_attention(q, k, v, SEQ // 3),
                prefix_lm_attention_reference(q, k, v, SEQ // 3), 2e-3,
            ),
        )
    # Rectangular grads (chunked-prefill shape: tail queries against
    # the full key set, per-side padding).
    from dlrover_tpu.ops.flash_attention import flash_attention_rect

    def dense_rect(q_, k_, v_, win=None):
        off = k_.shape[1] - q_.shape[1]
        d_ = q_.shape[-1]
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q_, k_,
            preferred_element_type=jnp.float32,
        ) / (d_**0.5)
        qp = off + jnp.arange(q_.shape[1])[:, None]
        kp = jnp.arange(k_.shape[1])[None, :]
        keep = kp <= qp
        if win is not None:
            keep &= (qp - kp) < win
        s = jnp.where(keep[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", w, v_.astype(jnp.float32)
        ).astype(q_.dtype)

    tq = SEQ // 4
    with _prec("f32"):
        check(
            "flash_rect_fwd_bwd",
            lambda: grad_check(
                lambda q_, k_, v_: flash_attention_rect(
                    q_, k_, v_, causal=True
                ),
                dense_rect, q[:, -tq:], k, v, atol=2e-2,
            ),
        )

    # Banded rectangular (q_offset + window) — the windowed ring's
    # live non-resident hop kernel (parallel/ring_attention.py
    # _ring_flash_windowed) and windowed chunked prefill; new in r5,
    # never compiled on hardware before this check.
    win_w = SEQ // 8
    for dt, tag, atol in DTYPES:
        with _prec(tag):
            check(
                f"flash_rect_windowed_fwd_bwd_{tag}",
                functools.partial(
                    grad_check,
                    lambda q_, k_, v_: flash_attention_rect(
                        q_, k_, v_, causal=True, window=win_w
                    ),
                    lambda q_, k_, v_: dense_rect(
                        q_, k_, v_, win=win_w
                    ),
                    q[:, -tq:].astype(dt), k.astype(dt),
                    v.astype(dt), atol=atol,
                ),
            )

    # The backward's sub-tiles of a block the mask crosses, at the
    # chip's real blocks (SEQ x SEQ, the default at 1024): the rolled
    # loops' bounds, the slices of the q block's rows along the lanes
    # and of the scratch accumulators are Mosaic's, which interpret
    # mode does not run. One diagonal block; ten blocks, four on the
    # diagonal; the band's edge across blocks at head size 128 with
    # grouped queries; key padding; q rows at an offset.
    from dlrover_tpu import obs

    def subtile_check():
        def causal(window=None, block=SEQ):
            return (
                lambda q_, k_, v_: flash_attention(
                    q_, k_, v_, causal=True, window=window,
                    block_q=block, block_k=block,
                ),
                lambda q_, k_, v_: dense(q_, k_, v_, True, window=window),
            )

        def grouped(f):
            return lambda q_, kv_, vv_: f(
                q_, jnp.repeat(kv_, 2, axis=2), jnp.repeat(vv_, 2, axis=2)
            )

        def rand(t, h, d, dt, seed):
            return (
                jax.random.normal(kk, (1, t, h, d), jnp.float32).astype(dt)
                for kk in jax.random.split(jax.random.PRNGKey(seed), 3)
            )

        tracer = obs.configure_tracer()
        try:
            with _prec("f32"):
                grad_check(*causal(), q, k, v, atol=2e-2)
                grad_check(
                    *causal(), *rand(4 * SEQ, 2, 64, jnp.float32, 1),
                    atol=2e-2,
                )
                # t=520 in the blocks it gets by default, padded to two
                grad_check(*causal(block=half), qo, ko, vo, atol=2e-2)
                grad_check(
                    lambda q_, k_, v_: flash_attention_rect(
                        q_, k_, v_, causal=True
                    ),
                    dense_rect, q[:, -tq:], k, v, atol=2e-2,
                )
            q8, k8, v8 = rand(8 * SEQ, 4, 128, jnp.bfloat16, 2)
            grad_check(
                *map(grouped, causal(window=4 * SEQ)),
                q8, k8[:, :, :2], v8[:, :, :2], atol=0.5,
            )
            areas = [
                e for e in tracer.events() if e["name"] == "flash.bwd_area"
            ]
        finally:
            obs.disable_tracer()
        assert len(areas) == 5, areas
        for e in areas:  # every call met crossing blocks, and split them
            assert e["sub"] < e["block_q"], e
            assert e["required"] <= e["run"] < e["visited"], e

    check("flash_bwd_subtiles", subtile_check)

    def two_head_sizes():
        """Latent attention's call (models/kimi_linear.py): queries
        and keys of 192 columns, values of 128, 32 heads, causal, at
        scale 192^-0.5, in bf16 against plain attention on the same
        values in float32. The output and the three gradients differ
        by the operands' own rounding (3.3e-3 on the chip, PERF.md
        section 6, PR 53; the limit is six times that); a kernel that
        sized ``o`` or ``dv`` by the query's head would not compile,
        and one that scaled by the value's reads 0.2."""
        d_qk, d_v, heads = (24, 16, 2) if SMALL else (192, 128, 32)
        keys = jax.random.split(jax.random.PRNGKey(11), 4)
        t = 2 * SEQ
        q2, k2 = (
            jax.random.normal(kk, (1, t, heads, d_qk)).astype(jnp.bfloat16)
            for kk in keys[:2]
        )
        v2 = jax.random.normal(keys[2], (1, t, heads, d_v)).astype(jnp.bfloat16)
        w2 = jax.random.normal(keys[3], (1, t, heads, d_v))
        scale = d_qk ** -0.5

        def grads(fn, *args):
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w2),
                argnums=(0, 1, 2),
            ))(*args)

        o = jax.jit(lambda *a: flash_attention(*a, scale=scale))(q2, k2, v2)
        assert o.shape == v2.shape, o.shape
        _, got = grads(lambda *a: flash_attention(*a, scale=scale), q2, k2, v2)
        with _prec("f32"):
            f32 = [x.astype(jnp.float32) for x in (q2, k2, v2)]
            want_o = jax.jit(lambda *a: dense(*a, scale=scale))(*f32)
            _, want = grads(lambda *a: dense(*a, scale=scale), *f32)
        _close_rel(o, want_o, 2e-2)
        for g, r in zip(got, want):
            _close_rel(g, r, 2e-2)

    check("flash_qk192_v128", two_head_sizes)


def flash_wide_checks():
    """The flash kernels on the layout the projections write
    (``flash_attention_wide``, ``ops/rope.rope_wide``,
    ``flash_group_sum``) against the 4-D entry with ``apply_rope`` and
    the repeat in front of it, at Mistral's shape a head: 8 x SEQ
    tokens, a window of half of them, groups of four heads of 128,
    bf16. The same kernels on the same blocks; the rotated q and k
    may differ in their last place (XLA keeps or drops the excess
    precision inside ``apply_rope`` as it fuses), dq and dk pass
    through ``delta``, summed in another order (a membership
    product), and dk through a rotation after the group sum and not
    before it."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_wide,
    )
    from dlrover_tpu.ops.rope import rope_wide

    t, h, hkv, d = 8 * SEQ, 8, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(62), 4)
    q = jax.random.normal(keys[0], (1, t, h * d)).astype(jnp.bfloat16)
    k, v = (
        jax.random.normal(kk, (1, t, hkv * d)).astype(jnp.bfloat16)
        for kk in keys[1:3]
    )
    w = jax.random.normal(keys[3], (1, t, h * d))
    angle = jnp.arange(t)[:, None] * (
        10000.0 ** (-jnp.arange(d // 2) / (d // 2))
    )[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    kw = dict(causal=True, window=t // 2)

    def wide(q, k, v):
        return flash_attention_wide(
            rope_wide(q, cos, sin, h), rope_wide(k, cos, sin, hkv), v,
            n_head=h, n_kv_head=hkv, **kw,
        )

    def four_d(q, k, v):
        q4 = llama.apply_rope(q.reshape(1, t, h, d), cos, sin)
        k4 = llama.apply_rope(k.reshape(1, t, hkv, d), cos, sin)
        return flash_attention(
            q4, jnp.repeat(k4, h // hkv, axis=2),
            jnp.repeat(v.reshape(1, t, hkv, d), h // hkv, axis=2), **kw,
        ).reshape(1, t, h * d)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2),
        ))(q, k, v)

    def parity():
        o_wide, o_4d = jax.jit(wide)(q, k, v), jax.jit(four_d)(q, k, v)
        _close_rel(o_wide, o_4d, 2e-2)
        (_, got), (_, want) = both(wide), both(four_d)
        for g, r in zip(got, want):
            _close_rel(g, r, 2e-2)

    check("flash_wide_gqa_bf16", parity)


def quant_checks():
    from dlrover_tpu.ops.quantization import (
        dequantize_blockwise,
        dequantize_blockwise_4bit,
        quantize_blockwise,
        quantize_blockwise_4bit,
    )

    x = jax.random.normal(jax.random.PRNGKey(4),
        (512, 256) if SMALL else (4096, 512),
    )

    def rt8():
        q, s, shape = quantize_blockwise(x)
        y = dequantize_blockwise(q, s, shape)
        _ERRS.append(float(jnp.abs(y - x).max()))
        assert _ERRS[-1] < 0.05

    def rt4():
        q, s, shape = quantize_blockwise_4bit(x)
        y = dequantize_blockwise_4bit(q, s, shape)
        _ERRS.append(float(jnp.abs(y - x).max()))
        assert _ERRS[-1] < 0.6

    check("quantize_8bit_roundtrip", rt8)
    check("quantize_4bit_roundtrip", rt4)


def xent_checks():
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    n, e, v = (64, 128, XENT_V) if SMALL else (512, 256, 50304)
    x = jax.random.normal(jax.random.PRNGKey(5), (n, e)) * 0.1
    w = jax.random.normal(jax.random.PRNGKey(6), (v, e)) * 0.02
    t = jax.random.randint(jax.random.PRNGKey(7), (n,), 0, v)

    def ref(x, w):
        logits = (x @ w.T).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(
            jnp.take_along_axis(lp, t[:, None], axis=-1)
        )

    def run():
        gk = jax.jit(jax.grad(
            lambda x, w: fused_cross_entropy(x, w, t, 8),
            argnums=(0, 1),
        ))
        gr = jax.jit(jax.grad(ref, argnums=(0, 1)))
        for got, want in zip(gk(x, w), gr(x, w)):
            _close(got, want, 2e-3)

    check("fused_xent", run)


def _close_rel(got, want, tol):
    """Largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    _ERRS.append(err)
    _LIMITS.append(tol)
    assert err <= tol, f"relative error {err:.3g} over {tol:.3g}"


def _ssd_recurrence(x, dt, a, b, c, d):
    """The state-space recurrence one token a step, float32: x
    [B, T, H*P], dt [B, T, H], a, d [H], b, c [B, T, 1, N]."""
    bsz, t, heads = dt.shape
    x = x.astype(jnp.float32).reshape(bsz, t, heads, -1)
    b, c = b.astype(jnp.float32)[:, :, 0], c.astype(jnp.float32)[:, :, 0]

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t) + d[:, None] * x_t
        return state, y_t

    state = jnp.zeros((bsz, heads, x.shape[-1], b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(
        token, state, [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)]
    )
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, -1)


def ssd_checks():
    """``ssd_fwd`` and ``ssd_bwd`` against the recurrence one token a
    step, at the published Mamba-2 widths over four chunks, with
    memories from a few tokens to several chunks. The float32
    tolerance lies between what the chip reads for the kernels as
    they are (2.4e-4) and with the running state rounded to bf16
    (1.4e-3; a state that does not cross a chunk boundary reads 0.74;
    PERF.md section 6, PR 34; tests/test_tpu_kernel_smoke.py tries
    both on the CPU); the bf16 one is the operands' own rounding
    (5.8e-3 on the chip)."""
    from dlrover_tpu.ops.ssd import ssd

    t, heads, p, n, chunk = (
        (64, 8, 16, 32, 16) if SMALL else (1024, 64, 64, 128, 256)
    )
    keys = jax.random.split(jax.random.PRNGKey(8), 7)
    x = jax.random.normal(keys[0], (1, t, heads * p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, t, heads)) - 2.0)
    a = -jnp.linspace(0.02, 1.0, heads)
    b = jax.random.normal(keys[2], (1, t, 1, n)) * 0.3
    c = jax.random.normal(keys[3], (1, t, 1, n)) * 0.3
    d = 1.0 + 0.1 * jax.random.normal(keys[4], (heads,))
    w = jax.random.normal(keys[5], (1, t, heads * p))

    def both(dtype, tol):
        xs, bs, cs = (v.astype(dtype) for v in (x, b, c))

        def run():
            def scalar(scan):
                return lambda *args: jnp.sum(
                    scan(*args).astype(jnp.float32) * w
                )

            grads = lambda scan: jax.jit(jax.value_and_grad(
                scalar(scan), argnums=(0, 1, 2, 3, 4, 5)
            ))(xs, dt, a, bs, cs, d)
            with _prec("f32" if dtype == jnp.float32 else "bf16"):
                y = jax.jit(lambda *args: ssd(*args, chunk=chunk))(
                    xs, dt, a, bs, cs, d
                )
                _close_rel(y, _ssd_recurrence(xs, dt, a, bs, cs, d), tol)
                (_, got), (_, want) = (
                    grads(lambda *args: ssd(*args, chunk=chunk)),
                    grads(_ssd_recurrence),
                )
            for g, r in zip(got, want):
                _close_rel(g, r, tol)

        return run

    check("ssd_fwd_bwd_f32", both(jnp.float32, 5e-4))
    check("ssd_fwd_bwd_bf16", both(jnp.bfloat16, 2e-2))


def kda_checks():
    """The chunked delta rule (ops/kda.py) against the recurrence a
    token at a time, at the published head size over eight chunks,
    with log decays down to -1.6 a token (the family's initial range:
    a chunk's running sum passes -50, and at the small shapes, where
    they go down to -3, -100: a decay factored as ``exp(G) exp(-G)``
    overflows there). The float32 limit lies between what the chip
    reads for the rule as it is (4.5e-6 forward, 8.4e-6 the largest
    gradient) and with the state reset at chunk boundaries or the
    delta term dropped (over 0.1 both; tests/test_tpu_kernel_smoke.py
    tries them on the CPU); the bf16 one is the operands' own rounding
    (3.8e-3 on the chip; PERF.md section 6, PR 53)."""
    from dlrover_tpu import obs
    from dlrover_tpu.ops import kda

    t, heads, d, strongest = (
        (128, 2, 16, 3.0) if SMALL else (512, 4, 128, 1.6)
    )
    keys = jax.random.split(jax.random.PRNGKey(12), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (1, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (1, t, heads, d)))
    v = jax.random.normal(keys[2], (1, t, heads, d))
    g = -jnp.exp(jax.random.uniform(
        keys[3], (1, t, heads, d), minval=np.log(1e-3),
        maxval=np.log(strongest),
    ))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, heads)))
    w = jax.random.normal(keys[5], (1, t, heads, d))

    def both(dtype, tol):
        qs, ks, vs = (x.astype(dtype) for x in (q, k, v))

        def run():
            grads = lambda rule: jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * w),
                argnums=(0, 1, 2, 3, 4),
            ))(qs, ks, vs, g, beta)
            tracer = obs.configure_tracer()
            try:
                with _prec("f32" if dtype == jnp.float32 else "bf16"):
                    o = jax.jit(lambda *a: kda.kda(*a))(qs, ks, vs, g, beta)
                    _close_rel(o, kda.recurrence(qs, ks, vs, g, beta), tol)
                    (_, got), (_, want) = (
                        grads(lambda *a: kda.kda(*a)), grads(kda.recurrence)
                    )
                    # The mixer's entry, on the [B, T, H*d] views.
                    flat = lambda x: x.reshape(1, t, -1)
                    _, got_wide = grads(lambda *a: kda.kda_wide(
                        *map(flat, a[:4]), a[4]
                    ).reshape(v.shape))
                scans = [
                    e for e in tracer.events() if e["name"] == "kda.scan"
                ]
            finally:
                obs.disable_tracer()
            for a, b in zip(got, want):
                _close_rel(a, b, tol)
            # The same kernels on the same operands: the same numbers.
            for a, b in zip(got_wide, got):
                assert np.array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32)
                ), "kda_wide is not kda"
            # The kernels take whole lanes of a head: at the full shape
            # this check holds kda_fwd and kda_bwd, and must not pass
            # on the plain form.
            said = [e.get("kernel") for e in scans]
            assert said and all(x is (d % 128 == 0) for x in said), (
                f"kda.scan said kernel: {said} at head size {d}"
            )

        return run

    check("kda_fwd_bwd_f32", both(jnp.float32, 2e-4))
    check("kda_fwd_bwd_bf16", both(jnp.bfloat16, 2e-2))


def _plain_conv_silu(x, w, bias):
    """A mixer's convolution as pad, shifted slices and multiply-adds,
    then SiLU, in the inputs' dtype throughout: the reference, and
    what autodiff differentiates for its gradients."""
    width, t = w.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = bias
    for k in range(width):
        out = out + x[:, k:k + t] * w[k]
    return jax.nn.silu(out)


def ssm_conv_checks():
    """``conv_silu_fwd`` and ``conv_silu_bwd`` (ops/causal_conv.py)
    as Granite's mixer calls them: the columns of ``x`` (4,096 from
    4,096 on) and of ``B|C`` (256 from 8,192 on) read in place from a
    [1, 4096, 8512] bf16 projection, eight row tiles, width 4; against
    the plain shifted form and its autodiff on the same values in
    float32. ``y``, ``dx``, ``dw`` and ``dbias`` each differ by the
    one bf16 cast that ends them, at most 2**-8 of the largest value
    (3.9e-3; the limit is twice that; a backward that reads ``g[t]``
    alone for ``dx[t]`` reads 1.61 at the small shapes:
    tests/test_tpu_kernel_smoke.py)."""
    from dlrover_tpu.ops import causal_conv

    t, inner, bc, width = (128, 256, 128, 4) if SMALL else (4096, 4096, 256, 4)
    wide = 2 * inner + bc + 64
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    proj = jax.random.normal(keys[0], (1, t, wide)).astype(jnp.bfloat16)

    def grads(fn, *args):
        def call(x, w, bias, dy):
            y, pull = jax.vjp(fn, x, w, bias)
            return (y, *pull(dy))
        return jax.jit(call)(*args)

    def both(start, channels):
        k_dy, k_w, k_b = jax.random.split(keys[1 + (start > inner)], 3)
        dy = jax.random.normal(k_dy, (1, t, channels)).astype(jnp.bfloat16)
        w, bias = (
            jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5).astype(
                jnp.bfloat16
            )
            for k, shape in ((k_w, (width, channels)), (k_b, (channels,)))
        )
        y, dproj, dw, dbias = grads(
            functools.partial(causal_conv.conv_silu, start=start),
            proj, w, bias, dy,
        )
        beside = dproj.at[..., start:start + channels].set(0)
        assert not np.any(np.asarray(beside, np.float32)), "dx off its columns"
        with _prec("f32"):
            want = grads(_plain_conv_silu, *(
                v.astype(jnp.float32)
                for v in (proj[..., start:start + channels], w, bias, dy)
            ))
        got = (y, dproj[..., start:start + channels], dw, dbias)
        for g, r in zip(got, want):
            _close_rel(g, r, 2.0 ** -7)

    def run():
        both(inner, inner)
        both(2 * inner, bc)

    check("ssm_conv_fwd_bwd_bf16", run)


def run(small: bool) -> list:
    """Every check, at the full or the small shapes; returns the
    result records (``ok`` False on a compile error or parity miss)."""
    global SMALL, SEQ, XENT_V
    SMALL = small
    SEQ = 128 if small else 1024
    XENT_V = 1024 if small else 50304
    RESULTS.clear()
    flash_checks()
    flash_wide_checks()
    quant_checks()
    xent_checks()
    ssd_checks()
    ssm_conv_checks()
    kda_checks()
    return list(RESULTS)


def main() -> int:
    small = "--small" in sys.argv
    print(f"devices: {jax.devices()}")
    if jax.default_backend() != "tpu" and not small:
        print(
            f"not on a TPU (backend {jax.default_backend()!r}): the "
            "full run validates hardware lowering and nothing else; "
            "use --small to check the tool itself",
            file=sys.stderr,
        )
        return 2
    results = run(small)
    fails = [r for r in results if not r["ok"]]
    print(json.dumps({
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "total": len(results),
        "failed": len(fails),
    }))
    return len(fails)


if __name__ == "__main__":
    sys.exit(main())
