"""Pallas flash attention vs. reference einsum attention (fwd + grads).

Runs the kernel in interpreter mode on the CPU test mesh (conftest sets
JAX_PLATFORMS=cpu), exercising the exact code path that compiles on TPU.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.gpt import _default_attention
from dlrover_tpu.ops.flash_attention import flash_attention

# ``dlrover_tpu.ops.flash_attention`` the attribute is the re-exported
# function; this is the module.
flash_module = importlib.import_module("dlrover_tpu.ops.flash_attention")


def _rand_qkv(key, b, t, h, d, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [128, 256])
def test_forward_matches_reference(causal, t):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, t, 2, 64)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _default_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_unpadded_vs_padded_seq():
    # t=192 pads to 256 internally; padded keys must not leak in.
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 192, 2, 64)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t", [520, 1000, 1024, 1536, 2048])
def test_default_block_sizes_pad_stays_bounded(t):
    """Regression: unequal default blocks once padded to
    lcm(block_q, block_k), which explodes for t=520 (lcm 33280).
    Defaults must never pad a sequence by more than one block."""
    import math

    from dlrover_tpu.ops.flash_attention import default_block_sizes

    bq, bk = default_block_sizes(t)
    pad = (-t) % math.lcm(bq, bk)
    assert pad < max(bq, bk)


def test_distinct_bwd_blocks_grads_match():
    """block_q_bwd/block_k_bwd different from the forward blocks must
    produce identical gradients (only tiling changes)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 1, 256, 2, 32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    base = loss(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True,
        )
    )
    tuned = loss(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            block_q_bwd=64, block_k_bwd=256, interpret=True,
        )
    )
    g1 = jax.grad(base, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(tuned, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 2, 64)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = _default_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_bf16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(jnp.float32),
        ref.astype(jnp.float32),
        atol=3e-2,
        rtol=3e-2,
    )


def test_jit_and_grad_under_jit():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 128, 1, 64)

    @jax.jit
    def step(q, k, v):
        def loss(q):
            return jnp.mean(
                flash_attention(q, k, v, causal=True, interpret=True) ** 2
            )

        return jax.value_and_grad(loss)(q)

    val, grad = step(q, k, v)
    assert jnp.isfinite(val)
    assert bool(jnp.all(jnp.isfinite(grad)))


def test_unequal_blocks_no_dropped_keys():
    # Regression: t=96 with block_q=128 (clamped to 96), block_k=64
    # must pad to lcm and visit every key block.
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 96, 2, 64)
    out = flash_attention(
        q, k, v, causal=False, block_q=128, block_k=64, interpret=True
    )
    ref = _default_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_oversized_explicit_blocks_clamp_to_power_of_two():
    """Regression: explicit block_k=1024 at t=520 used to clamp to 520,
    tripping the divisibility-chain guard for a call that worked before
    the guard existed. Oversized blocks now clamp to the largest power
    of two <= t and the call must succeed and match the reference."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 520, 2, 64)
    out = flash_attention(
        q, k, v, causal=True, block_q=512, block_k=1024, interpret=True
    )
    ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_oversized_block_with_tiny_sequence():
    """block_k=1024 at t=20 (default block_q=t): the oversized block
    must clamp to the padded length, not to a power of two that is
    coprime with the non-power-of-two default block_q."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 20, 2, 64)
    out = flash_attention(
        q, k, v, causal=True, block_k=1024, interpret=True
    )
    ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Sliding-window attention (Mistral-style band)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "t,window,bq,bk",
    [
        (256, 64, 128, 128),   # band narrower than a block
        (256, 100, 64, 64),    # band not a block multiple
        (256, 200, 128, 64),   # band wider than a block, unequal tiles
        (192, 64, 128, 64),    # padded sequence (192 -> 256) + window
        (128, 1, 64, 64),      # degenerate: each query sees only itself
    ],
)
def test_window_forward_matches_reference(t, window, bq, bk):
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), 2, t, 2, 64)
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True,
    )
    ref = _default_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_window_band_starts_beyond_first_executed_block():
    """Regression guard for the fully-masked-row hazard: with
    block_q=128 and window=32, the last rows of a q block have bands
    starting several kv blocks after the block-skip's earliest
    admitted block (which is chosen for the FIRST row). Fully-masked
    rows in executed blocks must contribute exp(0)=1 to nothing."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 1, 256, 2, 32)
    out = flash_attention(
        q, k, v, causal=True, window=32, block_q=128, block_k=32,
        interpret=True,
    )
    ref = _default_attention(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [48, 128])
def test_window_gradients_match_reference(window):
    q, k, v = _rand_qkv(jax.random.PRNGKey(12), 1, 192, 2, 32)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, window=window, block_q=64,
            block_k=64, interpret=True,
        )
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = _default_attention(q, k, v, causal=True, window=window)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_window_wider_than_sequence_is_plain_causal():
    q, k, v = _rand_qkv(jax.random.PRNGKey(13), 1, 128, 2, 64)
    wide = flash_attention(
        q, k, v, causal=True, window=4096, interpret=True
    )
    plain = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(wide, plain, atol=0, rtol=0)


def test_window_requires_causal():
    q, k, v = _rand_qkv(jax.random.PRNGKey(14), 1, 64, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(
            q, k, v, causal=False, window=16, interpret=True
        )


def test_window_with_lse_matches_and_grads():
    """return_lse path (ring-attention ingredient) with a window: lse
    must equal the reference band logsumexp and stay differentiable."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(15), 1, 128, 2, 32)

    o, lse = flash_attention(
        q, k, v, causal=True, window=48, block_q=64, block_k=64,
        interpret=True, return_lse=True,
    )
    # Reference lse over the band.
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / np.sqrt(d)
    pos = jnp.arange(q.shape[1])
    mask = (pos[:, None] >= pos[None, :]) & (
        (pos[:, None] - pos[None, :]) < 48
    )
    s = jnp.where(mask[None, None], s, -1e30)
    ref_lse = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        o, lse = flash_attention(
            q, k, v, causal=True, window=48, block_q=64, block_k=64,
            interpret=True, return_lse=True,
        )
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


def test_cfg_attn_blocks_pin_flows_to_kernel():
    """GPTConfig.attn_blocks (the autotune pin) must reach the flash
    kernel call and produce reference-equal output."""
    import dataclasses

    from dlrover_tpu.models import gpt

    cfg = dataclasses.replace(
        gpt.GPTConfig.gpt2(), use_flash_attention=True,
        attn_blocks=(64, 128, 64, 64),
    )
    attn = gpt.default_attention_for(cfg)
    assert attn.keywords["block_q"] == 64
    assert attn.keywords["block_k"] == 128
    assert attn.keywords["block_q_bwd"] == 64
    assert attn.keywords["block_k_bwd"] == 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(16), 1, 128, 2, 32)
    out = attn(q, k, v, interpret=True)
    ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# -- the row statistics' layout ------------------------------------------
# lse leaves ``_fwd``, and lse and delta enter the backward kernel, as
# [B, H, 1, T] rows along the lanes; the backward's tiles are
# key-major so the rows broadcast as they are read. Held to plain
# attention: o, lse and all three gradients, a cotangent on lse too.


def _masked_scores(q, k, causal, window=None, q_offset=0, scale=None):
    """Plain scaled scores [B, H, Tq, Tk], -1e30 where masked; q row i
    sits at key position ``q_offset + i``."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5 if scale is None else scale)
    qp = q_offset + jnp.arange(q.shape[1])[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= (qp - kp) < window
    return jnp.where(mask[None, None], s, -1e30)


def _dense_lse(*args, **kwargs):
    """Plain logsumexp of ``_masked_scores``, [B, H, Tq]."""
    return jax.nn.logsumexp(_masked_scores(*args, **kwargs), axis=-1)


def _loss_through_o_and_lse(fn):
    """A scalar with a cotangent on both outputs of ``fn -> (o, lse)``."""
    def loss(*args):
        o, lse = fn(*args)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
    return loss


LAYOUT_CASES = {
    # t, flash keywords
    "causal": (256, dict(causal=True, block_q=64, block_k=128)),
    "full": (256, dict(causal=False, block_q=128, block_k=64)),
    "window": (256, dict(causal=True, window=48, block_q=64, block_k=64)),
    "padded": (200, dict(causal=True, block_q=64, block_k=64)),
    "one_block": (200, dict(causal=True)),
    "bwd_blocks": (256, dict(
        causal=True, block_q=128, block_k=128, block_q_bwd=64,
        block_k_bwd=32,
    )),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_o_lse_and_gradients_match_plain_attention(case):
    t, kw = LAYOUT_CASES[case]
    causal, window = kw["causal"], kw.get("window")
    q, k, v = _rand_qkv(jax.random.PRNGKey(21), 2, t, 3, 32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, interpret=True, return_lse=True, **kw
        )

    def plain(q, k, v):
        return (
            _default_attention(q, k, v, causal=causal, window=window),
            _dense_lse(q, k, causal, window),
        )

    loss = _loss_through_o_and_lse
    o, lse = flash(q, k, v)
    want_o, want_lse = plain(q, k, v)
    assert lse.shape == (2, 3, t) and lse.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def _flash_pallas_calls(jaxpr):
    """{name: equation} of the flash ``pallas_call``s in the jaxpr."""
    from tests.test_remat_policies import _eqns

    return {
        str(eqn.params["name"]): eqn for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("return_lse", [False, True])
@pytest.mark.parametrize("rect", [False, True])
def test_rows_from_the_forward_kernel_to_the_backward_one(return_lse, rect):
    """The chip pads a buffer's minor dimension to 128 lanes: a
    [B, H, T, 1] statistic is 128 times its bytes in HBM. The backward
    kernel takes lse and delta as [B, H, 1, T] rows, whichever entry
    point and custom_vjp the call came through; the one column left
    is the forward kernel's own result, compacted to a row at once
    (PERF.md, PR 35, says why that one stays)."""
    from dlrover_tpu.ops.flash_attention import flash_attention_rect
    from tests.test_remat_policies import _eqns

    b, t, h, d = 2, 128, 3, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(22), b, t, h, d)
    fn = flash_attention_rect if rect else flash_attention

    def loss(q, k, v):
        out = fn(
            q, k, v, causal=True, block_q=64, block_k=64,
            interpret=True, return_lse=return_lse,
        )
        return sum(jnp.sum(x) for x in jax.tree.leaves(out))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = _flash_pallas_calls(jaxpr.jaxpr)
    assert sorted(calls) == ["flash_attention_bwd", "flash_attention_fwd"]
    bwd = calls["flash_attention_bwd"]
    shapes = [x.aval.shape for x in (*bwd.invars, *bwd.outvars)]
    assert not [s for s in shapes if s[-1] == 1], shapes
    assert len([s for s in shapes if s == (b, h, 1, t)]) == 2, shapes
    columns = [
        eqn.primitive.name for eqn in _eqns(jaxpr.jaxpr)
        for x in eqn.outvars if x.aval.shape == (b, h, t, 1)
    ]
    # The kernel's result and the slice that drops its unit lane.
    assert columns == ["pallas_call", "slice"], columns


def test_bwd_vmem_limit_charges_rows_not_lane_padded_columns():
    """Two row blocks of 1024 float32 (8 sublanes each, double
    buffered) are 128 KiB, not the 2 MiB two lane-padded columns
    were: the 8k backward still declares its need, by that much less."""
    from dlrover_tpu.ops.flash_attention import _bwd_vmem_limit

    assert _bwd_vmem_limit(1024, 64, 2, 1024, 1024) is None
    need = _bwd_vmem_limit(8192, 128, 2, 1024, 1024)
    dq = 8192 * 128 * (4 + 2 * 2)
    blocks = 2 * 2 * 3 * 1024 * 128 * 2 + 2 * 1024 * 128 * 4
    rows = 2 * 2 * 8 * 1024 * 4
    assert need == dq + blocks + rows + 2 * 1024 * 1024 * 4


@pytest.mark.parametrize(
    "t,window,carried", [(128, None, False), (256, None, True),
                         (128, 48, True)],
)
def test_one_kv_block_carries_nothing(t, window, carried):
    """A sequence of one kv block with no band: the block's softmax is
    the row's, so the forward kernel neither fills nor reads its
    running (max, sum, acc) scratch, and writes ``o`` and ``lse`` from
    the block's own values (GPT-2's T=1024 is one 1024-block: there
    the finalize is paid every grid step, so nothing amortises it).
    Two kv blocks, or a band that can skip the one, carry as before."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(23), 1, t, 2, 32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128,
        interpret=True,
    ))(q, k, v)
    (eqn,) = _flash_pallas_calls(jaxpr.jaxpr).values()
    kernel = eqn.params["jaxpr"]
    scratch = set(kernel.invars[-3:])  # m, l, acc
    assert all(len(s.aval.shape) == 2 for s in scratch)
    touched = [
        e.primitive.name for e in kernel.eqns
        if scratch & {x for x in e.invars if not hasattr(x, "val")}
    ]
    assert bool(touched) == carried, touched


# The backward kernel runs a block the mask crosses (the diagonal, the
# band's edge, the key padding) as sub-tiles, and only those that hold
# a live pair. ``_BWD_SPLIT`` sub-tiles a side: the shipped value and
# twice it, so that crossing blocks have 2 x 2 and 4 x 4.


def _bwd_area_events(split, monkeypatch, fn, *args):
    """``fn(*args)`` with the split pinned; its result and the
    ``flash.bwd_area`` events it fired."""
    from dlrover_tpu import obs

    monkeypatch.setattr(flash_module, "_BWD_SPLIT", split)
    tracer = obs.configure_tracer()
    try:
        out = fn(*args)
        events = [
            e for e in tracer.events() if e["name"] == "flash.bwd_area"
        ]
    finally:
        obs.disable_tracer()
    return out, events


SUBTILE_CASES = {
    # t, flash keywords
    "one_block": (64, dict(causal=True, block_q=64, block_k=64)),
    "several_blocks": (256, dict(causal=True, block_q=64, block_k=64)),
    "window_edge": (
        256, dict(causal=True, window=80, block_q=64, block_k=64)),
    # The last rows' bands start several kv blocks after the first one
    # the block-level skip admits for their q block.
    "window_band_beyond_first_block": (
        256, dict(causal=True, window=32, block_q=128, block_k=32)),
    "padded_520": (520, dict(causal=True)),
    # One block whose half is no multiple of 8: it runs whole, masked.
    "odd_block": (520, dict(causal=True, block_q=520, block_k=520)),
    "padded_in_blocks": (200, dict(causal=True, block_q=64, block_k=64)),
    "full_padded": (200, dict(causal=False, block_q=64, block_k=64)),
    "bwd_blocks": (256, dict(
        causal=True, block_q=64, block_k=64, block_q_bwd=128,
        block_k_bwd=64,
    )),
}


@pytest.mark.parametrize("split", [2, 4])
@pytest.mark.parametrize("case", sorted(SUBTILE_CASES))
def test_gradients_over_live_subtiles_match_plain_attention(
    case, split, monkeypatch
):
    t, kw = SUBTILE_CASES[case]
    causal, window = kw["causal"], kw.get("window")
    q, k, v = _rand_qkv(jax.random.PRNGKey(39), 1, t, 2, 32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, interpret=True, return_lse=True, **kw
        )

    def plain(q, k, v):
        return (
            _default_attention(q, k, v, causal=causal, window=window),
            _dense_lse(q, k, causal, window),
        )

    loss = _loss_through_o_and_lse  # a cotangent on lse too
    got, (ev,) = _bwd_area_events(
        split, monkeypatch, jax.grad(loss(flash), argnums=(0, 1, 2)),
        q, k, v,
    )
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)
    whole = math.gcd(ev["block_q"], ev["block_k"])
    assert ev["sub"] == (whole if case == "odd_block" else whole // split)
    assert ev["required"] <= ev["run"] <= ev["visited"]
    if causal and case != "odd_block":
        assert ev["run"] < ev["visited"]  # a diagonal block's far corner


def _element_mask(tq, tk, causal, window, seq_len, q_offset, pad=True):
    """The kernel's element mask over the padded lengths, by brute
    force: [tq, tk] bools."""
    rows = q_offset + np.arange(tq)[:, None]
    keys = np.arange(tk)[None, :]
    mask = np.ones((tq, tk), bool)
    if pad:
        mask &= keys < seq_len
    if causal:
        mask &= rows >= keys
    if window is not None:
        mask &= (rows - keys) < window
    return mask


AREA_SWEEP = [
    # tq, tk, block_q, block_k, sub, causal, window, seq_len, q_offset
    (64, 64, 64, 64, 32, True, None, 64, 0),
    (64, 64, 64, 64, 16, True, None, 64, 0),
    (256, 256, 64, 64, 16, True, None, 256, 0),
    (256, 256, 64, 64, 32, True, 80, 256, 0),
    (256, 256, 64, 64, 16, True, 48, 256, 0),
    (256, 256, 128, 32, 16, True, 32, 256, 0),
    (256, 256, 64, 128, 32, True, 100, 250, 0),
    (256, 256, 64, 64, 16, True, None, 200, 0),
    (256, 256, 64, 64, 32, False, None, 200, 0),
    (256, 256, 64, 64, 64, True, 70, 231, 0),
    (256, 512, 128, 128, 32, True, None, 500, 0),  # lcm padding: a dead block
    (64, 256, 64, 64, 16, True, None, 256, 192),   # rect: the last rows
    (64, 256, 32, 64, 16, True, 90, 250, 75),
    (128, 256, 64, 64, 32, True, 17, 256, 100),
    (32, 256, 32, 128, 8, True, None, 243, 211),
    (64, 128, 64, 64, 16, False, None, 128, 0),    # nothing dead
    (1024, 1024, 1024, 1024, 512, True, None, 1024, 0),
    (1024, 1024, 1024, 1024, 256, True, None, 1024, 0),
]


@pytest.mark.parametrize("args", AREA_SWEEP, ids=lambda a: "-".join(map(str, a)))
def test_live_subtiles_are_those_the_element_mask_finds(args):
    tq, tk, bq, bk, sub, causal, window, seq_len, q_offset = args
    mask = _element_mask(tq, tk, causal, window, seq_len, q_offset)
    # The block-level skip looks at the diagonal and the band alone.
    skip = _element_mask(tq, tk, causal, window, seq_len, q_offset, pad=False)
    blocks = {
        (iq, jk): tiles for iq, jk, tiles in flash_module._bwd_blocks(*args)
    }
    dead = 0
    for iq in range(tq // bq):
        for jk in range(tk // bk):
            rows = slice(iq * bq, (iq + 1) * bq)
            keys = slice(jk * bk, (jk + 1) * bk)
            if not skip[rows, keys].any():
                assert (iq, jk) not in blocks
                continue
            tiles = blocks[iq, jk]
            if mask[rows, keys].all():
                assert tiles is None  # whole and unmasked
                continue
            live = {
                (b, a)
                for b in range(bq // sub) for a in range(bk // sub)
                if mask[rows, keys][
                    b * sub:(b + 1) * sub, a * sub:(a + 1) * sub
                ].any()
            }
            assert set(tiles) == live and len(tiles) == len(live)
            dead += (bq // sub) * (bk // sub) - len(live)
    area = flash_module.bwd_area(*args)
    assert area["visited"] == len(blocks) * bq * bk
    assert area["required"] == int(mask.sum())
    assert area["required"] <= area["run"] <= area["visited"]
    assert area["run"] == area["visited"] - dead * sub * sub
    assert (area["run"] == area["visited"]) == (dead == 0)


@pytest.mark.parametrize("name,args,want", [
    # a head of the benchmark's cells, at the chip's blocks
    ("gpt2", (1024, 1024, 1024, 1024, 512, True, None, 1024),
     (1048576, 786432, 524800)),
    ("gpt2_sub256", (1024, 1024, 1024, 1024, 256, True, None, 1024),
     (1048576, 655360, 524800)),
    ("mistral", (8192, 8192, 1024, 1024, 512, True, 4096, 8192),
     (30 << 20, 27 << 20, 25167872)),
    ("olmoe_granite", (4096, 4096, 1024, 1024, 512, True, None, 4096),
     (10 << 20, 9 << 20, 8390656)),
])
def test_bwd_area_of_the_cells(name, args, want):
    area = flash_module.bwd_area(*args)
    assert (area["visited"], area["run"], area["required"]) == want


def test_bwd_sub_keeps_whole_lanes_on_the_chip():
    sub = flash_module._bwd_sub
    split = flash_module._BWD_SPLIT
    assert sub(1024, 1024, False) == 1024 // split
    assert sub(512, 1024, False) == 512 // split
    assert sub(520, 520, False) == 520  # 260 is no whole number of lanes
    assert sub(128, 128, False) == 128
    assert sub(64, 64, True) == 64 // split  # interpreted: multiples of 8
    assert sub(520, 520, True) == 520


@pytest.mark.parametrize("split", [1, 2, 4, 8])
def test_backward_body_holds_two_copies_of_the_arithmetic(split, monkeypatch):
    """What refused PR 32: a copy of the five products for every live
    sub-tile, traced and lowered at every start. The loops over the
    sub-tiles are rolled, so the kernel's body holds the products
    twice (the whole unmasked block, one masked sub-tile) however
    many sub-tiles a side, as it did when the second copy was the
    whole masked block; and the forward kernel's body is the same
    whatever the split."""
    from tests.test_remat_policies import _eqns

    monkeypatch.setattr(flash_module, "_BWD_SPLIT", split)
    q, k, v = _rand_qkv(jax.random.PRNGKey(24), 1, 256, 2, 32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=96, block_q=128, block_k=128,
            interpret=True,
        ))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = _flash_pallas_calls(jaxpr.jaxpr)

    def count(name, primitive):
        return len([
            e for e in _eqns(calls[name].params["jaxpr"])
            if e.primitive.name == primitive
        ])

    assert count("flash_attention_bwd", "dot_general") == 10
    assert count("flash_attention_bwd", "exp") == 2
    # Key sub-tiles: a fixed count, so a scan; the live query
    # sub-tiles of each: bounds computed in the kernel, so a while.
    rolled = 1 if split > 1 else 0
    assert count("flash_attention_bwd", "scan") == rolled
    assert count("flash_attention_bwd", "while") == rolled
    assert count("flash_attention_fwd", "dot_general") == 4
    assert count("flash_attention_fwd", "while") == 0
