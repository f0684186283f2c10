"""Rectangular flash attention: Tq != Tk, causal offsets, gradients.

The square kernel generalized with a static ``q_offset`` (global
position of q row 0 in key coordinates) and per-side padding —
chunked prefill, prefix-LM suffix rows, and cross-attention at exact
cost (ops/flash_attention.py flash_attention_rect).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_rect,
)
from tests.test_flash_attention import (
    _loss_through_o_and_lse,
    _masked_scores,
)


def _qkv(key, tq, tk, b=2, h=3, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, tq, h, d), jnp.float32),
        jax.random.normal(kk, (b, tk, h, d), jnp.float32),
        jax.random.normal(kv, (b, tk, h, d), jnp.float32),
    )


def _dense(q, k, v, causal, q_offset):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (d**0.5)
    if causal:
        qp = q_offset + jnp.arange(tq)[:, None]
        kp = jnp.arange(tk)[None, :]
        s = jnp.where((kp <= qp)[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", w, v.astype(jnp.float32)
    ).astype(q.dtype)


@pytest.mark.parametrize(
    "tq,tk,causal,offset",
    [
        (24, 64, False, 0),     # cross-attention, short queries
        (64, 24, False, 0),     # cross-attention, long queries
        (24, 64, True, None),   # chunked-prefill convention (tail)
        (24, 64, True, 8),      # explicit mid offset
        (40, 40, True, 0),      # square via the rect path
        (17, 51, True, None),   # odd sizes -> both sides pad
        (64, 64, True, None),   # offset defaults to 0 at equal sizes
    ],
)
def test_rect_matches_dense(tq, tk, causal, offset):
    q, k, v = _qkv(jax.random.PRNGKey(0), tq, tk)
    got = flash_attention_rect(
        q, k, v, causal=causal, q_offset=offset, interpret=True
    )
    eff = (tk - tq) if offset is None else offset
    want = _dense(q, k, v, causal, eff)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4
    )


def test_rect_grads_match_dense():
    """dq AND dk AND dv through the rectangular fused backward."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 24, 56, b=1, h=2, d=8)

    def f_kernel(q, k, v):
        return jnp.sum(
            flash_attention_rect(q, k, v, causal=True, interpret=True)
            ** 2
        )

    def f_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, True, 56 - 24) ** 2)

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3
        )


def test_rect_square_equals_flash_attention():
    """Tq == Tk with offset 0 reproduces the square kernel exactly
    (same blocks, same masks)."""
    q, k, v = _qkv(jax.random.PRNGKey(2), 64, 64)
    a = flash_attention_rect(
        q, k, v, causal=True, q_offset=0, interpret=True
    )
    b = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-6
    )


def test_rect_lse_matches_dense():
    q, k, v = _qkv(jax.random.PRNGKey(3), 16, 48)
    _, lse = flash_attention_rect(
        q, k, v, causal=True, interpret=True, return_lse=True
    )
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (16**0.5)
    qp = (48 - 16) + jnp.arange(16)[:, None]
    kp = jnp.arange(48)[None, :]
    s = jnp.where((kp <= qp)[None, None], s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want), atol=2e-5, rtol=1e-5
    )


def test_rect_rejects_negative_causal_offset():
    q, k, v = _qkv(jax.random.PRNGKey(4), 64, 24)
    with pytest.raises(ValueError, match="q_offset"):
        flash_attention_rect(
            q, k, v, causal=True, interpret=True
        )  # default offset 24-64 < 0


def test_chunked_prefill_equals_full_causal():
    """Processing queries in chunks against the full key set (each
    chunk at its own offset) reproduces the one-shot causal result —
    the chunked-prefill contract."""
    t = 96
    q, k, v = _qkv(jax.random.PRNGKey(5), t, t)
    full = flash_attention(q, k, v, causal=True, interpret=True)
    chunks = []
    for start in (0, 32, 64):
        chunks.append(
            flash_attention_rect(
                q[:, start:start + 32], k[:, :start + 32],
                v[:, :start + 32], causal=True, q_offset=start,
                interpret=True,
            )
        )
    got = jnp.concatenate(chunks, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full), atol=2e-5, rtol=1e-4
    )


def test_rect_window_matches_dense():
    """Sliding band + q_offset: the rect kernel's band compares run
    in key coordinates (Mistral chunked prefill)."""
    q, k, v = _qkv(jax.random.PRNGKey(6), 16, 48)
    got = flash_attention_rect(
        q, k, v, causal=True, window=8, interpret=True
    )
    off = 48 - 16
    b, tq, h, d = q.shape
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (d**0.5)
    qp = off + jnp.arange(tq)[:, None]
    kp = jnp.arange(48)[None, :]
    mask = (kp <= qp) & ((qp - kp) < 8)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    want = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
        v.astype(jnp.float32),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4
    )


@pytest.mark.parametrize(
    "tq,tk,offset,window",
    [
        (24, 64, None, None),   # the tail of the keys, both sides pad
        (32, 96, 16, None),     # a chunk in the middle
        (17, 51, None, None),   # odd sizes
        (32, 96, None, 24),     # a band across the offset
    ],
)
def test_rect_o_lse_and_gradients_with_a_cotangent_on_lse(
    tq, tk, offset, window
):
    """The rectangular call's row statistics ([B, H, 1, Tq] between
    the kernels, q rows at ``q_offset``): o, lse and dq, dk, dv
    against plain attention with a cotangent on both outputs."""
    q, k, v = _qkv(jax.random.PRNGKey(5), tq, tk, b=2, h=2, d=16)
    eff = (tk - tq) if offset is None else offset

    def plain(q, k, v):
        s = _masked_scores(q, k, True, window, q_offset=eff)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return o, jax.scipy.special.logsumexp(s, axis=-1)

    def flash(q, k, v):
        return flash_attention_rect(
            q, k, v, causal=True, q_offset=offset, window=window,
            block_q=16, block_k=32, interpret=True, return_lse=True,
        )

    loss = _loss_through_o_and_lse
    o, lse = flash(q, k, v)
    want_o, want_lse = plain(q, k, v)
    assert lse.shape == (2, 2, tq)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=1e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3)


@pytest.mark.parametrize("split", [2, 4])
@pytest.mark.parametrize(
    "tq,tk,offset,window,bq,bk",
    [
        (64, 256, None, None, 64, 64),   # the last rows: one diagonal block
        (64, 250, 75, 90, 32, 64),       # offset off every tile's edge
        (128, 256, 100, 17, 64, 64),     # a band narrower than a sub-tile
        (32, 243, 211, None, 32, 128),   # padded keys under the diagonal
    ],
)
def test_rect_gradients_over_live_subtiles(
    tq, tk, offset, window, bq, bk, split, monkeypatch
):
    """The backward's sub-tiles of a crossing block with the q rows at
    ``q_offset``: dq, dk, dv against plain attention with a cotangent
    on lse too, crossing blocks split 2 x 2 and 4 x 4 (and unequally
    where the blocks are unequal)."""
    from tests.test_flash_attention import _bwd_area_events

    q, k, v = _qkv(jax.random.PRNGKey(39), tq, tk, b=1, h=2, d=16)
    eff = (tk - tq) if offset is None else offset

    def plain(q, k, v):
        s = _masked_scores(q, k, True, window, q_offset=eff)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return o, jax.scipy.special.logsumexp(s, axis=-1)

    def flash(q, k, v):
        return flash_attention_rect(
            q, k, v, causal=True, q_offset=offset, window=window,
            block_q=bq, block_k=bk, interpret=True, return_lse=True,
        )

    loss = _loss_through_o_and_lse
    got, (ev,) = _bwd_area_events(
        split, monkeypatch, jax.grad(loss(flash), argnums=(0, 1, 2)),
        q, k, v,
    )
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3)
    assert ev["sub"] == min(bq, bk) // split
    assert ev["required"] <= ev["run"] < ev["visited"]
