"""What both plain references share: dense causal attention computed
one head at a time, and the mean token cross-entropy. float32,
``jax.default_matmul_precision("highest")`` (on a TPU an f32 matmul is
otherwise computed in bf16 passes), no kernel, no cache, no remat.

Each layer is its own jitted call on that layer's parameters cast to
float32 there, so that at the published widths the reference never
holds more than one layer in float32 beside the system's state. The
loop over heads is a ``lax.map``, a sequential loop by construction:
at T=8192 each head's [T, T] float32 scores are 256 MiB, and an
unrolled loop would let XLA hold all of them at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def attention(q, k, v, window=None):
    """q [B, T, H, D]; k, v [B, T, Hkv, D] -> [B, T, H, D]. Query head
    h reads key-value head h // (H / Hkv). Query i sees keys j with
    j <= i and, with a window, i - j < window."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    pos = jnp.arange(t)
    mask = pos[:, None] >= pos[None, :]
    if window is not None:
        mask &= (pos[:, None] - pos[None, :]) < window

    def one_head(i):
        qi = q[:, :, i, :]
        ki = k[:, :, i // group, :]
        vi = v[:, :, i // group, :]
        s = jnp.einsum("bqd,bkd->bqk", qi, ki) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vi)

    out = jax.lax.map(one_head, jnp.arange(h))  # [H, B, T, D]
    return jnp.transpose(out, (1, 2, 0, 3))


def mean_cross_entropy(x, table, targets):
    """x [B, T, E] final hidden, table [V, E], targets [B, T]."""
    logits = jnp.einsum("bte,ve->btv", x, table.astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def mean_over_rows(head_fn, x, targets, *weights):
    """``head_fn(x_row, targets_row, *weights)`` (a mean over one sequence's
    tokens) for each sequence in turn, averaged: every row has the
    same number of tokens, and one row's [T, V] float32 logits are all
    that is held at a time (1 GiB at T=8192, V=32000)."""
    step = jax.jit(head_fn)
    rows = x.shape[0]
    return sum(
        step(x[b: b + 1], targets[b: b + 1], *weights) for b in range(rows)
    ) / rows


def run_layers(x, blocks, layer_fn, n_layer):
    """``layer_fn(x, layer_params)`` for each slice of the stacked
    ``blocks`` in turn, one jitted call a layer."""
    step = jax.jit(layer_fn)
    for i in range(n_layer):
        x = step(x, jax.tree.map(lambda a: a[i], blocks))
    return x
