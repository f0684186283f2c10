"""Fused memory-efficient softmax cross-entropy over a tied embedding.

Parity with atorch's fused cross-entropy
(atorch/modules/transformer/cross_entropy.py:338LoC, a CUDA kernel
that avoids materializing log-softmax over the vocab): here the fusion
is chunking + custom_vjp. The naive path materializes TWO [B*T, V]
float32 tensors (logits and log-softmax) — 6.6 GB at batch 16, seq
1024, vocab 50k — and routes the backward matmuls through float32
cotangents (quarter-rate on the MXU). This implementation:

* never holds more than one [chunk, V] logits block;
* forms the gradients in the forward pass while a chunk's logits are
  there, so the logits are computed once: three [rows, V] x E
  products a step (logits, ``dx``, the table's gradient), which is
  what the mathematics needs. The backward rule only scales. A call
  nothing differentiates is the lean chunked loss, one product;
* stores ``dx`` (the activations' dtype) and the table's float32
  gradient between fwd and bwd, not ``x``, the table or any logits;
* emits bf16 cotangents into the two gradient matmuls so they run at
  full MXU rate;
* under a mesh runs once per device on that device's own rows
  (``_on_own_rows``), so the logits never cross a link: the table is
  gathered once a step, its float32 gradient summed over the batch
  axes once a step;
* takes an optional float32 weight a row (``weights``): the loss is
  then ``sum_i w_i nll_i`` in place of the mean, both gradients are
  formed with ``w_i`` where the mean has ``1/N``, in the same single
  pass over the logits, and the rows' losses go back as the weights'
  gradient (models/ouro.py weighs four passes' rows by a learned exit
  distribution). With no weights the traced program is what it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu import obs
from dlrover_tpu.parallel.mesh import batch_axes, per_device


# XLA fuses the cotangent's elementwise chain (exp, one-hot, scale,
# cast) into both gradient products and computes it again for every
# tile of their outputs across the width: cheaper than one pass over
# HBM for a narrow table, dearer for a wide one. From this width the
# cotangent is formed once a chunk and both products read it. Same
# values either way. v5e, value_and_grad of the head alone, ms a
# call, fused / once: width 768 (18,432 rows x 50,304) 32.4 / 38.8;
# 16,384 rows x 50,304 at width 1280 42.9 / 48.2, 1792 68.7 / 68.4,
# 2048 78.4 / 74.4; width 4096 (8,192 rows x 32,000) 49.5 / 42.1
# (chip runs, PR 29).
_COTANGENT_ONCE_FROM = 2048


def _chunks(x, targets, num_chunks):
    n = x.shape[0]
    return (
        x.reshape(num_chunks, n // num_chunks, -1),
        targets.reshape(num_chunks, -1),
    )


def _loss_terms(x_c, t_c, wte):
    """A chunk's float32 logits, their logsumexp and the gold logit."""
    logits = jnp.einsum(
        "ce,ve->cv", x_c, wte, preferred_element_type=jnp.float32
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
    return logits, lse, gold


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_cross_entropy(x, wte, targets, num_chunks: int = 8, weights=None):
    """Mean token cross-entropy of ``x @ wte^T`` against targets, or
    with ``weights`` the weighted sum ``sum_i weights[i] * nll[i]``.

    x: [N, E] (activations, bf16 ok); wte: [V, E] tied embedding;
    targets: [N] int; weights: None or [N] float32, differentiable.
    N must be divisible by num_chunks (pad or pick a divisor; model
    code uses B*T which is a power of two).

    This body is the call nothing differentiates (evaluation, a
    reference check): the loss alone, one product a chunk. Under
    differentiation JAX takes ``_fwd`` in its place.
    """

    def rows_fn(x, targets, wte):
        def chunk(args):
            _, lse, gold = _loss_terms(*args, wte)
            return lse - gold

        return jax.lax.map(chunk, _chunks(x, targets, num_chunks)).reshape(-1)

    nll = _on_own_rows(
        rows_fn, (x, targets), (wte,), num_chunks, announce=True
    )
    return _reduced(nll, weights)


def _reduced(nll, weights):
    """The rows' losses to one: their mean (every device holds the same
    number of rows, so over all rows of all devices), or with weights
    their weighted sum."""
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(weights.astype(jnp.float32) * nll)


def _on_own_rows(rows_fn, by_row, whole, num_chunks, summed=(),
                 announce=False):
    """``rows_fn(*by_row, *whole)`` on each device's own rows.

    Under an ambient mesh whose batch axes divide the rows (and leave
    every device a multiple of ``num_chunks``) the chunked loop runs
    once per device (parallel.mesh ``per_device``): the
    ``by_row`` operands stay where the batch put them and ``whole``
    (the table) enters whole, gathered once a step. Left to XLA the
    loop is partitioned along the table's ``embed`` dimension, which
    ``fsdp`` splits: every chip forms partial logits for ALL rows of
    a chunk and the ``[chunk, V]`` float32 logits are all-reduced,
    524 MB a chunk at Mistral-7B's widths on ``fsdp=4``. The other
    mesh axes (``tensor`` carries the vocabulary, ``seq`` the
    sequence) stay XLA's inside the call. One device, no mesh, rows
    no batch axis divides, the inside of a ``shard_map``: the plain
    call. ``announce`` emits the event that says it engaged, once a
    trace."""
    axes, local = batch_axes(by_row[0].shape[0])
    if not axes or local % num_chunks:
        return rows_fn(*by_row, *whole)
    if announce:
        obs.event(
            "head.per_device", axes=list(axes), rows_per_device=local,
            chunks=num_chunks,
        )
    return per_device(
        rows_fn, *by_row, *whole,
        split=(True,) * len(by_row) + (False,) * len(whole),
        summed=summed, manual_all=False,
    )


def _fwd(x, wte, targets, num_chunks, weights=None):
    """The loss and, while each chunk's logits are there, its share of
    ``dx`` and of the table's gradient for an upstream cotangent of 1:
    the backward needs no logits, so it does not form them again. A
    row's share carries its weight, ``1/N`` of the mean with none."""
    inv_rows = 1.0 / x.shape[0]  # the mean is over every device's rows
    by_row = (x, targets)
    if weights is not None:
        by_row += (weights.astype(jnp.float32),)

    def rows_fn(x, targets, *rest):
        *row_weights, wte = rest

        def chunk(dwte, args):
            x_c, t_c, *w_c = args
            logits, lse, gold = _loss_terms(x_c, t_c, wte)
            p = jnp.exp(logits - lse[:, None])
            dlogits = p - jax.nn.one_hot(t_c, wte.shape[0], dtype=p.dtype)
            scale = w_c[0][:, None] if w_c else inv_rows
            dlogits = (dlogits * scale).astype(x.dtype)  # bf16 cotangent
            if wte.shape[1] >= _COTANGENT_ONCE_FROM:
                dlogits = jax.lax.optimization_barrier(dlogits)
            dx_c = jnp.einsum("cv,ve->ce", dlogits, wte).astype(x.dtype)
            dwte = dwte + jnp.einsum(
                "cv,ce->ve", dlogits, x_c,
                preferred_element_type=jnp.float32,
            )
            return dwte, (lse - gold, dx_c)

        dwte, (nll, dx) = jax.lax.scan(
            chunk, jnp.zeros(wte.shape, jnp.float32),
            _chunks(x, targets, num_chunks) + tuple(
                w.reshape(num_chunks, -1) for w in row_weights
            ),
        )
        return nll.reshape(-1), dx.reshape(x.shape), dwte

    # The table's gradient: float32 over the chunks, summed over the
    # devices in float32 (``summed``), cast once, in ``_bwd``.
    nll, dx, dwte = _on_own_rows(
        rows_fn, by_row, (wte,), num_chunks,
        summed=(False, False, True), announce=True,
    )
    obs.event("head.grads_in_forward", rows=x.shape[0], chunks=num_chunks)
    # The empty array carries the table's dtype to ``_bwd``.
    res = (dx, dwte, jnp.zeros((0,), wte.dtype))
    if weights is not None:
        obs.event("head.weighted_rows", rows=x.shape[0], chunks=num_chunks)
        # The rows' losses are the weights' gradient; the empty array
        # carries their dtype.
        res += (nll, jnp.zeros((0,), weights.dtype))
    return _reduced(nll, weights), res


def _bwd(num_chunks, res, g):
    dx, dwte, like_wte, *weighted = res
    grads = (g * dx).astype(dx.dtype), (g * dwte).astype(like_wte.dtype), None
    if not weighted:
        return grads + (None,)
    nll, like_weights = weighted
    return grads + ((g * nll).astype(like_weights.dtype),)


fused_cross_entropy.defvjp(_fwd, _bwd)
