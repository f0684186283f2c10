"""Fused memory-efficient softmax cross-entropy over a tied embedding.

Parity with atorch's fused cross-entropy
(atorch/modules/transformer/cross_entropy.py:338LoC, a CUDA kernel
that avoids materializing log-softmax over the vocab): here the fusion
is chunking + custom_vjp. The naive path materializes TWO [B*T, V]
float32 tensors (logits and log-softmax) — 6.6 GB at batch 16, seq
1024, vocab 50k — and routes the backward matmuls through float32
cotangents (quarter-rate on the MXU). This implementation:

* never holds more than one [chunk, V] logits block (forward and
  backward recompute per chunk inside ``lax.map``);
* stores only the per-token logsumexp (f32 [N]) between fwd and bwd;
* emits bf16 cotangents into the unembedding matmuls so the backward
  runs at full MXU rate;
* under a mesh runs once per device on that device's own rows
  (``_on_own_rows``), so the logits never cross a link: the table is
  gathered once a pass, its float32 gradient summed over the batch
  axes once a step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu import obs
from dlrover_tpu.ops.flash_attention import batch_axes, per_device


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_cross_entropy(
    x, wte, targets, num_chunks: int = 8, save_logits: bool = False
):
    """Mean token cross-entropy of ``x @ wte^T`` against targets.

    x: [N, E] (activations, bf16 ok); wte: [V, E] tied embedding;
    targets: [N] int. N must be divisible by num_chunks (pad or pick a
    divisor; model code uses B*T which is a power of two).

    ``save_logits=True`` stashes the forward logits in x.dtype (bf16:
    2 bytes/entry, 1.6 GB at batch 16 x 1024 x 50k vocab) so the
    backward skips the [N,V] recompute matmul — ~V*E MACs/token of
    work MFU accounting never credits. Numerics caveat: with bf16
    activations the saved logits are rounded to bf16 before the
    backward ``exp``, so per-element softmax probabilities (and hence
    dlogits) carry a few-percent relative error versus the f32
    recompute path — zero-mean rounding noise on top of the bf16
    cotangent cast both paths share. Use it when HBM has room and
    bf16-grade gradients are acceptable (the GPT-2 bench regime);
    leave it off at Llama-7B scale where the recompute is the right
    trade, or when gradient bit-accuracy matters.
    """
    loss, _ = _fwd(x, wte, targets, num_chunks, save_logits)
    return loss


def _on_own_rows(rows_fn, by_row, whole, num_chunks, summed=(),
                 announce=False):
    """``rows_fn(*by_row, *whole)`` on each device's own rows.

    Under an ambient mesh whose batch axes divide the rows (and leave
    every device a multiple of ``num_chunks``) the chunked loop runs
    once per device (ops.flash_attention ``per_device``): the
    ``by_row`` operands stay where the batch put them and ``whole``
    (the table) enters whole, gathered once a pass. Left to XLA the
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


def _fwd(x, wte, targets, num_chunks, save_logits):
    def rows_fn(x, targets, wte):
        n = x.shape[0]
        xc = x.reshape(num_chunks, n // num_chunks, -1)
        tc = targets.reshape(num_chunks, -1)

        def chunk(args):
            x_c, t_c = args
            logits = jnp.einsum(
                "ce,ve->cv", x_c, wte, preferred_element_type=jnp.float32
            )
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
            kept = logits if save_logits else logits[:, :0]
            return lse, gold, kept.astype(x.dtype)

        lse, gold, saved = jax.lax.map(chunk, (xc, tc))
        saved = saved.reshape(n, saved.shape[-1])
        return lse.reshape(n), gold.reshape(n), saved

    lse, gold, saved = _on_own_rows(
        rows_fn, (x, targets), (wte,), num_chunks, announce=True
    )
    # Every device holds the same number of rows: the mean over all
    # rows of all devices.
    loss = jnp.mean(lse - gold)
    return loss, (x, wte, targets, lse, saved)


def _bwd(num_chunks, save_logits, res, g):
    x, wte, targets, lse, saved = res
    scale = g / x.shape[0]  # the mean is over every device's rows

    def rows_fn(x, targets, lse, saved, wte, scale):
        n = x.shape[0]
        c = n // num_chunks
        xc = x.reshape(num_chunks, c, -1)
        tc = targets.reshape(num_chunks, c)
        lc = lse.reshape(num_chunks, c)
        sc = saved.reshape(num_chunks, c, saved.shape[-1])

        def chunk_grads(carry, args):
            x_c, t_c, lse_c, saved_c = args
            if save_logits:
                logits = saved_c.astype(jnp.float32)
            else:
                logits = jnp.einsum(
                    "ce,ve->cv", x_c, wte,
                    preferred_element_type=jnp.float32,
                )
            p = jnp.exp(logits - lse_c[:, None])
            dlogits = p - jax.nn.one_hot(t_c, wte.shape[0], dtype=p.dtype)
            dlogits = (dlogits * scale).astype(x.dtype)  # bf16 cotangent
            dx_c = jnp.einsum("cv,ve->ce", dlogits, wte)
            dwte = carry + jnp.einsum(
                "cv,ce->ve", dlogits, x_c,
                preferred_element_type=jnp.float32,
            )
            return dwte, dx_c

        dwte0 = jnp.zeros(wte.shape, jnp.float32)
        dwte, dxc = jax.lax.scan(chunk_grads, dwte0, (xc, tc, lc, sc))
        return dxc.reshape(x.shape), dwte

    # The table's gradient: float32 over the chunks, summed over the
    # devices in float32 (``summed``), cast once.
    dx, dwte = _on_own_rows(
        rows_fn, (x, targets, lse, saved), (wte, scale), num_chunks,
        summed=(False, True),
    )
    return dx, dwte.astype(wte.dtype), None


fused_cross_entropy.defvjp(
    lambda x, wte, t, nc, sl: _fwd(x, wte, t, nc, sl), _bwd
)
