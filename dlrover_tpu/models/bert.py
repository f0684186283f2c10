"""Bidirectional encoder (BERT family) on the GPT backbone.

The reference accelerates HF BERT by swapping its attention for fused
kernels (module_replace: /root/reference/atorch/atorch/auto/opt_lib/
module_replace_optimization.py; FlashMHA mappings
atorch/modules/transformer/layers.py) and training it through
auto_accelerate. Here the encoder IS models/gpt.py's backbone with
``causal=False`` — identical learned positions, pre-LN blocks, GELU
MLP, flash kernels, sharding rules and remat policies
all apply unchanged — plus the two training surfaces BERT adds:

* the masked-language-model objective (:func:`mask_tokens` +
  :func:`mlm_loss_fn`), 80/10/10 corruption;
* a sequence-classification head over mean-pooled hiddens
  (:func:`init_classifier_params` + :func:`classifier_loss_fn`), the
  fine-tune path.

Everything the strategy engine knows about GPT (module profiles, TP
plans, pipe splits) transfers, since the parameters and jaxpr are the
same shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models import gpt

Params = Any


def bert_base(**overrides) -> gpt.GPTConfig:
    """BERT-base shape (L12 H12 E768, 30522 WordPiece vocab) as a
    non-causal GPTConfig."""
    cfg = gpt.GPTConfig(
        vocab_size=30522,
        block_size=512,
        n_layer=12,
        n_head=12,
        n_embd=768,
        causal=False,
    )
    return dataclasses.replace(cfg, **overrides)


def bert_large(**overrides) -> gpt.GPTConfig:
    cfg = gpt.GPTConfig(
        vocab_size=30522,
        block_size=512,
        n_layer=24,
        n_head=16,
        n_embd=1024,
        causal=False,
    )
    return dataclasses.replace(cfg, **overrides)


def tiny(**overrides) -> gpt.GPTConfig:
    """Test-size encoder."""
    cfg = gpt.GPTConfig(
        vocab_size=256,
        block_size=64,
        n_layer=2,
        n_head=4,
        n_embd=64,
        causal=False,
        dtype=jnp.float32,
        remat=False,
    )
    return dataclasses.replace(cfg, **overrides)


# Parameter init/axes are the backbone's own.
init_params = gpt.init_params
param_logical_axes = gpt.param_logical_axes


def mask_tokens(
    key: jax.Array,
    tokens: jax.Array,
    vocab_size: int,
    mask_id: int,
    mask_rate: float = 0.15,
) -> tuple:
    """BERT corruption: select ``mask_rate`` of positions; replace 80%
    with [MASK], 10% with a random token, keep 10%. Returns
    (corrupted [B,T], labels [B,T] = original tokens, weights [B,T]
    f32 1.0 at selected positions). Fully traceable — usable inside
    jit / the input pipeline."""
    k_sel, k_op, k_rand = jax.random.split(key, 3)
    sel = jax.random.uniform(k_sel, tokens.shape) < mask_rate
    op = jax.random.uniform(k_op, tokens.shape)
    rand_tok = jax.random.randint(k_rand, tokens.shape, 0, vocab_size)
    corrupted = jnp.where(
        sel & (op < 0.8),
        mask_id,
        jnp.where(sel & (op >= 0.9), rand_tok, tokens),
    )
    return corrupted, tokens, sel.astype(jnp.float32)


def mlm_loss_fn(
    params: Params,
    tokens: jax.Array,
    labels: jax.Array,
    weights: jax.Array,
    cfg: gpt.GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """Mean cross-entropy over the selected (weight>0) positions,
    logits via the tied embedding head."""
    logits = gpt.forward(params, tokens, cfg, attn_fn)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return -jnp.sum(ll * weights) / denom


def init_classifier_params(
    key: jax.Array, cfg: gpt.GPTConfig, n_classes: int
) -> Params:
    """Backbone params plus a mean-pool classification head."""
    k_body, k_head = jax.random.split(key)
    params = gpt.init_params(k_body, cfg)
    params["cls_w"] = (
        jax.random.normal(k_head, (cfg.n_embd, n_classes)) * 0.02
    )
    params["cls_b"] = jnp.zeros((n_classes,))
    return params


def classifier_logits(
    params: Params,
    tokens: jax.Array,
    cfg: gpt.GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """[B, T] -> [B, n_classes] via mean-pooled final hiddens (the
    pooler; mean beats CLS-token pooling without a dedicated token)."""
    x = gpt.backbone(params, tokens, cfg, attn_fn)  # [B, T, E]
    pooled = jnp.mean(x.astype(jnp.float32), axis=1)
    return pooled @ params["cls_w"] + params["cls_b"]


def classifier_loss_fn(
    params: Params,
    tokens: jax.Array,
    labels: jax.Array,
    cfg: gpt.GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    logits = classifier_logits(params, tokens, cfg, attn_fn)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return -jnp.mean(ll)


def classifier_logical_axes(cfg: gpt.GPTConfig, n_classes: int):
    axes = gpt.param_logical_axes(cfg)
    axes["cls_w"] = ("embed", None)
    axes["cls_b"] = (None,)
    return axes
