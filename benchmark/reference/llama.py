"""Plain reference for family ``llama``: the Llama / Mistral block
(Touvron et al. 2023; Jiang et al. 2023, "Mistral 7B"; huggingface
``MistralModel``) on the program's parameter tree.

Pre-norm RMSNorm, rotary position embedding in huggingface's
split-halves convention (the first half of a head's dimensions is
paired with the second), grouped-query attention, a causal mask with
Mistral's sliding window (query i sees keys j, i - window < j <= i),
SwiGLU MLP, final RMSNorm, untied loss head. No departure from the
published block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, T, H, D]: rotate (x[..., i], x[..., i + D/2]) by
    pos * theta ** (-2i / D)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss(params, tokens, targets, config: dict):
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    window = config.get("sliding_window")

    def layer(x, lp):
        lp = common.f32(lp)
        b, t, e = x.shape
        d = e // heads
        h = _rms_norm(x, lp["rms1"], eps)
        q = _rope((h @ lp["wq"]).reshape(b, t, heads, d), theta)
        k = _rope((h @ lp["wk"]).reshape(b, t, kv_heads, d), theta)
        v = (h @ lp["wv"]).reshape(b, t, kv_heads, d)
        att = common.attention(q, k, v, window=window)
        x = x + att.reshape(b, t, e) @ lp["wo"]
        h = _rms_norm(x, lp["rms2"], eps)
        return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]

    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
            params["wte"], tokens
        )
        x = common.run_layers(
            x, params["blocks"], layer, config["num_hidden_layers"]
        )
        return common.mean_over_rows(
            lambda x, tgt, g, head: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps), head, tgt
            ),
            x, targets, params["rmsf"], params["lm_head"],
        )
