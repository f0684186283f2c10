"""Plain reference for family ``gpt``: GPT-2's block (Radford et al.
2019; huggingface ``GPT2Model``) on the program's parameter tree.

Pre-norm LayerNorm (eps from the file), learned positions, attention
with a causal mask, MLP of 4E with tanh-GELU (``gelu_new``), final
LayerNorm, loss head tied to the embedding. One departure from the
published block, which the program makes and the configuration file
records under ``assumed``: no bias on the attention projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3))
    )


def loss(params, tokens, targets, config: dict):
    heads = config["n_head"]
    eps = config["layer_norm_epsilon"]

    def layer(x, lp):
        lp = common.f32(lp)
        b, t, e = x.shape
        h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
        q, k, v = jnp.split(h @ lp["wqkv"], 3, axis=-1)
        shape = (b, t, heads, e // heads)
        att = common.attention(
            q.reshape(shape), k.reshape(shape), v.reshape(shape)
        )
        x = x + att.reshape(b, t, e) @ lp["wo"]
        h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
        return x + _gelu_new(h @ lp["wi"] + lp["bi"]) @ lp["wo2"] + lp["bo2"]

    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = jax.jit(
            lambda wte, wpe, tok: wte[tok].astype(jnp.float32)
            + wpe[:t][None].astype(jnp.float32)
        )(params["wte"], params["wpe"], tokens)
        x = common.run_layers(x, params["blocks"], layer, config["n_layer"])
        return common.mean_over_rows(
            lambda x, tgt, g, b, wte: common.mean_cross_entropy(
                _layer_norm(x, g.astype(jnp.float32), b.astype(jnp.float32), eps),
                wte, tgt,
            ),
            x, targets, params["lnf_g"], params["lnf_b"], params["wte"],
        )
