"""Plain reference for family ``olmoe``: the OLMoE block (Muennighoff et
al. 2024, "OLMoE: Open Mixture-of-Experts Language Models";
huggingface ``OlmoeDecoderLayer``) on the program's parameter tree.

One layer, for hidden states x [B, T, 2048]:

    h = RMSNorm(x)                                   (gain ``rms1``)
    q = RMSNorm(h W_q), k = RMSNorm(h W_k)           (gains ``q_norm``,
        ``k_norm`` over the WHOLE projected vector, before the split
        into heads), v = h W_v
    heads of ``hidden_size / num_attention_heads``; rotary embedding on
    q and k in huggingface's split-halves convention at ``rope_theta``;
    causal softmax attention, no window; x = x + att W_o
    h = RMSNorm(x)                                   (gain ``rms2``)
    logits = h W_r (no bias), p = softmax over all ``num_experts`` in
    float32; the ``num_experts_per_tok`` largest p are kept WITH THEIR
    SOFTMAX VALUES (``norm_topk_prob`` false: not renormalised; true
    divides them by their sum)
    x = x + sum over the kept experts e of
            p_e * W_down_e (silu(W_gate_e h) * W_up_e h)

then a final RMSNorm (``rmsf``) and an untied head. The training loss
is the mean token cross-entropy
  + ``router_aux_loss_coef`` x the load-balancing loss
  + ``router_z_loss_coef`` x the router z-loss,
the two router losses averaged over layers:

    load balancing (huggingface ``load_balancing_loss_func``; Switch
      Transformer eq. 4-6 with every one of the top-k choices counted):
      num_experts x sum_e (pairs (token, choice) sent to e / tokens)
                          x (mean over tokens of p_e)
    z-loss (OLMoE paper, section on the router z-loss; ST-MoE):
      mean over tokens of logsumexp(logits)^2

Every expert is applied to every token and masked by the choice: no
sort, no grouped product, no capacity, nothing of ``models/moe.py``.

Departures from the published code, all of them:
* huggingface concatenates the layers' router logits and takes the
  means over layers x tokens before the product; here each layer's
  loss is computed alone and the layers' losses averaged, as the
  model's own training code (OLMo with megablocks) does. One layer
  (this benchmark's depth) gives the same number either way.
* huggingface's ``OlmoeForCausalLM`` has no z-loss; the paper trains
  with it at 0.001, and so does this loss. Both coefficients are the
  configuration file's ``assumed`` keys.
* ``clip_qkv`` is null in the published configuration and is not
  implemented.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common
from benchmark.reference.llama import _rms_norm, _rope


def _experts(h, moe, top_k, norm_topk):
    """h [n, E] float32 -> (y [n, E], load-balancing loss, z-loss)."""
    n_experts = moe["router"].shape[-1]
    logits = h @ moe["router"].astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(p, top_k)
    kept = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=p.dtype), axis=1)
    weight = p * kept  # [n, experts], 0 where not chosen
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(e, y):
        gate = moe["wg"][e].astype(jnp.float32)
        up = moe["wi"][e].astype(jnp.float32)
        down = moe["wo"][e].astype(jnp.float32)
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + weight[:, e][:, None] * out

    y = jax.lax.fori_loop(0, n_experts, one_expert, jnp.zeros_like(h))
    balance = n_experts * jnp.sum(
        jnp.mean(kept, axis=0) * jnp.mean(p, axis=0)
    )
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, balance, z


def _coefficients(config: dict) -> tuple:
    assumed = config.get("assumed", {})
    return (assumed["router_aux_loss_coef"], assumed["router_z_loss_coef"])


def _layer_fn(config: dict):
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    top_k = config["num_experts_per_tok"]
    norm_topk = config["norm_topk_prob"]

    def layer(carry, lp):
        x, balance, z = carry
        moe = lp["moe"]
        lp = common.f32({k: v for k, v in lp.items() if k != "moe"})
        b, t, e = x.shape
        d = e // heads
        h = _rms_norm(x, lp["rms1"], eps)
        q = _rms_norm(h @ lp["wq"], lp["q_norm"], eps)
        k = _rms_norm(h @ lp["wk"], lp["k_norm"], eps)
        q = _rope(q.reshape(b, t, heads, d), theta)
        k = _rope(k.reshape(b, t, kv_heads, d), theta)
        v = (h @ lp["wv"]).reshape(b, t, kv_heads, d)
        x = x + common.attention(q, k, v).reshape(b, t, e) @ lp["wo"]
        h = _rms_norm(x, lp["rms2"], eps)
        y, bal, zl = _experts(h.reshape(b * t, e), moe, top_k, norm_topk)
        return x + y.reshape(b, t, e), balance + bal, z + zl

    return layer


def hidden_and_router_losses(params, tokens, config: dict):
    """([B, T, E] hidden before the final norm, load-balancing loss,
    z-loss), the two losses averaged over layers."""
    layers = config["num_hidden_layers"]
    x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
        params["wte"], tokens
    )
    zero = jnp.zeros((), jnp.float32)
    x, balance, z = common.run_layers(
        (x, zero, zero), params["blocks"], _layer_fn(config), layers
    )
    return x, balance / layers, z / layers


def logits(params, tokens, config: dict):
    """[B, T, V] float32, for the CPU tests."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = hidden_and_router_losses(params, tokens, config)
        x = _rms_norm(
            x, params["rmsf"].astype(jnp.float32), config["rms_norm_eps"]
        )
        return jnp.einsum(
            "bte,ve->btv", x, params["lm_head"].astype(jnp.float32)
        )


def loss(params, tokens, targets, config: dict):
    eps = config["rms_norm_eps"]
    aux_coef, z_coef = _coefficients(config)
    with jax.default_matmul_precision("highest"):
        x, balance, z = hidden_and_router_losses(params, tokens, config)
        ce = common.mean_over_rows(
            lambda x, tgt, g, head: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps), head, tgt
            ),
            x, targets, params["rmsf"], params["lm_head"],
        )
        return ce + aux_coef * balance + z_coef * z
