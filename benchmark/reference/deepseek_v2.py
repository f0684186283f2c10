"""Plain reference for family ``deepseek_v2``: the DeepSeek-V2 block
(DeepSeek-AI 2024, "DeepSeek-V2: A Strong, Economical, and Efficient
Mixture-of-Experts Language Model", arXiv:2405.04434; huggingface
``deepseek_v2``, deepseek-ai/DeepSeek-V2-Lite's ``config.json``) on
the program's parameter tree in the published column order, float32
at "highest": an explicit causal mask, the rotation written out pair
by pair with its own YaRN frequencies, the shared key part repeated
by index, the experts as a loop with dense weights, the balance term
from counts. No kernel, no sort, no grouped product, nothing of
``models/``.

Pre-norm residual blocks, RMSNorm with ``rms_norm_eps``:
``h = x + attention(norm(x))``, ``y = h + ffn(norm(h))``; a final
norm; an untied head. The first ``first_k_dense_replace`` layers have
the dense MLP, every other (``moe_layer_freq`` 1) the expert layer.

Latent attention (``H = num_attention_heads``, ``d_n =
qk_nope_head_dim``, ``d_r = qk_rope_head_dim``, ``d_v = v_head_dim``,
``r = kv_lora_rank``; ``q_lora_rank`` null: the query is one
projection; no bias):

    q = x W_q as [T, H, d_n + d_r] = [q_n | q_r]
    c, k_r = split(x W_kva, [r, d_r])       k_r one vector, no head's
    k_n, v = split(RMSNorm(c) W_kvb as [T, H, d_n + d_v])
    q_r, k_r <- R_t(q_r), R_t(k_r)          k_r is not normed
    k = [k_n | k_r for every head]
    o = causal softmax(q k^T s) v;  o W_o
    s = (d_n + d_r)^-0.5 x m(mscale_all_dim)^2
    m(a) = 0.1 a ln(factor) + 1             (1 where factor <= 1)

The rotation at position t (``rope_scaling``; ``yarn``):

    R_t turns the channel pairs (2i, 2i + 1), i < d_r / 2, by t f_i:
        (x_2i, x_2i+1) <- (x_2i cos - x_2i+1 sin, x_2i+1 cos + x_2i sin)
    dim(beta) = d_r ln(L / (2 pi beta)) / (2 ln theta), L the original
        context
    low, high = floor(dim(beta_fast)), ceil(dim(beta_slow)), clipped
        to [0, d_r - 1]
    ramp_i = clip((i - low) / (high - low), 0, 1)
    f_i = theta^(-2i / d_r) x ((1 - ramp_i) + ramp_i / factor)
    cos, sin times m(mscale) / m(mscale_all_dim)

Expert layer: ``p = softmax(x W_r)`` over all the router's experts in
float32; the ``num_experts_per_tok`` largest are chosen (``greedy``,
``n_group`` 1) and weigh as they are times ``routed_scaling_factor``
(``norm_topk_prob`` false); ``y = sum_k p_k E_k(x) + E_shared(x)``,
every expert ``W_down (silu(W_gate x) * W_up x)``, the shared one of
width ``n_shared_experts x moe_intermediate_size``. Of a chip's share
the sum runs over the experts held alone (``n_routed_experts`` of them
from ``first_expert`` on, of the router's ``router_num_experts``);
what the absent ones would have added is left out, here as in the
program.

The training loss is the mean token cross-entropy + ``aux_loss_alpha``
x the sum over the expert layers of the sequence-wise balance term
(``seq_aux``): the mean over sequences b of ``sum_e f_be P_be``, ``f_be
= (pairs of sequence b sent to e) x experts / (T x
num_experts_per_tok)``, ``P_be`` the mean over the sequence of
``p_bte``.

Departures from the published description, all of them:
* The published code adds the balance term's gradient through a
  function that leaves its value out of the loss it reports; here the
  term is part of the loss (the gradients are the same).
* The published code re-orders each rotated part to split halves and
  rotates there; the pairs turned are the same adjacent ones, which is
  what is written here.
* Attention is computed a block of ``Q_BLOCK`` queries at a time
  against all the keys, so that 8,192 tokens fit beside the system's
  state; a blocked sum of the same terms.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families.deepseek_v2 import layer_kinds  # no JAX there
from benchmark.reference import common
from benchmark.reference.llama import _rms_norm

Q_BLOCK = 512


def mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def frequencies(config: dict) -> list:
    """f_i, i < d_r / 2, of the rotated part."""
    d = config["qk_rope_head_dim"]
    theta = float(config["rope_theta"])
    freqs = [theta ** (-2 * i / d) for i in range(d // 2)]
    scaling = config["rope_scaling"]
    if scaling is None:
        return freqs
    if scaling["type"] != "yarn":
        raise ValueError(f"no rope_scaling type {scaling['type']!r} here")
    original = scaling["original_max_position_embeddings"]

    def dim(beta):
        return d * math.log(original / (beta * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), d - 1)
    for i in range(d // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freqs[i] *= (1.0 - ramp) + ramp / float(scaling["factor"])
    return freqs


def softmax_scale(config: dict) -> float:
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    scaling = config["rope_scaling"]
    if scaling is None or not scaling.get("mscale_all_dim"):
        return d ** -0.5
    return d ** -0.5 * mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2


def rotate(x, config: dict):
    """x [B, T, heads, d_r]: the adjacent pairs turned by position."""
    t = x.shape[1]
    ang = (
        jnp.arange(t, dtype=jnp.float32)[:, None]
        * jnp.asarray(frequencies(config), jnp.float32)[None, :]
    )
    scaling = config["rope_scaling"] or {"factor": 1}
    size = mscale(scaling["factor"], scaling.get("mscale", 1)) / mscale(
        scaling["factor"], scaling.get("mscale_all_dim", 0)
    )
    cos = (size * jnp.cos(ang))[None, :, None, :]
    sin = (size * jnp.sin(ang))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    )
    return turned.reshape(x.shape)


def attention(q, k, v, scale: float):
    """q, k [B, T, H, d]; v [B, T, H, d_v] -> [B, T, H, d_v]: causal
    softmax attention, ``Q_BLOCK`` queries at a time."""
    b, t, h, _ = q.shape
    block = min(Q_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(t)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t + pad, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, v.shape[-1])
    return out[:, :t]


def mla_mixer(u, lp, config: dict):
    heads = config["num_attention_heads"]
    rank, d_n = config["kv_lora_rank"], config["qk_nope_head_dim"]
    d_r, d_v = config["qk_rope_head_dim"], config["v_head_dim"]
    if config["q_lora_rank"] is not None:
        raise ValueError("the reference has a plain query projection")
    b, t, _ = u.shape
    q = (u @ lp["wq"]).reshape(b, t, heads, d_n + d_r)
    latent = u @ lp["w_kva"]
    c = _rms_norm(latent[..., :rank], lp["kv_norm"], config["rms_norm_eps"])
    kv = (c @ lp["w_kvb"]).reshape(b, t, heads, d_n + d_v)
    q_r = rotate(q[..., d_n:], config)
    k_r = rotate(latent[..., None, rank:], config)
    every_head = jnp.zeros((heads,), jnp.int32)
    q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :d_n], k_r[:, :, every_head, :]], axis=-1)
    att = attention(q, k, kv[..., d_n:], softmax_scale(config))
    return att.reshape(b, t, heads * d_v) @ lp["w_o"]


def router_weights(h, router, config: dict):
    """h [B, T, E] -> (weight [B, T, router experts] float32, 0 where
    not chosen; the layer's balance term)."""
    if config["scoring_func"] != "softmax" or config["topk_method"] != "greedy":
        raise ValueError("the reference has the greedy softmax router")
    n_router, k = router.shape[-1], config["num_experts_per_tok"]
    p = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(p, k)
    kept = jnp.sum(jax.nn.one_hot(chosen, n_router, dtype=p.dtype), axis=-2)
    weight = p * kept
    if config["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * config["routed_scaling_factor"]
    t = h.shape[1]
    f = jnp.sum(kept, axis=1) * n_router / (t * k)  # [B, experts]
    balance = jnp.mean(jnp.sum(f * jnp.mean(p, axis=1), axis=-1))
    return weight, balance


def swiglu(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def routed_experts(h, weight, moe, first: int):
    """h [n, E], weight [n, router experts] -> the part of the layer's
    result that the experts in ``moe`` give: experts ``first`` to
    ``first + len(moe['wi'])`` of the router's. Every one of them is
    applied to every token and masked by the choice."""

    def one_expert(e, y):
        gate = moe["wg"][e].astype(jnp.float32)
        up = moe["wi"][e].astype(jnp.float32)
        down = moe["wo"][e].astype(jnp.float32)
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        picked = jax.lax.dynamic_index_in_dim(
            weight, first + e, axis=1, keepdims=True
        )
        return y + picked * out

    return jax.lax.fori_loop(
        0, moe["wi"].shape[0], one_expert, jnp.zeros_like(h)
    )


def expert_layer(h, moe, config: dict, first: int):
    """h [B, T, E] -> (this share's routed part and the shared
    experts; the layer's balance term)."""
    b, t, e = h.shape
    weight, balance = router_weights(h, moe["router"], config)
    routed = routed_experts(
        h.reshape(b * t, e), weight.reshape(b * t, -1), moe, first
    )
    y = routed.reshape(b, t, e)
    if "shared" in moe:
        y = y + swiglu(h, common.f32(moe["shared"]))
    return y, balance


def _layer_fn(config: dict, ffn: str):
    eps = config["rms_norm_eps"]
    first = config.get("assumed", {}).get("first_expert", 0)

    def layer(x, lp):
        moe = lp.get("moe")
        lp = common.f32({k: v for k, v in lp.items() if k != "moe"})
        x = x + mla_mixer(_rms_norm(x, lp["rms1"], eps), lp, config)
        h = _rms_norm(x, lp["rms2"], eps)
        if ffn == "dense":
            return x + swiglu(h, lp), jnp.zeros((), jnp.float32)
        y, balance = expert_layer(h, moe, config, first)
        return x + y, balance

    return layer


def hidden_and_balance(params, tokens, config: dict):
    """([B, T, E] hidden before the final norm, the balance terms
    summed over the expert layers); one jitted call a layer on that
    layer's parameters, so that no more than one layer is held in
    float32."""
    x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
        params["wte"], tokens
    )
    steps = {}
    balance = jnp.zeros((), jnp.float32)
    for i, ffn in enumerate(layer_kinds(config)):
        if ffn not in steps:
            steps[ffn] = jax.jit(_layer_fn(config, ffn))
        x, bal = steps[ffn](x, params["layers"][f"{i}_mla_{ffn}"])
        balance = balance + bal
    return x, balance


def loss(params, tokens, targets, config: dict):
    eps = config["rms_norm_eps"]
    alpha = config["assumed"]["aux_loss_alpha"]
    with jax.default_matmul_precision("highest"):
        x, balance = hidden_and_balance(params, tokens, config)
        ce = common.mean_over_rows(
            lambda x, tgt, g, head: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps), head, tgt
            ),
            x, targets, params["rmsf"], params["lm_head"],
        )
        return ce + alpha * balance
