"""Plain reference for family ``kimi_linear``: the Kimi Linear block
(Kimi Team 2025, "Kimi Linear: An Expressive, Efficient Attention
Architecture", arXiv:2510.26692; huggingface ``kimi_linear``) on the
program's parameter tree, float32 at "highest", a token at a time
where the model is a recurrence: no chunk, no kernel, no sort, no
grouped product, nothing of ``ops/kda.py`` or ``models/moe.py``.

Pre-norm residual blocks, RMSNorm with ``rms_norm_eps``:
``h = x + mixer(norm(x))``, ``y = h + ffn(norm(h))``; a final norm; an
untied head. Published layers are numbered from 1: layer i is a KDA
layer if ``linear_attn_config.kda_layers`` lists it and a latent
attention layer if ``full_attn_layers`` does; the first
``first_k_dense_replace`` layers have the dense MLP, every other the
expert layer.

KDA mixer (``d = linear_attn_config.head_dim`` a head, ``H =
num_heads``):

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        conv: depthwise, causal, width ``short_conv_kernel_size``, no bias
    q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q d^-0.5
    g_t = -exp(A_log[h]) softplus((x_t W_fa) W_fb + dt_bias)   a channel
    beta_t = sigmoid(x_t W_b)                                   a head
    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                       S float32 [d, d], zero at t = 0
    y = (RMSNorm_d(o_t) * w) * sigmoid((x_t W_ga) W_gb);  y W_o

Latent attention (``mla_use_nope``: nothing is rotated; ``q_lora_rank``
null: the query is one projection):

    q = x W_q as [T, H, qk_nope + qk_rope]
    c, k_r = split(x W_kva, [kv_lora_rank, qk_rope])
    k_n, v = split(RMSNorm(c) W_kvb as [T, H, qk_nope + v_head])
    k = [k_n | k_r for every head];  causal softmax attention at scale
    (qk_nope + qk_rope)^-0.5, values of width v_head;  W_o

Expert layer: ``s = sigmoid(x W_r)`` over all the router's experts in
float32; the ``num_experts_per_token`` chosen are the largest of
``s + b`` (``num_expert_group`` 1 makes the grouped top-k plain);
weights ``s[chosen]`` without ``b``, over their sum plus 1e-20
(``moe_renormalize``), times ``routed_scaling_factor``;
``y = sum_k w_k E_k(x) + E_shared(x)``, every expert
``W_down (silu(W_gate x) * W_up x)``. Of a chip's share the sum runs
over the experts held alone (``num_experts`` of them from
``first_expert`` on, of the router's ``router_num_experts``); what the
absent ones would have added is left out, here as in the program.

Departures from the published code, all of them:
* The bias ``b`` is a buffer: the published balancing step that moves
  it has no rate in ``config.json`` and is left out (``assumed``).
* The 128-wide two-step projections of the decay and of the output
  gate, the L2 norm's epsilon and the initial values are the published
  modelling code's as recalled; ``config.json`` has no key for them
  (``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families.kimi_linear import layer_kinds  # no JAX there
from benchmark.reference import common
from benchmark.reference.llama import _rms_norm

L2_EPS = 1e-6


def _conv_silu(x, w):
    """x [B, T, C], w [K, C]: ``w[k]`` multiplies the input K - 1 - k
    tokens back; zero before the first token."""
    width, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(
        sum(w[k] * padded[:, k: k + t] for k in range(width))
    )


def delta_rule(q, k, v, g, beta):
    """The recurrence a token at a time. q, k, g [B, T, H, d], v
    [B, T, H, d_v], beta [B, T, H] -> o [B, T, H, d_v]."""
    b, t, h, d = q.shape
    steps = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))

    def read(state, x):
        # S^T x as a multiply and a sum over the key's channels: exact
        # float32 on the vector unit, and a fraction of the time the
        # chip takes for 8,192 products of a matrix with one vector.
        return jnp.sum(state * x[..., None], axis=-2)

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        decayed = jnp.exp(g_t)[..., None] * state
        delta = beta_t[..., None] * (v_t - read(decayed, k_t))
        state = decayed + k_t[..., None] * delta[..., None, :]
        return state, read(state, q_t)

    zero = jnp.zeros((b, h, d, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, zero, steps)
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(u, lp, config: dict):
    linear = config["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    b, t, _ = u.shape
    inner = heads * d
    qkv = _conv_silu(u @ lp["w_qkv"], lp["conv_w"])
    q, k, v = (
        qkv[..., i * inner: (i + 1) * inner].reshape(b, t, heads, d)
        for i in range(3)
    )
    unit = lambda x: x / jnp.sqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS
    )
    q, k = unit(q) * d ** -0.5, unit(k)
    step = jax.nn.softplus((u @ lp["w_fa"]) @ lp["w_fb"] + lp["dt_bias"])
    g = -jnp.exp(lp["A_log"])[:, None] * step.reshape(b, t, heads, d)
    beta = jax.nn.sigmoid(u @ lp["w_b"])
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        + config["rms_norm_eps"]
    )
    gate = jax.nn.sigmoid((u @ lp["w_ga"]) @ lp["w_gb"])
    y = o * lp["o_norm"] * gate.reshape(b, t, heads, d)
    return y.reshape(b, t, inner) @ lp["w_o"]


def mla_mixer(u, lp, config: dict):
    heads = config["num_attention_heads"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope, d_v = config["qk_rope_head_dim"], config["v_head_dim"]
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None:
        raise ValueError(
            "the reference has latent attention without positions and "
            "with a plain query projection, as published"
        )
    b, t, _ = u.shape
    q = (u @ lp["wq"]).reshape(b, t, heads, nope + rope)
    latent = u @ lp["w_kva"]
    c = _rms_norm(latent[..., :rank], lp["kv_norm"], config["rms_norm_eps"])
    kv = (c @ lp["w_kvb"]).reshape(b, t, heads, nope + d_v)
    shared = jnp.broadcast_to(latent[..., None, rank:], (b, t, heads, rope))
    k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
    # common.attention scales by one over the root of q's head size.
    att = common.attention(q, k, kv[..., nope:])
    return att.reshape(b, t, heads * d_v) @ lp["w_o"]


def router_weights(h, moe, config: dict):
    """h [n, E] -> [n, router experts] float32: each token's weight on
    every expert, zero where not chosen."""
    if config["moe_router_activation_func"] != "sigmoid":
        raise ValueError("the reference has the published sigmoid router")
    n_router = moe["router"].shape[-1]
    scores = jax.nn.sigmoid(h @ moe["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(
        scores + moe["router_bias"], config["num_experts_per_token"]
    )
    kept = jnp.sum(jax.nn.one_hot(chosen, n_router, dtype=scores.dtype), axis=1)
    weight = scores * kept
    if config["moe_renormalize"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * config["routed_scaling_factor"]


def routed_experts(h, moe, config: dict, first: int):
    """The part of the layer's result that the experts in ``moe``
    give: experts ``first`` to ``first + len(moe['wi'])`` of the
    router's. Every one of them is applied to every token and masked
    by the choice."""
    weight = router_weights(h, moe, config)

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


def swiglu(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def expert_layer(h, moe, config: dict):
    """h [B, T, E] -> the expert layer's output: this share's routed
    part and the shared expert."""
    first = config.get("assumed", {}).get("first_expert", 0)
    b, t, e = h.shape
    routed = routed_experts(h.reshape(b * t, e), moe, config, first)
    return routed.reshape(b, t, e) + swiglu(h, common.f32(moe["shared"]))


def _layer_fn(config: dict, mixer: str, ffn: str):
    eps = config["rms_norm_eps"]

    def layer(x, lp):
        moe = lp.get("moe")
        lp = common.f32({k: v for k, v in lp.items() if k != "moe"})
        h = _rms_norm(x, lp["rms1"], eps)
        x = x + (
            kda_mixer(h, lp, config) if mixer == "kda"
            else mla_mixer(h, lp, config)
        )
        h = _rms_norm(x, lp["rms2"], eps)
        if ffn == "dense":
            return x + swiglu(h, lp)
        return x + expert_layer(h, moe, config)

    return layer


def hidden(params, tokens, config: dict):
    """[B, T, E] hidden before the final norm; one jitted call a
    layer on that layer's parameters, so that no more than one layer
    is held in float32."""
    x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
        params["wte"], tokens
    )
    steps = {}
    for i, kind in enumerate(layer_kinds(config)):
        if kind not in steps:
            steps[kind] = jax.jit(_layer_fn(config, *kind))
        x = steps[kind](x, params["layers"]["%d_%s_%s" % ((i,) + kind)])
    return x


def logits(params, tokens, config: dict):
    """[B, T, V] float32, for the CPU tests."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(
            hidden(params, tokens, config),
            params["rmsf"].astype(jnp.float32), config["rms_norm_eps"],
        )
        return jnp.einsum(
            "bte,ve->btv", x, params["lm_head"].astype(jnp.float32)
        )


def loss(params, tokens, targets, config: dict):
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        return common.mean_over_rows(
            lambda x, tgt, g, head: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps), head, tgt
            ),
            hidden(params, tokens, config), targets,
            params["rmsf"], params["lm_head"],
        )
