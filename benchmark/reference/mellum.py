"""Plain reference for family ``mellum``: the Mellum 2 block
(huggingface ``mellum``; JetBrains/Mellum2-12B-A2.5B-Instruct's
``config.json``) on the program's parameter tree, float32 at
"highest": explicit masks, key and value heads repeated by index,
both rotation tables written out here from the published formula, the
experts as a loop with dense weights. No kernel, no sort, no grouped
product, nothing of ``models/mellum.py``, ``models/llama.py`` or
``models/moe.py``.

Pre-norm residual blocks, RMSNorm with ``rms_norm_eps``:
``h = x + attention(norm(x))``, ``y = h + experts(norm(h))``; a final
norm; an untied head. Layer i (from 0) is of kind ``layer_types[i]``.

Attention (``H = num_attention_heads`` query heads, ``G =
num_key_value_heads`` key/value heads, ``d = head_dim`` columns each,
which is not ``hidden_size / H``; no bias):

    q = x W_q as [T, H, d];  k, v = x W_k, x W_v as [T, G, d]
    q, k <- rotate(q), rotate(k) by the layer kind's (cos, sin), in
        huggingface's split-halves convention
    query head h reads key/value head h // (H / G)
    scores q k^T / sqrt(d); query i sees key j when j <= i and, in a
    ``sliding_attention`` layer, i - j < sliding_window; softmax; W_o

The rotation of a kind, from ``rope_parameters[kind]``:

    default:  inv_freq_i = theta^(-2i / d), i < d / 2
              cos, sin = cos(pos x inv_freq), sin(pos x inv_freq)
    yarn (Peng et al. 2023; huggingface ``_compute_yarn_parameters``):
        dim(beta) = d ln(L / (2 pi beta)) / (2 ln theta), L the
            original context
        low, high = floor(dim(beta_fast)), ceil(dim(beta_slow)),
            clipped to [0, d - 1]
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = theta^(-2i / d) x ((1 - ramp_i) + ramp_i / factor)
        cos, sin = attention_factor x (cos, sin)(pos x inv_freq)

Expert layer (``mlp_layer_types`` all ``sparse``): ``p = softmax(x
W_r)`` over all the router's experts in float32; the
``num_experts_per_tok`` largest are kept and, ``norm_topk_prob`` true,
divided by their sum; ``y = sum_k w_k E_k(x)``, every expert ``W_down
(silu(W_gate x) * W_up x)``. Of a chip's share the sum runs over the
experts held alone (``num_experts`` of them from ``first_expert`` on,
of the router's ``router_num_experts``); what the absent ones would
have added is left out, here as in the program.

The training loss is the mean token cross-entropy +
``router_aux_loss_coef`` x the load-balancing loss averaged over the
layers (huggingface ``load_balancing_loss_func``: experts x sum over
experts of (pairs sent to the expert / tokens) x (its mean p), every
one of the top-k choices counted, over all the router's experts).

Departures from the published description, all of them:
* No normalisation of q and k by head: ``config.json`` has no key for
  one (``assumed.qk_norm``).
* huggingface concatenates the layers' router logits before the
  load-balancing product; here each layer's loss is computed alone and
  the layers' averaged, as ``reference/olmoe.py`` does and says.
* The multi-token-prediction head is left out (``assumed.mtp``).
* Attention is computed a block of ``Q_BLOCK`` queries at a time
  against all the keys, so that 8,192 tokens fit beside the system's
  state; a blocked sum of the same terms.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families.mellum import SLIDING, layer_kinds  # no JAX there
from benchmark.reference import common
from benchmark.reference.llama import _rms_norm

Q_BLOCK = 512


def rotation(rope: dict, d: int, t: int):
    """(cos, sin) [t, d / 2] float32 of one ``rope_parameters`` entry."""
    half = d // 2
    theta = float(rope["rope_theta"])
    inv_freq = [theta ** (-i / half) for i in range(half)]
    scale = 1.0
    if rope["rope_type"] == "yarn":
        factor = float(rope["factor"])
        original = rope["original_max_position_embeddings"]

        def dim(beta):
            return d * math.log(original / (beta * 2 * math.pi)) / (
                2 * math.log(theta)
            )

        low = max(math.floor(dim(rope["beta_fast"])), 0)
        high = min(math.ceil(dim(rope["beta_slow"])), d - 1)
        for i in range(half):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            inv_freq[i] *= (1.0 - ramp) + ramp / factor
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"no rope_type {rope['rope_type']!r} here")
    ang = (
        jnp.arange(t, dtype=jnp.float32)[:, None]
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    )
    return scale * jnp.cos(ang), scale * jnp.sin(ang)


def rotate(x, cos, sin):
    """x [B, T, H, d]: (x_i, x_{i + d/2}) turned by (cos_i, sin_i)."""
    half = x.shape[-1] // 2
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, window=None):
    """q [B, T, H, d]; k, v [B, T, G, d] -> [B, T, H, d]: masked
    softmax attention, ``Q_BLOCK`` queries at a time."""
    b, t, h, d = q.shape
    kv_head = jnp.arange(h) // (h // k.shape[2])
    k, v = k[:, :, kv_head, :], v[:, :, kv_head, :]
    block = min(Q_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(t)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        rows = start + jnp.arange(block)
        seen = keys[None, :] <= rows[:, None]
        if window is not None:
            seen &= rows[:, None] - keys[None, :] < window
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t + pad, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, d)
    return out[:, :t]


def router_weights(h, router, config: dict):
    """h [n, E] -> (weight [n, router experts] float32, 0 where not
    chosen; the layer's load-balancing loss)."""
    n_router = router.shape[-1]
    p = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(p, config["num_experts_per_tok"])
    kept = jnp.sum(jax.nn.one_hot(chosen, n_router, dtype=p.dtype), axis=1)
    weight = p * kept
    if config["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    balance = n_router * jnp.sum(jnp.mean(kept, axis=0) * jnp.mean(p, axis=0))
    return weight, balance


def expert_layer(h, moe, config: dict, first: int):
    """h [n, E] -> (the part of the layer's result that the experts in
    ``moe`` give, experts ``first`` to ``first + len(moe['wi'])`` of
    the router's; the load-balancing loss). Every one of them is
    applied to every token and masked by the choice."""
    weight, balance = router_weights(h, moe["router"], config)

    def one_expert(e, y):
        gate = moe["wg"][e].astype(jnp.float32)
        up = moe["wi"][e].astype(jnp.float32)
        down = moe["wo"][e].astype(jnp.float32)
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        picked = jax.lax.dynamic_index_in_dim(
            weight, first + e, axis=1, keepdims=True
        )
        return y + picked * out

    y = jax.lax.fori_loop(0, moe["wi"].shape[0], one_expert, jnp.zeros_like(h))
    return y, balance


def _layer_fn(config: dict, kind: str):
    heads = config["num_attention_heads"]
    groups = config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    window = config["sliding_window"] if kind == SLIDING else None
    first = config["assumed"]["first_expert"]

    def layer(x, lp):
        moe = lp["moe"]
        lp = common.f32({k: v for k, v in lp.items() if k != "moe"})
        b, t, e = x.shape
        cos, sin = rotation(config["rope_parameters"][kind], d, t)
        h = _rms_norm(x, lp["rms1"], eps)
        q = rotate((h @ lp["wq"]).reshape(b, t, heads, d), cos, sin)
        k = rotate((h @ lp["wk"]).reshape(b, t, groups, d), cos, sin)
        v = (h @ lp["wv"]).reshape(b, t, groups, d)
        att = attention(q, k, v, window)
        x = x + att.reshape(b, t, heads * d) @ lp["wo"]
        h = _rms_norm(x, lp["rms2"], eps)
        y, balance = expert_layer(h.reshape(b * t, e), moe, config, first)
        return x + y.reshape(b, t, e), balance

    return layer


def hidden_and_balance(params, tokens, config: dict):
    """([B, T, E] hidden before the final norm, the load-balancing loss
    averaged over the layers); one jitted call a layer on that layer's
    parameters, so that no more than one layer is held in float32."""
    x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
        params["wte"], tokens
    )
    kinds = layer_kinds(config)
    period = len(params["periods"])
    names = [f"{i}_{kinds[i]}" for i in range(period)]
    steps = {kind: jax.jit(_layer_fn(config, kind)) for kind in set(kinds)}
    balance = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(kinds):
        lp = jax.tree.map(
            lambda a: a[i // period], params["periods"][names[i % period]]
        )
        x, bal = steps[kind](x, lp)
        balance = balance + bal
    return x, balance / len(kinds)


def logits(params, tokens, config: dict):
    """[B, T, V] float32, for the CPU tests."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_and_balance(params, tokens, config)
        x = _rms_norm(
            x, params["rmsf"].astype(jnp.float32), config["rms_norm_eps"]
        )
        return jnp.einsum(
            "bte,ve->btv", x, params["lm_head"].astype(jnp.float32)
        )


def loss(params, tokens, targets, config: dict):
    eps = config["rms_norm_eps"]
    coef = config["assumed"]["router_aux_loss_coef"]
    with jax.default_matmul_precision("highest"):
        x, balance = hidden_and_balance(params, tokens, config)
        ce = common.mean_over_rows(
            lambda x, tgt, g, head: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps), head, tgt
            ),
            x, targets, params["rmsf"], params["lm_head"],
        )
        return ce + coef * balance
