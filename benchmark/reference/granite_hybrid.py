"""Plain reference for family ``granite_hybrid``: the Granite 4.0-H
stack (huggingface ``GraniteMoeHybridForCausalLM`` with
``num_local_experts`` 0; the Mamba-2 mixer of Dao & Gu 2024,
"Transformers are SSMs", as huggingface's ``GraniteMoeHybridMambaLayer``
/ ``BambaMixer`` writes it) on the program's parameter tree.

For hidden states h [B, T, E], ``rms`` an RMS norm with a gain:

    h = wte[tokens] * embedding_multiplier
    every layer:  h = h + residual_multiplier * mixer(rms_1(h))
                  h = h + residual_multiplier * mlp(rms_2(h))
    mlp(u) = (silu(u W_gate) * (u W_up)) W_down
    logits = rms_f(h) wte^T / logits_scaling     (the table is tied)
    loss   = mean token cross-entropy over the rows held

``layer_types`` says which mixer a layer has.

* ``attention``: ``num_attention_heads`` query heads and
  ``num_key_value_heads`` key-value heads of ``hidden_size /
  num_attention_heads``, no bias, NO positional embedding
  (``position_embedding_type`` "nope"), causal softmax of
  ``q k^T * attention_multiplier``, then ``W_o``.
* ``mamba``: ``[z | xBC | dt] = u W_in`` (widths d_inner, d_inner + 2 x
  groups x state, heads; no bias); ``xBC = silu(conv(xBC))``, a causal
  depthwise convolution of width ``mamba_d_conv`` with bias;
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)``; for each head, ONE TOKEN AT A TIME,

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     (S is head x state)
      y_t = S_t C_t + D x_t

  from S = 0; ``v = y * silu(z)``, ``y = g * v / sqrt(mean(v^2) + eps)``
  with the mean over the whole inner width (one norm group: the gate
  BEFORE the norm); then ``W_out``.

float32 throughout at ``jax.default_matmul_precision("highest")``; no
chunks, no kernel, nothing of ``dlrover_tpu``: the recurrence is a
``lax.scan`` with one step a token, so it cannot share a fault with the
chunked algorithm it checks.

Departures from the published code, all of them:
* huggingface evaluates the recurrence in chunks of
  ``mamba_chunk_size`` (or in a fused kernel); here it is the
  recurrence itself. ``mamba_chunk_size`` is not read.
* huggingface's MLP holds ``W_gate`` and ``W_up`` side by side in one
  ``input_linear``; the program's tree holds the two halves.
* huggingface clamps ``dt`` to ``time_step_limit``, (0, inf) as
  published: no effect, not implemented.
* Where the configuration's ``vocab_size`` is a slice of the published
  table, ids, logits and loss are over the rows held: a smaller
  vocabulary, the same arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _attention(q, k, v, scale):
    """q [B, T, H, D]; k, v [B, T, Hkv, D]: query head h reads
    key-value head h // (H / Hkv); causal; one head at a time, so that
    one [T, T] float32 score matrix is held."""
    t, heads = q.shape[1], q.shape[2]
    group = heads // k.shape[2]
    pos = jnp.arange(t)
    mask = pos[:, None] >= pos[None, :]

    def one_head(i):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, i], k[:, :, i // group]) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v[:, :, i // group])

    return jnp.transpose(jax.lax.map(one_head, jnp.arange(heads)), (1, 2, 0, 3))


def recurrence(x, dt, a, b, c, d):
    """The state-space recurrence as written, a token a step.
    x [B, T, H, P]; dt [B, T, H]; a, d [H]; b, c [B, T, G, N] (head h
    reads group h // (H / G)). Returns y [B, T, H, P]."""
    heads = x.shape[2]
    per_group = heads // b.shape[2]
    b = jnp.repeat(b, per_group, axis=2)
    c = jnp.repeat(c, per_group, axis=2)

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, H, P], [B, H], [B, H, N] x 2
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        )
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) + d[:, None] * x_t
        return state, y_t

    state = jnp.zeros(x.shape[0:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    by_token = [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(token, state, by_token)
    return jnp.moveaxis(y, 0, 1)


def _mamba(u, lp, config):
    bsz, t, _ = u.shape
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    width = config["mamba_d_conv"]
    inner, gn = heads * p, groups * n
    proj = u @ lp["w_in"]
    z, xbc, dt = (
        proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
        proj[..., 2 * inner + 2 * gn:],
    )
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = lp["conv_b"] + sum(
        padded[:, k:k + t] * lp["conv_w"][k] for k in range(width)
    )
    xbc = jax.nn.silu(conv)
    y = recurrence(
        xbc[..., :inner].reshape(bsz, t, heads, p),
        jax.nn.softplus(dt + lp["dt_bias"]),
        -jnp.exp(lp["A_log"]),
        xbc[..., inner:inner + gn].reshape(bsz, t, groups, n),
        xbc[..., inner + gn:].reshape(bsz, t, groups, n),
        lp["D"],
    ).reshape(bsz, t, inner)
    v = y * jax.nn.silu(z)
    y = _rms_norm(v, lp["ssm_norm"], config["rms_norm_eps"])
    return y @ lp["w_out"]


def _layer_fn(config: dict, kind: str):
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    residual = config["residual_multiplier"]

    def layer(x, lp):
        lp = common.f32(lp)
        b, t, e = x.shape
        h = _rms_norm(x, lp["rms1"], eps)
        if kind == "mamba":
            mixed = _mamba(h, lp, config)
        else:
            d = e // heads
            mixed = _attention(
                (h @ lp["wq"]).reshape(b, t, heads, d),
                (h @ lp["wk"]).reshape(b, t, kv_heads, d),
                (h @ lp["wv"]).reshape(b, t, kv_heads, d),
                config["attention_multiplier"],
            ).reshape(b, t, e) @ lp["wo"]
        x = x + residual * mixed
        h = _rms_norm(x, lp["rms2"], eps)
        return x + residual * (
            (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        )

    return layer


def layers_in_order(runs: dict, layer_types: list):
    """(kind, that layer's parameters) for layers 0, 1, ... out of the
    program's tree: ``runs`` holds one subtree a run of layers of one
    kind within a period (``<i>_<kind>``, in order), each leaf shaped
    [periods, layers in the run, ...]."""
    names = sorted(runs, key=lambda name: int(name.split("_")[0]))
    period = sum(
        jax.tree.leaves(runs[name])[0].shape[1] for name in names
    )
    out = []
    for rep in range(len(layer_types) // period):
        for name in names:
            kind = name.split("_", 1)[1]
            for i in range(jax.tree.leaves(runs[name])[0].shape[1]):
                lp = jax.tree.map(lambda a: a[rep, i], runs[name])
                out.append((kind, lp))
    assert [kind for kind, _ in out] == list(layer_types), (
        "the parameter tree's runs do not spell layer_types"
    )
    return out


def hidden(params, tokens, config: dict):
    """[B, T, E] float32 before the final norm; one jitted call a
    layer on that layer's parameters cast to float32 there."""
    x = jax.jit(
        lambda wte, tok: wte[tok].astype(jnp.float32)
        * config["embedding_multiplier"]
    )(params["wte"], tokens)
    steps = {
        kind: jax.jit(_layer_fn(config, kind)) for kind in set(config["layer_types"])
    }
    for kind, lp in layers_in_order(params["runs"], config["layer_types"]):
        x = steps[kind](x, lp)
    return x


def logits(params, tokens, config: dict):
    """[B, T, V] float32, for the CPU tests."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(
            hidden(params, tokens, config),
            params["rmsf"].astype(jnp.float32), config["rms_norm_eps"],
        )
        return jnp.einsum(
            "bte,ve->btv", x, params["wte"].astype(jnp.float32)
        ) / config["logits_scaling"]


def loss(params, tokens, targets, config: dict):
    eps, scaling = config["rms_norm_eps"], config["logits_scaling"]
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, config)
        return common.mean_over_rows(
            lambda x, tgt, g, table: common.mean_cross_entropy(
                _rms_norm(x, g.astype(jnp.float32), eps) / scaling, table, tgt
            ),
            x, targets, params["rmsf"], params["wte"],
        )
