"""Plain reference for family ``ouro``: the looped language model of
"Scaling Latent Reasoning via Looped Language Models" (Zhu et al.
2025; huggingface ``ByteDance/Ouro-2.6B``, ``modeling_ouro.py``) on
the program's parameter tree, written from the equations and not from
the program. float32, ``default_matmul_precision("highest")``, Python
loops over passes, layers and heads; no ``scan``, no ``custom_vjp``, no
kernel, nothing imported from ``dlrover_tpu``.

**Block** ("sandwich" norms: four RMS norms a layer, one into and one
out of each half)::

    a = x + n2(attn(n1(x)))
    y = a + n4(swiglu(n3(a)))

``attn`` is causal softmax attention on ``num_attention_heads`` heads
of ``hidden_size / num_attention_heads`` (as many key-value heads, no
window, no bias) with rotary positions in huggingface's split-halves
convention (the first half of a head's dimensions is paired with the
second); ``swiglu(h) = (silu(h Wg) * (h Wu)) Wd``; ``n(x) = x /
sqrt(mean(x^2) + eps) * g``.

**Loop**::

    h_0 = embed(ids)
    h_t = norm(stack(h_{t-1}))          t = 1 .. total_ut_steps

``stack`` is the same layers with the **same weights** every pass and
``norm`` the model's final RMS norm, applied at the end of EVERY pass:
its output is both what the head reads at pass ``t`` and what pass
``t + 1`` starts from.

**Exit gate**, one linear map with a bias, ``hidden_size -> 1``, on
``h_t``::

    lambda_t = sigmoid(h_t . w + b)
    p_1 = lambda_1
    p_t = lambda_t * prod_{j<t} (1 - lambda_j)        1 < t < last
    p_last = prod_{j<last} (1 - lambda_j)             the mass left

**Training loss** (the paper's first-stage, entropy-regularised
objective), with ``l_t(i)`` the cross-entropy of ``head(h_t)`` at
position ``i`` and ``H`` the entropy of the exit distribution::

    L = mean_i [ sum_t p_t(i) l_t(i) - beta * H(p(i)) ]

**Departures and choices.** None from the equations above, which are a
reading of the published code and paper from memory (there is no
network here): the order of the two norms of a half (into the branch,
then out of it before the residual add), and the final norm closing
every pass, are as ``modeling_ouro.py`` has them as recalled. ``beta``
is not in ``config.json``; it is the configuration's
``assumed.exit_entropy_coef``. The early exit at inference
(``early_exit_threshold``) is serving's and is not part of a training
loss. The products ``prod (1 - lambda_j)`` are formed as written, in
float32 (the program forms them in logarithms).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


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


@jax.jit
def _one_head(q, k, v):
    """q, k, v [B, T, D] of one head -> [B, T, D]: query i sees keys
    j <= i. One call a head, so that one head's [T, T] float32 scores
    (64 MiB at T=4096) are all that is held at a time."""
    t, d = q.shape[1], q.shape[2]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(t)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    w = jnp.exp(s)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", w, v)


def _attention(q, k, v):
    return jnp.stack(
        [_one_head(q[:, :, i], k[:, :, i], v[:, :, i])
         for i in range(q.shape[2])], axis=2,
    )


def _exit_distribution(lam):
    """lam: one [B, T] array a pass -> the passes' p_t, as many."""
    stay = jnp.ones_like(lam[0])
    p = []
    for lam_t in lam[:-1]:
        p.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    return p + [stay]


def loss(params, tokens, targets, config: dict):
    heads = config["num_attention_heads"]
    if config["num_key_value_heads"] != heads or config.get("sliding_window"):
        raise ValueError("family ouro: full attention, a key-value head a head")
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    beta = config["assumed"]["exit_entropy_coef"]

    @jax.jit
    def into_attention(x, lp):
        b, t, e = x.shape
        h = _rms_norm(x, lp["rms1"], eps)
        split = lambda y: y.reshape(b, t, heads, e // heads)  # noqa: E731
        return (_rope(split(h @ lp["wq"]), theta),
                _rope(split(h @ lp["wk"]), theta), split(h @ lp["wv"]))

    @jax.jit
    def after_attention(x, att, lp):
        b, t, e = x.shape
        a = x + _rms_norm(att.reshape(b, t, e) @ lp["wo"], lp["rms2"], eps)
        h = _rms_norm(a, lp["rms3"], eps)
        mlp = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        return a + _rms_norm(mlp, lp["rms4"], eps)

    @jax.jit
    def pass_end(x, g, gate_w, gate_b, table, tgt):
        """The norm that closes a pass; the gate and the head on it."""
        h = _rms_norm(x, g.astype(jnp.float32), eps)
        lam = jax.nn.sigmoid(
            h @ gate_w.astype(jnp.float32) + gate_b.astype(jnp.float32)[0]
        )
        logits = jnp.einsum("bte,ve->btv", h, table.astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return h, lam, nll

    with jax.default_matmul_precision("highest"):
        total = 0.0
        # One sequence at a time: every row has the same number of
        # tokens, so the mean over positions is the mean of the rows'.
        for r in range(tokens.shape[0]):
            x = params["wte"][tokens[r: r + 1]].astype(jnp.float32)
            lam, nll = [], []
            for _ in range(config["total_ut_steps"]):
                for i in range(config["num_hidden_layers"]):
                    lp = _f32(jax.tree.map(lambda a: a[i], params["blocks"]))
                    x = after_attention(
                        x, _attention(*into_attention(x, lp)), lp
                    )
                x, lam_t, nll_t = pass_end(
                    x, params["rmsf"], params["gate_w"], params["gate_b"],
                    params["lm_head"], targets[r: r + 1],
                )
                lam.append(lam_t)
                nll.append(nll_t)
            p = _exit_distribution(lam)
            expected = sum(p_t * nll_t for p_t, nll_t in zip(p, nll))
            # p log p -> 0 as p -> 0.
            entropy = -sum(
                jnp.where(p_t > 0, p_t * jnp.log(jnp.maximum(p_t, 1e-38)), 0.0)
                for p_t in p
            )
            total = total + jnp.mean(expected - beta * entropy)
        return total / tokens.shape[0]
