"""Plain reference for family ``phi4_flash``: the Phi-4-mini-flash
stack (huggingface ``phi4flash``, microsoft/Phi-4-mini-flash-reasoning's
``config.json``; Ren et al. 2025, arXiv:2507.06607, "SambaY") on the
program's parameter tree, float32 at "highest": the selective scan one
token at a time, attention as explicit [T, T] softmax maps with the
mask written out, a head pair at a time, the memory and the shared
keys and values passed from layer to layer by hand. No chunk, no
kernel, no custom rule, nothing of ``dlrover_tpu``.

For ``n`` published layers (``n % 4 == 0``), layer ``l`` from 0:

    l < n/2, l even:   Mamba              l < n/2, l odd:   window attention
    l = n/2:           Mamba, y -> m      l = n/2 + 1:      full attention, k, v -> K, V
    l >= n/2 + 2 even: GMU on m           l >= n/2 + 2 odd: cross attention on K, V

Every layer, LN a LayerNorm with gain and bias (``layer_norm_eps``):

    h = x + mixer(LN1(x));  out = h + (silu(u W_gate) * (u W_up)) W_down,  u = LN2(h)

and after the last a final LayerNorm, logits on the tied table, the
mean token cross-entropy over the rows held.

Mamba (u [T, E]; Di = expand x E channels, N = d_state, R = dt_rank):

    [xc | z] = u W_in;  xs = silu(conv(xc))   causal, depthwise, width d_conv, bias
    [dr | B | C] = xs W_x;  dt = softplus(dr W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(dt_t[:, None] * A) * s_{t-1} + (dt_t * xs_t)[:, None] * B_t[None, :]
    y_t = s_t C_t + D * xs_t                  from s = 0, ONE TOKEN AT A TIME
    out = (y * silu(z)) W_out;  in layer n/2 the memory is m = y

GMU: ``out = (m * silu(u W_in)) W_out``.

Differential attention (H query heads, G key-value heads of d columns;
pair i holds query heads 2i, 2i + 1 and reads key-value pair j = i //
((H/2) / (G/2)), key heads 2j, 2j + 1, values V = [v_2j | v_2j+1]):

    [q | k | v] = u W_qkv + b      (a cross layer: q alone; k, v are layer n/2 + 1's)
    P1 = softmax(q1 k1^T / sqrt(d) + mask);  P2 = softmax(q2 k2^T / sqrt(d) + mask)
    lam0 = 0.8 - 0.6 exp(-0.3 l)   l the layer's PUBLISHED index
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
    a = P1 V - lam P2 V;  a <- g * a / sqrt(mean(a^2) + eps) * (1 - lam0)   over the 2 d
    out = [a_0 | a_1 | ...] W_o + b_o

The mask is causal; a window layer's query at t sees keys in
``(t - sliding_window, t]``; a cross layer's is the full layer's.

Departures from the published description, all of them:
* The published code evaluates the recurrence in a fused kernel; here
  it is the recurrence itself.
* The published MLP holds ``W_gate`` and ``W_up`` side by side in one
  ``gate_up_proj``; the program's tree holds the two halves.
* No rotation and no position table, as the config has none.
* Where ``num_hidden_layers`` is a slice of the published stack, layer
  i of the file is published layer ``assumed.first_layer + i`` with
  that layer's kind and ``lam0``; where ``vocab_size`` is a slice of
  the table, ids, logits and loss are over the rows held: a smaller
  vocabulary, the same arithmetic.
* A pair's two maps are computed for one pair at a time (a
  ``lax.map``), so that 4,096 tokens fit beside the system's state: a
  loop over the same terms.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families.phi4_flash import layer_kinds  # no JAX there
from benchmark.reference import common

STATE_SPACE = ("mamba", "mamba_memory")


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def recurrence(xs, dt, a, b, c, d):
    """The selective recurrence as written, a token a step. xs, dt
    [B, T, Di]; a [Di, N]; b, c [B, T, N]; d [Di] -> y [B, T, Di]."""

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, Di], [B, Di], [B, N], [B, N]
        state = (
            jnp.exp(dt_t[..., None] * a) * state
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.einsum("bdn,bn->bd", state, c_t) + d * x_t

    state = jnp.zeros((xs.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(
        token, state, [jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, c)]
    )
    return jnp.moveaxis(y, 0, 1)


def _mamba(u, lp):
    """(the mixer's result, the scan's output y)."""
    t = u.shape[1]
    inner, n = lp["A_log"].shape
    rank, width = lp["w_dt"].shape[0], lp["conv_w"].shape[0]
    proj = u @ lp["w_in"]
    xc, z = proj[..., :inner], proj[..., inner:]
    padded = jnp.pad(xc, ((0, 0), (width - 1, 0), (0, 0)))
    xs = jax.nn.silu(lp["conv_b"] + sum(
        padded[:, k:k + t] * lp["conv_w"][k] for k in range(width)
    ))
    dbc = xs @ lp["w_x"]
    y = recurrence(
        xs, jax.nn.softplus(dbc[..., :rank] @ lp["w_dt"] + lp["b_dt"]),
        -jnp.exp(lp["A_log"]), dbc[..., rank:rank + n], dbc[..., rank + n:],
        lp["D"],
    )
    return (y * jax.nn.silu(z)) @ lp["w_out"], y


def _differential(q, k, v, lp, lam0, window, eps):
    """q [B, T, H, d]; k, v [B, T, G, d] -> [B, T, H d]."""
    bsz, t, heads, d = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    group = pairs // kv_pairs
    pos = jnp.arange(t)
    seen = pos[:, None] >= pos[None, :]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    lam = (
        jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]))
        + lam0
    )

    def probabilities(q_h, k_h):
        s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)

    def one_pair(i):
        j = i // group
        head = lambda x, h: jax.lax.dynamic_index_in_dim(x, h, 2, False)
        wide_v = jnp.concatenate(
            [head(v, 2 * j), head(v, 2 * j + 1)], axis=-1
        )
        p1 = probabilities(head(q, 2 * i), head(k, 2 * j))
        p2 = probabilities(head(q, 2 * i + 1), head(k, 2 * j + 1))
        a = jnp.einsum("bqk,bkd->bqd", p1, wide_v) - lam * jnp.einsum(
            "bqk,bkd->bqd", p2, wide_v
        )
        a = a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps)
        return a * lp["subln"] * (1.0 - lam0)

    out = jax.lax.map(one_pair, jnp.arange(pairs))  # [pairs, B, T, 2 d]
    return jnp.transpose(out, (1, 2, 0, 3)).reshape(bsz, t, heads * d)


def _layer_fn(config: dict, kind: str):
    """(x, this layer's parameters, lam0, the memory, the shared keys,
    the shared values) -> (x, what the layer made or None)."""
    heads, groups = config["num_attention_heads"], config["num_key_value_heads"]
    eps = config["layer_norm_eps"]
    window = config["sliding_window"] if kind == "attn_window" else None

    def layer(x, lp, lam0, memory, keys, values):
        lp = common.f32(lp)
        bsz, t, e = x.shape
        d = e // heads
        u = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
        made = None
        if kind in STATE_SPACE:
            mixed, y = _mamba(u, lp)
            made = y if kind == "mamba_memory" else None
        elif kind == "gmu":
            mixed = (memory * jax.nn.silu(u @ lp["w_in"])) @ lp["w_out"]
        else:
            qkv = u @ lp["wqkv"] + lp["bqkv"]
            if kind == "attn_cross":
                q, k, v = qkv, keys, values
            else:
                q = qkv[..., :heads * d]
                k, v = jnp.split(qkv[..., heads * d:], 2, axis=-1)
            if kind == "attn_full":
                made = (k, v)
            mixed = _differential(
                q.reshape(bsz, t, heads, d), k.reshape(bsz, t, groups, d),
                v.reshape(bsz, t, groups, d), lp, lam0, window, eps,
            ) @ lp["wo"] + lp["bo"]
        x = x + mixed
        u = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
        x = x + (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]
        return x, made

    return layer


def layers_in_order(runs: dict, kinds: list):
    """That layer's parameters for layers 0, 1, ... out of the
    program's tree: ``runs`` holds one subtree a run of equal units
    (``<i>_<kind>`` or ``<i>_<kind>__<kind>``, in order), in it one
    subtree a kind of the unit, each leaf shaped [units, ...]."""
    out = []
    for name in sorted(runs, key=lambda name: int(name.split("_")[0])):
        unit = name.split("_", 1)[1].split("__")
        units = jax.tree.leaves(runs[name])[0].shape[0]
        for rep in range(units):
            for kind in unit:
                lp = jax.tree.map(lambda a: a[rep], runs[name][kind])
                out.append((kind, lp))
    assert [kind for kind, _ in out] == list(kinds), (
        "the parameter tree's runs do not spell the layers' kinds"
    )
    return [lp for _, lp in out]


def hidden(params, tokens, config: dict):
    """[B, T, E] float32 before the final norm; one jitted call a
    layer on that layer's parameters cast to float32 there."""
    x = jax.jit(lambda wte, tok: wte[tok].astype(jnp.float32))(
        params["wte"], tokens
    )
    kinds = layer_kinds(config)
    first = config["assumed"]["first_layer"]
    steps = {kind: jax.jit(_layer_fn(config, kind)) for kind in set(kinds)}
    memory = keys = values = None
    for i, (kind, lp) in enumerate(
        zip(kinds, layers_in_order(params["runs"], kinds))
    ):
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * (first + i))
        x, made = steps[kind](x, lp, lam0, memory, keys, values)
        if kind == "mamba_memory":
            memory = made
        elif kind == "attn_full":
            keys, values = made
    return x


def logits(params, tokens, config: dict):
    """[B, T, V] float32, for the CPU tests."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(
            hidden(params, tokens, config),
            params["lnf_g"].astype(jnp.float32),
            params["lnf_b"].astype(jnp.float32), config["layer_norm_eps"],
        )
        return jnp.einsum("bte,ve->btv", x, params["wte"].astype(jnp.float32))


def loss(params, tokens, targets, config: dict):
    eps = config["layer_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, config)
        return common.mean_over_rows(
            lambda x, tgt, g, b, table: common.mean_cross_entropy(
                _layer_norm(x, g.astype(jnp.float32), b.astype(jnp.float32), eps),
                table, tgt,
            ),
            x, targets, params["lnf_g"], params["lnf_b"], params["wte"],
        )
