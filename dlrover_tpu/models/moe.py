"""Mixture-of-experts feed-forward: routed by sort and dropless on
every mesh without an ``expert`` axis, GShard one-hot under one.

``moe_mlp`` is the drop-in for a block's dense MLP. The router is the
same on both paths (float32 logits, softmax over all experts, the
``top_k`` largest, renormalised over the kept ones or not), and so are
the two auxiliary losses: the load-balancing loss over **all** top-k
choices (Switch Transformer; huggingface ``load_balancing_loss_func``,
which Mixtral and OLMoE share) and the router z-loss. What differs is
how tokens reach their experts, and the mesh the trace is under
chooses it, not an option:

* **No ``expert`` axis (one chip, ``data``, ``fsdp``): sorted.** The
  ``tokens x top_k`` (token, choice) pairs are sorted by expert
  (stable), the tokens gathered into that order, the experts applied
  as grouped matrix products over the ragged groups
  (``ops/grouped_matmul.py``), the rows brought back by the inverse
  permutation and summed over the choices with their weights. No
  capacity, so no token is ever dropped, and the work is the
  ``top_k`` experts' a token, whatever ``n_experts`` is. Routing is
  per token, so under a mesh each device sorts its own shard's tokens
  (``ops/flash_attention.per_device``: the expert weights whole on
  every device, their gradients summed over the mesh); the auxiliary
  losses are means over all tokens and stay outside that.
* **An ``expert`` axis larger than 1: one-hot (GShard).** Dispatch
  and combine tensors ``[tokens, experts, capacity]`` and einsums,
  expert weights sharded over ``expert``; GSPMD inserts the
  all-to-alls. Tokens beyond an expert's capacity are dropped (the
  residual carries them), and ``capacity_factor`` belongs to this
  path alone. It stays until a four-chip expert-parallel cell can
  judge a sorted replacement with an explicit all-to-all.

``routing_stats`` is a pure function of the router logits for tests
and offline looks; no step calls it (a step returns its loss only, and
a host callback inside one would stall the chip).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_embd: int
    n_experts: int = 8
    expert_hidden: int = 0  # 0 -> 4 * n_embd
    top_k: int = 2
    # The one-hot path's alone: slots an expert has for its tokens,
    # over the even share. The sorted path has no capacity.
    capacity_factor: float = 1.25
    # loss weights (GShard defaults; OLMoE trains with the same two)
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    dtype: Any = jnp.bfloat16
    # gated=True: experts are SwiGLU (w_gate/w_in/w_out) — the
    # Mixtral expert shape — instead of the 2-matmul GELU FFN.
    gated: bool = False
    # renorm_top_k=True: combine weights are renormalized over the
    # token's kept choices (Mixtral's softmax-over-top-k) instead of
    # the raw full-softmax probabilities (GShard, OLMoE).
    renorm_top_k: bool = False

    @property
    def hidden(self) -> int:
        return self.expert_hidden or 4 * self.n_embd


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    k_r, k_i, k_o, k_g = jax.random.split(key, 4)
    E, D, H = cfg.n_experts, cfg.n_embd, cfg.hidden
    std = 0.02

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype
        )

    params = {
        # Router stays float32: tiny, and routing decisions are
        # precision-sensitive.
        "router": jax.random.normal(k_r, (D, E), jnp.float32) * std,
        "wi": norm(k_i, (E, D, H)),
        "wo": norm(k_o, (E, H, D)),
    }
    if cfg.gated:
        params["wg"] = norm(k_g, (E, D, H))
    return params


def moe_logical_axes(
    gated: bool = False,
) -> Dict[str, Tuple[Optional[str], ...]]:
    axes = {
        "router": (None, None),
        "wi": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }
    if gated:
        axes["wg"] = ("expert", "embed", "mlp")
    return axes


# ---------------------------------------------------------------------------
# The router, shared by both paths
# ---------------------------------------------------------------------------


def router_logits(flat: jax.Array, router: jax.Array) -> jax.Array:
    """[n, D] activations -> [n, E] float32 logits. A float32 product
    in fact: on a TPU the default precision would round both sides to
    bf16 first, and a top-k choice flips on the last bits."""
    return jnp.dot(
        flat.astype(jnp.float32), router,
        precision=jax.lax.Precision.HIGHEST,
    )


def top_k_route(
    probs: jax.Array, top_k: int, renorm: bool
) -> Tuple[jax.Array, jax.Array]:
    """The ``top_k`` largest of each row of ``probs`` [n, E]: their
    weights [n, k] float32, renormalised to sum to 1 or as they are,
    and their experts [n, k] int32, the largest first."""
    weights, experts = jax.lax.top_k(probs, top_k)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def expert_counts(experts: jax.Array, n_experts: int) -> jax.Array:
    """(token, choice) pairs each expert received, [E] int32."""
    return jnp.sum(
        jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
        axis=tuple(range(experts.ndim)),
    )


def router_losses(
    logits: jax.Array, probs: jax.Array, counts: jax.Array
) -> Dict[str, jax.Array]:
    """``aux_loss``: experts x sum over experts of (pairs the expert
    received over the number of tokens) x (its mean router
    probability): huggingface's ``load_balancing_loss_func`` on one
    layer, every one of the top-k choices counted (1 x top_k when the
    load is even). ``z_loss``: mean squared log-sum-exp of the
    logits."""
    n, n_experts = logits.shape
    share = counts.astype(jnp.float32) / n
    return {
        "aux_loss": n_experts * jnp.sum(share * jnp.mean(probs, axis=0)),
        "z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }


def routing_stats(logits: jax.Array, top_k: int) -> Dict[str, jax.Array]:
    """What a routing looks like, from the router logits [n, E] alone:
    the fullest expert's load over the mean load, the share of experts
    that received nothing, and the share of (token, choice) pairs the
    sorted path drops, which is 0 by construction."""
    n_experts = logits.shape[-1]
    _, experts = top_k_route(jax.nn.softmax(logits, axis=-1), top_k, False)
    counts = expert_counts(experts, n_experts)
    return {
        "tokens_per_expert": counts,
        "max_over_mean": jnp.max(counts) / jnp.mean(counts.astype(jnp.float32)),
        "empty_share": jnp.mean((counts == 0).astype(jnp.float32)),
        "dropped_share": jnp.zeros((), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Sorted path: no capacity, no dropped token
# ---------------------------------------------------------------------------


# The two permutations are gathers forward AND backward: a row of the
# sorted order has exactly one (token, choice) pair and the reverse,
# so each one's gradient is the other index list's gather. Autodiff
# of a gather would give a scatter-add over 2048-wide rows instead.


@jax.custom_vjp
def _to_expert_order(flat, order, inverse):
    """flat [n, D] -> [n * k, D]: row r is the token of the r-th
    (token, choice) pair in expert order."""
    return flat[order // (order.shape[0] // flat.shape[0])]


def _to_expert_order_fwd(flat, order, inverse):
    return _to_expert_order(flat, order, inverse), (inverse, flat.shape[0])


def _to_expert_order_bwd(res, g):
    inverse, n = res
    with jax.named_scope("moe_route"):
        back = g[inverse].reshape(n, -1, g.shape[-1])
        d_flat = jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype)
    return d_flat, None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@jax.custom_vjp
def _to_token_order(rows, order, inverse):
    """rows [n * k, D] in expert order -> the same rows in (token,
    choice) order."""
    return rows[inverse]


def _to_token_order_fwd(rows, order, inverse):
    return rows[inverse], order


def _to_token_order_bwd(order, g):
    with jax.named_scope("moe_combine"):
        return g[order], None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


def _sorted_experts(flat, experts, weights, wi, wo, wg, *, n_experts):
    """One device's tokens through their experts. flat [n, D],
    experts / weights [n, k] -> [n, D] float32."""
    from dlrover_tpu.accelerate.remat import (
        MLP_HIDDEN, MOE_IN, MOE_ORDER, MOE_OUT, keep,
    )
    from dlrover_tpu.ops.grouped_matmul import gmm

    # Under remat="full" the layer keeps, by name, what its backward
    # takes from its forward, each value named where it flows into a
    # ``custom_vjp``'s residuals: the three index vectors, the rows in
    # expert order (``gmm``'s ``lhs`` for two weight gradients), the
    # up and gate products and the down product's rows back in token
    # order. Neither sort, no gather and no grouped product then runs
    # a second time; the activation does, one elementwise pass.
    n, d = flat.shape
    k = experts.shape[1]
    with jax.named_scope("moe_route"):
        pair_expert = experts.reshape(n * k)
        pairs = jnp.arange(n * k, dtype=jnp.int32)
        # order[r]: which pair stands at row r of the expert order;
        # inverse[p]: at which row pair p stands.
        _, order = jax.lax.sort((pair_expert, pairs), num_keys=1, is_stable=True)
        _, inverse = jax.lax.sort((order, pairs), num_keys=1)
        order, inverse = keep(order, MOE_ORDER), keep(inverse, MOE_ORDER)
        group_sizes = keep(expert_counts(experts, n_experts), MOE_ORDER)
        xs = keep(_to_expert_order(flat, order, inverse), MOE_IN)
    with jax.named_scope("moe_experts"):
        # Grouped products over the ragged groups: Pallas kernels
        # ``moe_gmm`` (and, backward, ``moe_tgmm``). They measured 1.4
        # to 1.75 times XLA's own ``jax.lax.ragged_dot`` at 131,072
        # rows in 64 groups on a v5e (PERF.md, PR 26).
        h = keep(gmm(xs, wi, group_sizes), MLP_HIDDEN)
        if wg is not None:
            g = keep(gmm(xs, wg, group_sizes), MLP_HIDDEN)
            h = (jax.nn.silu(g.astype(jnp.float32)) * h).astype(xs.dtype)
        else:
            h = jax.nn.gelu(h.astype(jnp.float32)).astype(xs.dtype)
        out = gmm(h, wo, group_sizes)
    with jax.named_scope("moe_combine"):
        back = keep(_to_token_order(out, order, inverse), MOE_OUT)
        return jnp.einsum(
            "nk,nkd->nd", weights,
            back.reshape(n, k, d).astype(jnp.float32),
        )


# ---------------------------------------------------------------------------
# One-hot path (GShard), under an ``expert`` mesh axis
# ---------------------------------------------------------------------------


def _gating(
    logits: jax.Array,  # [n, E] float32
    top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (dispatch [n,E,C] bool, combine [n,E,C] f32, metrics).

    GShard-style: for each of the k choices in order, tokens claim
    expert capacity slots by cumulative position; overflowing tokens
    are dropped for that choice (residual path carries them).
    """
    n, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((n, E, capacity), jnp.bool_)
    combine = jnp.zeros((n, E, capacity), jnp.float32)
    # slots already taken per expert by earlier choices
    fill = jnp.zeros((E,), jnp.int32)
    masked_logits = logits
    # (token, choice) pairs per expert, kept or dropped: the
    # load-balancing loss counts the router's choices
    counts = jnp.zeros((E,), jnp.int32)

    for choice in range(top_k):
        idx = jnp.argmax(masked_logits, axis=-1)  # [n]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [n, E]
        counts = counts + jnp.sum(onehot, axis=0)
        # position of each token within its chosen expert's queue
        pos_in_expert = (
            jnp.cumsum(onehot, axis=0) - onehot
        ) * onehot  # [n, E]
        pos = jnp.sum(pos_in_expert, axis=-1) + fill[idx]  # [n]
        keep = pos < capacity
        gate = jnp.sum(probs * onehot, axis=-1) * keep  # [n]
        slot = jax.nn.one_hot(
            jnp.where(keep, pos, capacity), capacity + 1, dtype=jnp.float32
        )[:, :capacity]  # [n, C] (dropped tokens -> all-zero row)
        d = onehot[:, :, None].astype(jnp.float32) * slot[:, None, :]
        dispatch = jnp.logical_or(dispatch, d > 0)
        combine = combine + gate[:, None, None] * d
        fill = fill + jnp.sum(
            onehot * keep[:, None].astype(jnp.int32), axis=0
        )
        # mask this choice out for the next round
        masked_logits = jnp.where(onehot > 0, -1e30, masked_logits)

    metrics = router_losses(logits, probs, counts)
    metrics["dropped_fraction"] = 1.0 - jnp.sum(combine > 0) / (n * top_k)
    return dispatch, combine, metrics


def top_k_gating(logits, top_k, capacity):
    return _gating(logits, top_k, capacity)


def switch_gating(logits, capacity):
    """Top-1 Switch-Transformer routing (ref switch_gating.py)."""
    return _gating(logits, 1, capacity)


def _onehot_moe(params, flat, logits, cfg: MoEConfig):
    """flat [n, D] -> (y [n, D] float32, router losses)."""
    n = flat.shape[0]
    capacity = int(
        np.ceil(cfg.capacity_factor * cfg.top_k * n / cfg.n_experts)
    )
    dispatch, combine, metrics = _gating(logits, cfg.top_k, capacity)
    if cfg.renorm_top_k:
        # Mixtral semantics: weights renormalized over the token's
        # kept choices (== softmax over the top-k logits when no
        # capacity drop occurs).
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)

    # dispatch tokens to expert buffers: [E, C, D]
    buf = jnp.einsum(
        "nec,nd->ecd",
        dispatch.astype(cfg.dtype),
        flat.astype(cfg.dtype),
    )
    # expert FFN, batched over the (sharded) expert dim
    h = jnp.einsum(
        "ecd,edh->ech", buf, params["wi"],
        preferred_element_type=jnp.float32,
    )
    if cfg.gated:
        g = jnp.einsum(
            "ecd,edh->ech", buf, params["wg"],
            preferred_element_type=jnp.float32,
        )
        h = (jax.nn.silu(g) * h).astype(cfg.dtype)
    else:
        h = jax.nn.gelu(h).astype(cfg.dtype)
    out = jnp.einsum(
        "ech,ehd->ecd", h, params["wo"],
        preferred_element_type=jnp.float32,
    )
    # combine back, weighted by gates
    y = jnp.einsum("nec,ecd->nd", combine, out.astype(jnp.float32))
    return y, metrics


def _sorted_moe(params, flat, logits, cfg: MoEConfig):
    """flat [n, D] -> (y [n, D] float32, router losses)."""
    from dlrover_tpu.ops.flash_attention import per_device

    with jax.named_scope("moe_route"):
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = top_k_route(probs, cfg.top_k, cfg.renorm_top_k)
        metrics = router_losses(
            logits, probs, expert_counts(experts, cfg.n_experts)
        )
    gated = cfg.gated
    operands = [flat.astype(cfg.dtype), experts, weights,
                params["wi"], params["wo"]]
    if gated:
        operands.append(params["wg"])

    def call(flat, experts, weights, wi, wo, wg=None):
        return _sorted_experts(
            flat, experts, weights, wi, wo, wg, n_experts=cfg.n_experts
        )

    # A call of its own (``jax.jit``) for what ``jax.checkpoint`` does
    # to a kept value: where the block's own equations consume one, it
    # rounds it once more (``reduce_precision``, against XLA's excess
    # precision between a forward and its recompute). What the layer
    # keeps is bf16 in memory as a Pallas call or a gather wrote it,
    # neither of which the chip fuses a rounding into, so each was a
    # pass of its own over every row (four, 4.9 ms of OLMoE's step). A
    # kept value that leaves a call is left as it is, as inside the
    # mesh path's ``shard_map``. Made here, once a trace, so that
    # every trace of a block runs ``keep`` and says what it named.
    y = per_device(
        jax.jit(call), *operands,
        split=(True, True, True) + (False,) * (len(operands) - 3),
    )
    return y, metrics


def moe_mlp(
    params: Dict[str, Any],
    x: jax.Array,  # [B, T, D]
    cfg: MoEConfig,
) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward. Returns (y [B,T,D], aux_loss scalar).

    Drop-in for the dense MLP of a transformer block: add aux_loss
    (already weighted) to the training loss.
    """
    from dlrover_tpu.accelerate.remat import ROUTER_LOGITS, keep

    B, T, D = x.shape
    flat = x.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        # Kept under remat="full", as the policy by primitive type
        # kept them: [n, E] float32 is small, and the top-k choice is
        # then the forward's own. What the sorted path keeps besides
        # is named in ``_sorted_experts``.
        logits = keep(
            router_logits(flat, params["router"]), ROUTER_LOGITS
        )  # [n, E]
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.shape.get("expert", 1) > 1:
        y, metrics = _onehot_moe(params, flat, logits, cfg)
    else:
        y, metrics = _sorted_moe(params, flat, logits, cfg)
    aux = (
        cfg.aux_loss_weight * metrics["aux_loss"]
        + cfg.z_loss_weight * metrics["z_loss"]
    )
    return y.reshape(B, T, D).astype(x.dtype), aux
