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
  (``parallel/mesh.per_device``: the expert weights whole on
  every device, their gradients summed over the mesh); the auxiliary
  losses are means over all tokens and stay outside that.
* **An ``expert`` axis larger than 1: one-hot (GShard).** Dispatch
  and combine tensors ``[tokens, experts, capacity]`` and einsums,
  expert weights sharded over ``expert``; GSPMD inserts the
  all-to-alls. Tokens beyond an expert's capacity are dropped (the
  residual carries them), and ``capacity_factor`` belongs to this
  path alone. It stays until a four-chip expert-parallel cell can
  judge a sorted replacement with an explicit all-to-all.

* **A chip's share of the experts (``held`` > 0): held.** Under
  expert parallelism a chip holds ``held`` of the ``n_experts``, the
  experts ``[first_expert, first_expert + held)``. The router keeps
  its ``n_experts`` outputs and its ``top_k`` a token; the layer
  computes the part of the result its own experts give and leaves the
  rest out: no exchange, and no code that stands in for the absent
  chips. Sorted and dropless like the first path, but only the held
  pairs' rows are gathered and brought back, and the products run
  over a buffer sized by the share (``rows_cap``), all of it whatever
  the load (``_held_experts`` says how the shapes stay static). The
  router of such a layer may score by sigmoid, choose with a bias
  that does not enter the weights, scale the weights, and the layer
  may add a shared expert every token passes (``scoring``,
  ``choice_bias``, ``routed_scale``, ``shared_hidden``): what the
  layer is, read from the model's configuration.

``routing_stats`` is a pure function of the router logits for tests
and offline looks; no step calls it (a step returns its loss only, and
a host callback inside one would stall the chip).
"""

from __future__ import annotations

import dataclasses
import functools
from math import comb
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import rows_sum
from dlrover_tpu.parallel.mesh import batch_axes, per_device


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
    # What the router scores with: "softmax" over all experts, or
    # "sigmoid" of each logit on its own.
    scoring: str = "softmax"
    # choice_bias=True: a per-expert bias (``router_bias``, a buffer
    # no gradient reaches) is added to the scores for the top-k choice
    # and left out of the weights.
    choice_bias: bool = False
    # The weights' multiplier after the renormalisation.
    routed_scale: float = 1.0
    # Width of a shared expert every token passes (gated); 0: none.
    shared_hidden: int = 0
    # held > 0: this chip's share, experts [first_expert,
    # first_expert + held) of the router's n_experts.
    first_expert: int = 0
    held: int = 0

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.scoring!r}")
        if self.held and not (
            0 <= self.first_expert
            and self.first_expert + self.held <= self.n_experts
        ):
            raise ValueError(
                f"experts [{self.first_expert}, "
                f"{self.first_expert + self.held}) are not among "
                f"{self.n_experts}"
            )
        if not self.held and (
            self.scoring != "softmax" or self.choice_bias
            or self.routed_scale != 1.0 or self.shared_hidden
        ):
            # The sorted and the one-hot paths know the softmax router
            # and the routed experts alone.
            raise ValueError(
                "scoring, choice_bias, routed_scale and shared_hidden "
                "are the held path's: set held (n_experts for all)"
            )

    @property
    def hidden(self) -> int:
        return self.expert_hidden or 4 * self.n_embd

    @property
    def experts_here(self) -> int:
        """Experts whose matrices this parameter tree holds."""
        return self.held or self.n_experts


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    k_r, k_i, k_o, k_g = jax.random.split(key, 4)
    E, D, H = cfg.experts_here, cfg.n_embd, cfg.hidden
    std = 0.02

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype
        )

    params = {
        # Router stays float32: tiny, and routing decisions are
        # precision-sensitive.
        "router": jax.random.normal(
            k_r, (D, cfg.n_experts), jnp.float32
        ) * std,
        "wi": norm(k_i, (E, D, H)),
        "wo": norm(k_o, (E, H, D)),
    }
    if cfg.gated:
        params["wg"] = norm(k_g, (E, D, H))
    if cfg.choice_bias:
        params["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.shared_hidden:
        k_sg, k_su, k_sd = jax.random.split(jax.random.fold_in(key, 1), 3)
        S = cfg.shared_hidden
        params["shared"] = {
            "w_gate": norm(k_sg, (D, S)),
            "w_up": norm(k_su, (D, S)),
            "w_down": norm(k_sd, (S, D)),
        }
    return params


def moe_logical_axes(
    gated: bool = False, choice_bias: bool = False, shared: bool = False,
) -> Dict[str, Any]:
    axes = {
        "router": (None, None),
        "wi": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }
    if gated:
        axes["wg"] = ("expert", "embed", "mlp")
    if choice_bias:
        axes["router_bias"] = (None,)
    if shared:
        axes["shared"] = {
            "w_gate": ("embed", "mlp"),
            "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed"),
        }
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


def route(
    logits: jax.Array, bias: Optional[jax.Array], cfg: MoEConfig
) -> Tuple[jax.Array, jax.Array]:
    """The layer's router on float32 logits [n, E]: (weights [n, k]
    float32, experts [n, k] int32). Softmax over all experts or a
    sigmoid of each; the ``top_k`` largest scores, of ``score + bias``
    where the layer has a choice bias, which chooses and does not
    weigh: the weights are the chosen experts' scores without it,
    over their sum (``renorm_top_k``; plus 1e-20, as the published
    code guards it) and times ``routed_scale``."""
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if not cfg.choice_bias:
        weights, experts = top_k_route(scores, cfg.top_k, False)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), cfg.top_k
        )
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.renorm_top_k:
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20
        )
    if cfg.routed_scale != 1.0:
        weights = weights * cfg.routed_scale
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


def routing_stats(
    logits: jax.Array, top_k: int, cfg: Optional[MoEConfig] = None,
    bias: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """What a routing looks like, from the router logits [n, E] alone:
    the fullest expert's load over the mean load, the share of experts
    that received nothing, the share of (token, choice) pairs dropped
    (0: the sorted path drops none) and, of a layer that holds a share
    of the experts (``cfg.held``), the pairs a token sends to those
    (``top_k x held / n_experts`` at even load) and the ``rows_cap``
    blocks the held path then runs (at least 1). ``cfg`` and ``bias``
    give the layer's scoring and choice bias; else a softmax router."""
    n, n_experts = logits.shape
    if cfg is None:
        _, experts = top_k_route(jax.nn.softmax(logits, axis=-1), top_k, False)
        first, held, cap = 0, n_experts, n * top_k
    else:
        _, experts = route(logits, bias, cfg)
        first, held, cap = cfg.first_expert, cfg.experts_here, rows_cap(n, cfg)
    counts = expert_counts(experts, n_experts)
    here = jnp.sum(counts[first: first + held])
    return {
        "tokens_per_expert": counts,
        "max_over_mean": jnp.max(counts) / jnp.mean(counts.astype(jnp.float32)),
        "empty_share": jnp.mean((counts == 0).astype(jnp.float32)),
        "dropped_share": jnp.zeros((), jnp.float32),
        "held_pairs_per_token": here / n,
        "held_row_blocks": jnp.maximum(1, -(-here // cap)),
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


# ---------------------------------------------------------------------------
# Held path: a chip's share of the experts, sorted and dropless
# ---------------------------------------------------------------------------

# Rows of the held path's buffer. Shapes are static and the held rows
# are not: a token's choices can all be held, so the only bound that
# never fails is ``tokens x top_k``. The layer is both: the held
# pairs' rows, sorted by expert, are taken ``rows_cap`` at a time by a
# ``lax.scan`` over the ``tokens x top_k / rows_cap`` blocks there can
# be, and a block past the counted rows (but the first, which always
# runs) is skipped by a ``lax.cond``: one block's program, forward and
# backward, in the one step program. What a step pays follows its load
# in blocks and in nothing finer: the common case is the first block
# alone, and a layer whose held pairs pass the buffer pays for one
# more buffer, not for every pair of the layer. Within a block the
# grouped products walk the whole buffer (``_held_block`` gives the
# rows past the held pairs, zeros, to the last group), so a layer up
# to its buffer costs the same whatever the router sent it, and a
# step's time does not follow the router (with tiles past the count
# skipped a step read 637 ms to 647 by how many layers' routers had
# collapsed onto a held expert: PERF.md section 6, PR 53).
# The rows are the tokens times h*, the number of a token's ``top_k``
# choices that are held here in all but ``ROWS_CAP_TAIL`` of the draws
# (``covered_choices``, below, has the law and why a load comes in
# whole tokens' worth): the same tail at every share from one number,
# and no constant multiple of the mean load. 8 of 256
# held, 8 a token: h* = 1, 8,192 rows for 8,192 tokens, 4 x the mean;
# 16 of 64: h* = 4, 32,768 rows, 2 x the mean, two blocks of which the
# second is behind the ``lax.cond`` (4 x the mean was every pair of
# that layer: PERF.md section 6, PR 58); every expert: every pair.
ROWS_CAP_TAIL = 1 / 40

# What the records say about the held load is that it comes in whole
# tokens' worth. A frequent token sends all its copies the same way
# (the benchmark stream's most frequent token is 17 to 19% of a
# sequence), and a router that no balancing step holds even sends
# EVERY token the same way within five steps (PERF.md section 6, PR
# 53), so a layer's held pairs are ``tokens x h``, h the number of the
# ``top_k`` chosen experts that this chip holds. For a choice that
# knows nothing of the share, h follows the hypergeometric law of
# ``top_k`` drawn of ``n_experts`` with ``held`` marked, and the buffer
# has rows for the smallest h >= 1 that all but ``ROWS_CAP_TAIL`` of
# the draws stay within; a layer past it runs one more block. At 8 of
# 256, 8 a token: P(h > 0) = 0.227, P(h > 1) = 0.0218, so h* = 1 (the
# 8,192 rows PR 53's third session chose by measurement: at 4,096 a
# collapsed layer paid a second block in every step). At 16 of 64:
# P(h > 3) = 0.099, P(h > 4) = 0.0192, so h* = 4. At ``held ==
# n_experts`` h is always ``top_k``.


def covered_choices(cfg: MoEConfig) -> Tuple[int, float]:
    """(h*, the tail it leaves): of a token's ``top_k`` choices, how
    many held here the buffer has rows for, and the probability that
    ``top_k`` experts drawn of ``n_experts`` hold more than that many
    of this chip's ``experts_here``."""
    E, H, k = cfg.n_experts, cfg.experts_here, cfg.top_k
    p = [comb(H, h) * comb(E - H, k - h) / comb(E, k) for h in range(k + 1)]
    tails = ((h, sum(p[h + 1:])) for h in range(1, k + 1))
    return next((h, tail) for h, tail in tails if tail <= ROWS_CAP_TAIL)


def rows_cap(n: int, cfg: MoEConfig) -> int:
    """Rows of the held path's buffer for ``n`` tokens on a device:
    ``n x h*`` (``covered_choices``) up to a multiple of 16 (bf16's
    sublane tile), at most ``n x top_k``."""
    cap = -(-n * covered_choices(cfg)[0] // 16) * 16
    return max(16, min(cap, n * cfg.top_k))


# The two moves between token order and the held rows, each the other's
# transpose, and neither touches a row of an absent expert. tokens ->
# rows is a gather, one token a row. rows -> tokens is one kernel
# (ops/rows_sum.py, ``moe_rows_sum``): the rows are sorted by expert and
# ascend by token inside a group, where a token stands at most once, so
# a tile of tokens has one range of rows a group (``plan["visits"]``)
# and picks its rows out of that range's chunks by a 0/1 product.


@jax.custom_vjp
def _rows_of_tokens(flat, plan):
    """flat [n, D] -> [cap, D]: row r is the token of the r-th held
    pair in expert order; rows past the held pairs are zero."""
    token, live = plan["token"], plan["live"]
    return jnp.where(live[:, None], flat[token], jnp.zeros((), flat.dtype))


def _rows_of_tokens_fwd(flat, plan):
    return _rows_of_tokens(flat, plan), (plan, flat.shape[0])


def _rows_of_tokens_bwd(res, g):
    plan, n = res
    with jax.named_scope("moe_route"):
        return _tokens_of_rows(g, None, plan, n).astype(g.dtype), None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _tokens_of_rows(rows, weight, plan, n, grad_dtype=None):
    """rows [cap, D], weight [cap] float32 or None -> [n, D] float32:
    each token's rows, times their weights, summed in float32; zero
    for a token with none. The backward gathers the cotangent in
    ``grad_dtype``, the dtype the caller holds these sums in."""
    return rows_sum.rows_sum(rows, plan["token"], weight, plan["visits"], n)


def _tokens_of_rows_fwd(rows, weight, plan, n, grad_dtype=None):
    res = (rows, weight, plan)
    return _tokens_of_rows(*res, n, grad_dtype), res


def _tokens_of_rows_bwd(n, grad_dtype, res, g):
    rows, weight, plan = res
    with jax.named_scope("moe_combine"):
        back = _rows_of_tokens(g.astype(grad_dtype or g.dtype), plan)
        if weight is None:
            return back.astype(rows.dtype), None, None
        return (  # float32 products, whatever ``back`` is held in
            (back * weight[:, None]).astype(rows.dtype),
            jnp.sum(back * rows.astype(jnp.float32), axis=1),
            None,
        )


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


@jax.custom_vjp
def _row_weights(weights, plan):
    """weights [n, k] -> [cap]: the weight of each row's pair, zero
    past the held pairs; backward a gather too, by the pairs' rows."""
    taken = weights.reshape(-1)[plan["order"]]
    return jnp.where(plan["live"], taken, 0.0)


def _row_weights_fwd(weights, plan):
    return _row_weights(weights, plan), (plan, weights.shape)


def _row_weights_bwd(res, g):
    plan, shape = res
    g = jnp.where(plan["live"], g, 0.0)
    back = jnp.where(plan["pair_here"], g[plan["row_of_pair"]], 0.0)
    return back.reshape(shape), None


_row_weights.defvjp(_row_weights_fwd, _row_weights_bwd)


def _held_order(local, held: int) -> Dict[str, Any]:
    """Where the held pairs stand, once a layer (int32 and bool
    vectors, no gradient). local [n, k] int32: a pair's expert among
    the held ones, ``held`` for an absent one. The pairs are sorted by
    that (stable): the held experts' first, expert by expert, every
    absent pair in one trailing group no product touches."""
    n, k = local.shape
    pairs = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort(
        (local.reshape(n * k), pairs), num_keys=1, is_stable=True
    )
    _, row_of_pair = jax.lax.sort((order, pairs), num_keys=1)
    ends = jnp.cumsum(expert_counts(local, held + 1)[:held])
    return {
        "order": order, "row_of_pair": row_of_pair, "ends": ends,
        "pair_held": (local < held).reshape(n * k),
        "before": rows_sum.pairs_before(local, held),
    }


def _block_plan(whole, j, n: int, k: int, cap: int) -> Dict[str, Any]:
    """Block ``j`` of that order, rows ``[j x cap, (j + 1) x cap)``:
    the buffer's rows, the part of every expert's group that falls
    among them, and of that part the rows of each tile of tokens."""
    lo = j * cap
    ends = whole["ends"]
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    window = lambda x: jnp.clip(x, lo, lo + cap)
    live = lo + jnp.arange(cap, dtype=jnp.int32) < ends[-1]
    padded = jnp.pad(whole["order"], (0, -(n * k) % cap))
    order = jax.lax.dynamic_slice(padded, (lo,), (cap,))
    token = order // k
    at = whole["row_of_pair"] - lo
    pair_here = whole["pair_held"] & (at >= 0) & (at < cap)
    # Where each tile of tokens' rows start in this block, by expert:
    # the expert's start in the whole order plus its pairs of the tokens
    # before the tile, cut to the block; consecutive edges are a window.
    edges = window(starts[None, :] + whole["before"]) - lo
    return {
        "order": order, "token": token, "live": live,
        "group_sizes": window(ends) - window(starts),
        "visits": rows_sum.visits(edges[:-1], edges[1:], cap),
        "pair_here": pair_here,
        "row_of_pair": jnp.clip(at, 0, cap - 1),
    }


def _held_block(plan, flat, weights, wi, wo, wg, dtype=None):
    """One device's tokens through the experts held here, for the
    held pairs of one block. flat [n, D], weights [n, k] -> [n, D]
    float32, which the caller holds in ``dtype``."""
    from dlrover_tpu.ops.grouped_matmul import gmm

    with jax.named_scope("moe_route"):
        # The rows past the held pairs (zeros) go to the last expert's
        # group, so the grouped products walk every tile of the buffer
        # whatever the load: a zero row gives a zero row, forward and
        # backward, and the block's time is the buffer's, not the
        # count's (the comment above ``ROWS_CAP_TAIL`` says why).
        sizes = plan["group_sizes"]
        sizes = sizes.at[-1].add(plan["live"].shape[0] - jnp.sum(sizes))
        row_weight = _row_weights(weights, plan)
        xs = _rows_of_tokens(flat, plan)
    with jax.named_scope("moe_experts"):
        h = gmm(xs, wi, sizes)
        if wg is not None:
            g = gmm(xs, wg, sizes)
            h = (jax.nn.silu(g.astype(jnp.float32)) * h).astype(xs.dtype)
        else:
            h = jax.nn.gelu(h.astype(jnp.float32)).astype(xs.dtype)
        out = gmm(h, wo, sizes)
    with jax.named_scope("moe_combine"):
        return _tokens_of_rows(out, row_weight, plan, flat.shape[0], dtype)


def _held_experts(flat, local, weights, *matrices, held, cap, dtype=None):
    """One device's tokens through the experts held here: dropless
    whatever the load, summed in float32 and handed on in ``dtype``
    (float32 if none). ``matrices``: wi, wo and, of a gated layer, wg.
    A scan over the blocks of ``cap`` sorted rows there can be, each
    after the first behind a ``lax.cond`` on the counted rows; the
    backward takes from the forward its operands alone and forms each
    block it needs again (the rows are 1/32 of the pairs at the
    published share, and a value kept inside a ``lax.cond`` branch
    would be written, as zeros, by the other too), so nothing is
    stacked over the blocks."""
    n, k = local.shape
    blocks = -(-n * k // cap)

    def block(whole, j, flat, weights, wi, wo, wg=None):
        with jax.named_scope("moe_route"):
            plan = _block_plan(whole, j, n, k, cap)
        return _held_block(plan, flat, weights, wi, wo, wg, dtype)

    def over_blocks(local, add_block, start):
        """``start`` plus ``add_block(whole, j)`` of every block that
        holds a row."""
        with jax.named_scope("moe_route"):
            whole = _held_order(local, held)

        def step(total, j):
            # The first block always runs: under a balanced load it
            # always holds rows, and a step that skipped it would be
            # one no deployment sees (a router that no balancing step
            # holds even turns away from a share's experts within tens
            # of steps: PERF.md section 6, PR 53).
            return jax.lax.cond(
                (j == 0) | (j * cap < whole["ends"][-1]),
                lambda total: jax.tree.map(
                    jnp.add, total, add_block(whole, j)
                ),
                lambda total: total,
                total,
            ), None

        return jax.lax.scan(
            step, start, jnp.arange(blocks, dtype=jnp.int32)
        )[0]

    @jax.custom_vjp
    def run(flat, local, weights, *matrices):
        return over_blocks(
            local,
            lambda whole, j: block(whole, j, flat, weights, *matrices),
            jnp.zeros(flat.shape, jnp.float32),
        ).astype(dtype or jnp.float32)

    def run_fwd(*operands):
        return run(*operands), operands

    def run_bwd(operands, g):
        flat, local, *rest = operands

        def grads(whole, j):
            _, pull = jax.vjp(
                lambda flat, *rest: block(whole, j, flat, *rest), flat, *rest
            )
            return pull(g.astype(jnp.float32))

        d_flat, *d_rest = over_blocks(
            local, grads, jax.tree.map(jnp.zeros_like, (flat, *rest))
        )
        return (d_flat, None, *d_rest)

    run.defvjp(run_fwd, run_bwd)
    return run(flat, local, weights, *matrices)


def _held_moe(params, flat, logits, cfg: MoEConfig):
    """flat [n, D] -> y likewise: the part the experts held here give."""
    from dlrover_tpu import obs

    held = cfg.experts_here
    _, n_here = batch_axes(flat.shape[0])
    cap, (choices, tail) = rows_cap(n_here, cfg), covered_choices(cfg)
    sizes = rows_sum.layout(n_here, cap, held)
    obs.event(
        "moe.held", router_experts=cfg.n_experts, scoring=cfg.scoring,
        first_expert=cfg.first_expert, held=held, top_k=cfg.top_k,
        rows_cap=cap, tokens=n_here, row_blocks=-(-n_here * cfg.top_k // cap),
        cap_over_mean=cap * cfg.n_experts / (n_here * cfg.top_k * held),
        covered_choices=choices, tail=tail,
        sum_tile=sizes["tile"], sum_chunk_visits=sizes["visits"],
    )
    with jax.named_scope("moe_route"):
        weights, experts = route(logits, params.get("router_bias"), cfg)
        local = experts - cfg.first_expert
        local = jnp.where((local >= 0) & (local < held), local, held)
    operands = [flat.astype(cfg.dtype), local, weights,
                params["wi"], params["wo"]]
    if cfg.gated:
        operands.append(params["wg"])

    return per_device(
        functools.partial(_held_experts, held=held, cap=cap, dtype=flat.dtype),
        *operands,
        split=(True, True, True) + (False,) * (len(operands) - 3),
    )


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
    if cfg.held:
        # A chip's share of the experts (all of them, at ``held ==
        # n_experts``). No auxiliary loss: such a router is balanced by
        # its bias, outside the step.
        with jax.named_scope("moe_routed"):
            y = _held_moe(params, flat, logits, cfg)
        y = y.reshape(B, T, D).astype(x.dtype)
        if cfg.shared_hidden:
            from dlrover_tpu.models.llama import swiglu

            with jax.named_scope("moe_shared"):
                y = y + swiglu(x, params["shared"])
        return y, jnp.zeros((), jnp.float32)
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
