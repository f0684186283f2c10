"""Named rematerialization / offload policies.

Capability parity with the reference's selective offloading checkpoint
(atorch/auto/opt_lib/selective_offloading_checkpoint.py — choose per
layer which activations to keep, recompute, or push to host memory)
expressed the TPU way: ``jax.checkpoint`` policies. XLA already fuses
and schedules the recompute; the policy just declares which residuals
are worth HBM, and ``save_and_offload_only_these_names`` streams named
residuals to pinned host memory instead of either keeping or
recomputing them — the third point of the reference's tradeoff.

Policies (cfg.remat / Strategy.remat accept these names):

  "none"       keep every residual (fastest, most HBM)
  "full"       recompute blocks; keep what the block names (KEPT):
               the projections into attention, the flash forward's
               (o, lse), the MLP's hidden products, of a sorted
               expert layer the router's logits, the sorted order,
               the rows into and out of the experts and their hidden
               products, and of a state-space mixer its projection,
               the scan's output and the chunk states; what a layer
               makes for later layers to read (a memory, shared keys
               and values)
  "attention"  recompute only attention internals
  "dots"       recompute everything except matmul outputs
  "offload"    offload block-boundary residuals (checkpoint_name
               "block_out") to pinned host memory, save nothing else

Booleans keep working: True == "full", False == "none".

What "full" keeps is chosen by name, not by primitive type, because a
policy by type cannot see inside the flash ``custom_vjp`` (it kept
none of the kernel's outputs, so the backward ran the forward kernel
a second time) and cannot leave one product out. With names the
block keeps the flash forward's ``o`` IN PLACE OF the out-projection's
output ``att @ wo``: the same ``[B, T, E]`` bytes a layer, and the
backward recomputes one ``E x E`` product a token instead of the
whole attention forward. On XLA attention there is no ``flash_o``:
attention is recomputed as before, and ``att @ wo`` with it.

The sorted expert layer (models/moe.py) is the same case three times
over: its grouped products and both its permutations are
``custom_vjp``s, so whatever their backward rules hold has to be
named where it enters them, or the backward sorts, gathers and
multiplies the experts a second time (three of nine ``moe_gmm`` calls
and two of six row gathers a layer, before the names). It runs in a
call of its own, so that ``jax.checkpoint`` does not round what it
keeps a second time (models/moe._sorted_moe says why).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

import jax
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu import obs

# residual name tagged at each transformer block boundary (models
# call jax.ad_checkpoint.checkpoint_name on the block output)
BLOCK_OUT = "block_out"

# What "full" keeps of a block, by the names the block gives them
# (:func:`keep`): the projections into attention (GPT's ``qkv``;
# Llama's ``q``, ``k``, ``v`` before the head repeat), the flash
# forward's output and its row logsumexp (ops/flash_attention._kept
# chooses their layouts), the MLP's hidden products (GPT's
# ``wi`` product; Llama's ``gate`` and ``up``; an expert layer's up
# and gate products as the grouped product returns them). NOT the
# out-projection's output: it is recomputed from the kept ``flash_o``.
# Of a sorted expert layer (models/moe._sorted_experts) besides: the
# router's logits, the sorted order (``order``, ``inverse``,
# ``group_sizes``, int32), the rows gathered into expert order and the
# down product's rows back in token order: what the permutations', the
# grouped products' and the weighted sum's backward take from the
# forward. NOT the activation: one elementwise pass. Of a state-space mixer
# (models/granite_hybrid.py): the projection into it, and the scan's
# output with the state every chunk starts from (ops/ssd.py), which
# are all its backward kernel takes from the forward one.
ATTN_IN = "attn_in"
FLASH_O = "flash_o"
FLASH_LSE = "flash_lse"
MLP_HIDDEN = "mlp_hidden"
ROUTER_LOGITS = "router_logits"
MOE_ORDER = "moe_order"
MOE_IN = "moe_in"
MOE_OUT = "moe_out"
SSM_IN = "ssm_in"
SSD_Y = "ssd_y"
SSD_STATES = "ssd_states"
# Of a delta-rule mixer (models/kimi_linear.py, ops/kda.py): the three
# projections into its convolutions, the rule's output and the state
# every chunk starts from. Of a latent-attention mixer: the latent the
# keys and values are projected up from.
KDA_IN = "kda_in"
KDA_O = "kda_o"
KDA_STATES = "kda_states"
MLA_LATENT = "mla_latent"
# Of a per-channel selective scan (ops/selective_scan.py): its output
# and the state every chunk starts from. Of a stack whose later layers
# read one layer's tensors (models/phi4_flash.py): the scan output
# that is every gated memory unit's memory and the keys and values
# every cross-attention layer attends, named where they are made.
SELSCAN_Y = "selscan_y"
SELSCAN_STATES = "selscan_states"
LAYER_MEMORY = "layer_memory"
SHARED_KV = "shared_kv"
KEPT = (ATTN_IN, FLASH_O, FLASH_LSE, MLP_HIDDEN, ROUTER_LOGITS,
        MOE_ORDER, MOE_IN, MOE_OUT, SSM_IN, SSD_Y, SSD_STATES,
        KDA_IN, KDA_O, KDA_STATES, MLA_LATENT, SELSCAN_Y,
        SELSCAN_STATES, LAYER_MEMORY, SHARED_KV)

POLICY_NAMES = ("none", "full", "attention", "dots", "offload")


def canonical(policy: Any) -> str:
    if policy is True:
        return "full"
    if policy in (False, None):
        return "none"
    if policy in POLICY_NAMES:
        return str(policy)
    raise ValueError(
        f"unknown remat policy {policy!r}; choose from "
        f"{POLICY_NAMES} (or True/False)"
    )


# The names tagged while a block under "full" is being traced, for
# the ``remat.kept`` event; None outside such a trace.
_tracing = threading.local()


def keep(x: jax.Array, name: str) -> jax.Array:
    """Name ``x`` as one of the residuals "full" keeps (``name`` is
    one of :data:`KEPT`). A no-op under every other policy."""
    seen = getattr(_tracing, "names", None)
    if seen is not None:
        seen.add(name)
    return checkpoint_name(x, name)


def full_policy():
    return jax.checkpoint_policies.save_only_these_names(*KEPT)


def _announced(block_fn: Callable) -> Callable:
    """``block_fn``, saying once a trace which of :data:`KEPT` the
    block named: event ``remat.kept`` with ``names`` and
    ``flash_residuals`` (the block held a flash forward, whose
    ``(o, lse)`` the backward then takes as they are)."""

    def block(*args):
        _tracing.names = set()
        try:
            out = block_fn(*args)
        finally:
            seen, _tracing.names = _tracing.names, None
        _tracing.last = tuple(sorted(seen))
        obs.event(
            "remat.kept", names=sorted(seen),
            flash_residuals=FLASH_O in seen,
        )
        return out

    return block


def last_kept() -> tuple:
    """The names the block this thread traced last under "full" gave
    (what ``remat.kept`` said of it); empty before any."""
    return getattr(_tracing, "last", ())


def offload_policy():
    """Block-boundary residuals stream to pinned host RAM; everything
    else is recomputed. HBM cost of the backward pass drops to one
    block's activations + transfer buffers."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=[BLOCK_OUT],
        offload_src="device",
        offload_dst="pinned_host",
    )


def apply_block_remat(
    block_fn: Callable,
    policy: Any,
    attn_fn: Optional[Callable] = None,
):
    """Wrap a transformer block (and optionally its attention inner
    fn) according to the named policy. Returns (block_fn, attn_fn)."""
    name = canonical(policy)
    if name == "none":
        return block_fn, attn_fn
    if name == "attention":
        if attn_fn is None:
            raise ValueError(
                "remat='attention' needs the attention callable"
            )
        return block_fn, jax.checkpoint(attn_fn)
    if name == "full":
        return (
            jax.checkpoint(_announced(block_fn), policy=full_policy()),
            attn_fn,
        )
    if name == "dots":
        return (
            jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.dots_saveable,
            ),
            attn_fn,
        )
    if name == "offload":
        return (
            jax.checkpoint(block_fn, policy=offload_policy()),
            attn_fn,
        )
    raise AssertionError(name)


def wire_block(inner_block: Callable, policy: Any,
               attn_fn: Callable) -> Callable:
    """One-stop wiring for model backbones: returns the block callable
    ``(x, layer_params) -> x`` with the named policy applied.

    Encapsulates the two policy-dependent quirks every model family
    would otherwise copy-paste: "attention" wraps the attention
    callable (not the block), and all other checkpointing policies
    need the block's output residual name-tagged INSIDE the
    checkpointed region so the "offload" policy can stream it to host
    RAM. The block may return either the carried activation alone or
    an ``(x, aux)`` tuple (MoE blocks carry a router loss); only the
    activation is name-tagged."""
    if canonical(policy) == "attention":
        _, wrapped_attn = apply_block_remat(None, "attention", attn_fn)
        return lambda x, lp: inner_block(x, lp, wrapped_attn)

    def named_block(x, lp):
        out = inner_block(x, lp, attn_fn)
        if isinstance(out, tuple):
            y, aux = out
            return tag_block_output(y), aux
        return tag_block_output(out)

    block, _ = apply_block_remat(named_block, policy, attn_fn)
    return block


def tag_block_output(x: jax.Array) -> jax.Array:
    """Tag a block's output residual so the offload policy can name
    it. A no-op under every other policy."""
    return checkpoint_name(x, BLOCK_OUT)
