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
  "full"       recompute blocks; save only non-batch matmul outputs
  "attention"  recompute only attention internals
  "dots"       recompute everything except matmul outputs
  "offload"    offload block-boundary residuals (checkpoint_name
               "block_out") to pinned host memory, save nothing else
  "save_attn"  "full"'s saves PLUS the flash forward's (o, lse), so
               the backward reuses them instead of re-running the
               flash forward kernel (a dot-level policy can't see
               inside the flash custom_vjp). Trades ~T*E bytes/layer
               of HBM for the whole attention recompute (r5 profile:
               the flash fwd is 8.8 ms of a 173 ms step at b18,
               re-run a second time under "full"; the residual
               traffic costs ~1 ms).

Booleans keep working: True == "full", False == "none".
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

# residual name tagged at each transformer block boundary (models
# call jax.ad_checkpoint.checkpoint_name on the block output)
BLOCK_OUT = "block_out"

POLICY_NAMES = (
    "none", "full", "attention", "dots", "offload", "save_attn"
)


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


# The ONE definition of what "full" saves — save_attn is documented
# as "full's saves plus the flash outputs", so both must build on the
# same base or they silently diverge.
def full_policy():
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def save_attn_policy():
    """"full" remat's saves PLUS the flash forward kernel's outputs.

    "full" here is ``dots_with_no_batch_dims_saveable`` — it already
    saves the projection/MLP dot outputs (the scan-stacked residuals
    in the r5 step trace); what it cannot save is the attention
    output, because that lives INSIDE the flash custom_vjp whose
    residuals a dot-level policy never sees. The union adds exactly
    the pallas_call named "flash_attention_fwd": its saved (o, lse)
    feed the flash backward kernel as residuals directly, and
    jax.checkpoint's partial eval dead-code-eliminates the forward
    kernel from the recompute — verified by counting pallas_call eqns
    in the grad jaxpr (tests/test_remat_policies.py): full remat
    traces the fwd kernel twice, this policy once, with everything
    else saved/recomputed exactly as under "full". (Saving ONLY the
    flash outputs — without full's dot saves — would force the
    projection matmuls to recompute in the backward and lose more
    than the skipped flash re-run gains.) With XLA (non-flash)
    attention there is no matching eqn and this degrades gracefully
    to "full"."""

    def flash_fwd_saveable(prim, *_, **params):
        if prim.name != "pallas_call":
            return False
        return params.get("name") == "flash_attention_fwd"

    return jax.checkpoint_policies.save_from_both_policies(
        full_policy(), flash_fwd_saveable
    )


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
            jax.checkpoint(block_fn, policy=full_policy()),
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
    if name == "save_attn":
        return (
            jax.checkpoint(block_fn, policy=save_attn_policy()),
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
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, BLOCK_OUT)
