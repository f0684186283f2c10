"""Named remat/offload policies (ref
selective_offloading_checkpoint.py): every policy computes identical
loss and gradients — only the memory/time tradeoff differs.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accelerate.remat import POLICY_NAMES, canonical
from dlrover_tpu.models import gpt, layers, llama


def _cfg(remat):
    return gpt.GPTConfig(
        vocab_size=128,
        block_size=32,
        n_layer=2,
        n_head=2,
        n_embd=32,
        dtype=jnp.float32,
        remat=remat,
    )


def _loss_and_grads(remat, **cfg_overrides):
    cfg = dataclasses.replace(_cfg(remat), **cfg_overrides)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, cfg.block_size), 0, cfg.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    loss_fn = functools.partial(gpt.loss_fn, cfg=cfg)
    return jax.jit(jax.value_and_grad(loss_fn))(
        params, tokens, targets
    )


class TestRematPolicies:
    def test_canonical_names(self):
        assert canonical(True) == "full"
        assert canonical(False) == "none"
        assert canonical(None) == "none"
        for n in POLICY_NAMES:
            assert canonical(n) == n
        with pytest.raises(ValueError, match="unknown remat"):
            canonical("bogus")

    @pytest.mark.parametrize(
        "policy",
        ["full", "attention", "dots", "offload", True],
    )
    def test_policy_matches_no_remat(self, policy):
        """Loss and every gradient identical to remat='none' — remat
        is a memory knob, never a numerics knob."""
        try:
            base_loss, base_grads = _loss_and_grads("none")
            loss, grads = _loss_and_grads(policy)
        except Exception as exc:  # noqa: BLE001
            if "pinned_host" in str(exc) or "memory kind" in str(exc):
                pytest.skip(f"backend lacks host offload: {exc}")
            raise
        np.testing.assert_allclose(
            float(loss), float(base_loss), rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(grads), jax.tree.leaves(base_grads)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
            )

    def test_full_remat_uses_less_temp_memory_than_none(self):
        """XLA's own accounting: recompute trades memory for FLOPs.
        Six layers, three in line and three scanned (models/layers.py).
        A stack that is all in line shows nothing here: the CPU's
        compiler merges an in-line layer's recompute with its forward
        (as many products compiled with "full" as with "none", and as
        many bytes), which a loop's body rules out. The chip's keeps
        the recompute (tests/test_tpu_compile_dense.py: Mistral's two
        layers, both in line, 10.8 GB for the scan's 14.4)."""
        def build(remat):
            cfg = dataclasses.replace(_cfg(remat), n_layer=6)
            params = gpt.init_params(jax.random.PRNGKey(0), cfg)
            tokens = jnp.zeros((4, cfg.block_size), jnp.int32)
            loss_fn = functools.partial(gpt.loss_fn, cfg=cfg)
            return (
                jax.jit(jax.grad(loss_fn))
                .lower(params, tokens, tokens)
                .compile()
                .memory_analysis()
            )

        m_none = build("none")
        m_full = build("full")
        if m_none is None or m_full is None:
            pytest.skip("backend lacks memory analysis")
        assert (
            m_full.temp_size_in_bytes < m_none.temp_size_in_bytes
        )

    def test_strategy_carries_named_policy(self):
        from dlrover_tpu.accelerate.strategy import Strategy

        s = Strategy(
            mesh_shape=(("data", 8),), remat="offload"
        )
        assert "remat:offload" in s.name()
        assert Strategy.from_json(s.to_json()).remat == "offload"
        assert "remat:full" in Strategy(
            mesh_shape=(("data", 8),), remat=True
        ).name()


def _eqns(jaxpr):
    """Every equation of the jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else [v]:
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from _eqns(x)


def _flash_calls(jaxpr, acc):
    """Name of every flash ``pallas_call`` in the jaxpr, recursively
    (an expert layer's grouped products are Pallas calls too)."""
    for eqn in _eqns(jaxpr):
        name = str(eqn.params.get("name", ""))
        if eqn.primitive.name == "pallas_call" and "flash" in name:
            acc.append(name)
    return acc


# (B, T) of the flash cases. Every family: E = 32, float32, so that
# CPU gradients compare at the tolerances of the policies above;
# shapes chosen so that no two kept residuals but ``x`` and
# ``flash_o`` share one. Five layers: three in line and two scanned
# (models/layers.py), so the gradient's jaxpr holds the block
# ``BODIES`` times, three in-line calls and the scan's body, and the
# tests below read both forms.
B, T = 3, 128
N_LAYER = 5
BODIES = layers.IN_LINE + 1


def _flash_family(family, remat, **overrides):
    """(loss_fn(params, tokens, targets), params) of a 5-layer model
    on the flash kernel: GPT; Llama with grouped queries and a
    window; a Llama block with an expert layer."""
    if family == "gpt":
        cfg = dataclasses.replace(
            _cfg(remat), block_size=T, n_layer=N_LAYER,
            use_flash_attention=True, attn_blocks=(128, 128, 128, 128),
            **overrides,
        )
        return (
            functools.partial(gpt.loss_fn, cfg=cfg),
            gpt.init_params(jax.random.PRNGKey(0), cfg),
        )
    cfg = llama.LlamaConfig(
        vocab_size=128, block_size=T, n_layer=N_LAYER, n_head=4,
        n_kv_head=2, n_embd=32, intermediate=96, dtype=jnp.float32,
        remat=remat, use_flash_attention=True,
        attn_blocks=(64, 64, 64, 64),
        sliding_window=None if family == "moe" else 48,
        n_experts=4 if family == "moe" else 0,
    )
    return (
        functools.partial(llama.loss_fn, cfg=cfg),
        llama.init_params(jax.random.PRNGKey(0), cfg),
    )


def _tokens():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 128)
    return tokens, jnp.roll(tokens, -1, axis=1)


@functools.lru_cache(maxsize=None)
def _grad_jaxpr(family, remat, **overrides):
    loss_fn, params = _flash_family(family, remat, **overrides)
    return jax.make_jaxpr(jax.grad(loss_fn))(params, *_tokens()).jaxpr


def _stacked_residuals(jaxpr):
    """Shapes, without the layer dimension, of what the forward layer
    scan stacks for the backward: its outputs of rank > 0 beyond the
    carry, as a sorted list."""
    scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
    stacked = scan.outvars[scan.params["num_carry"]:]
    return sorted(
        (v.aval.shape[1:], str(v.aval.dtype)) for v in stacked
        if v.aval.ndim > 1
    )


def _in_line_residuals(
    jaxpr, forward="flash_attention_fwd", backward="flash_attention_bwd"
):
    """The same of each layer that runs in line: of every call of the
    block among the gradient's own equations that holds the kernel
    ``forward`` and not ``backward`` (a layer's forward), the results
    of rank > 0 beyond the carry (``x``; with Llama's block
    ``(x, aux)``). A list a call."""
    found = []
    for eqn in jaxpr.eqns:
        inner = eqn.params.get("jaxpr")
        if eqn.primitive.name != "jit" or inner is None:
            continue
        kernels = [
            str(e.params.get("name")) for e in _eqns(inner.jaxpr)
            if e.primitive.name == "pallas_call"
        ]
        if forward not in kernels or backward in kernels:
            continue
        carried = 1 + (eqn.outvars[1].aval.ndim == 0)
        found.append(sorted(
            (v.aval.shape, str(v.aval.dtype))
            for v in eqn.outvars[carried:] if v.aval.ndim > 0
        ))
    return found


def _without_x(kept, width=32):
    """What an in-line layer keeps of ``kept``, the scan's set: all
    but the block's input ``[B, T, width]``, which in line is the
    value the call before returned and no result of this one."""
    kept = list(kept)
    kept.remove(((B, T, width), "float32"))
    return kept


FAMILIES = ["gpt", "llama_gqa_window", "moe"]
E, H, F = 32, 4, 96
F32 = "float32"
# What "full" keeps of a block, per family: x (the block's input),
# flash_o in the model's layout at these head sizes (the same shape
# as x), flash_lse [B, H, 1, T] as the kernels write and read it, and
# the named products. No third
# [B, T, E]: that would be the out-projection's output.
KEPT_SHAPES = {
    "gpt": sorted([
        ((B, T, 32), F32), ((B, T, 32), F32),   # x, flash_o
        ((B, 2, 1, T), F32),                    # flash_lse, 2 heads
        ((B, T, 96), F32),                      # qkv
        ((B, T, 128), F32),                     # the wi product
    ]),
    "llama_gqa_window": sorted([
        ((B, T, E), F32), ((B, T, E), F32),     # x, flash_o
        ((B, T, E), F32),                       # q
        ((B, T, E // 2), F32), ((B, T, E // 2), F32),  # k, v: 2 of 4 heads
        ((B, H, 1, T), F32),                    # flash_lse
        ((B, T, F), F32), ((B, T, F), F32),     # gate, up
    ]),
    # The expert layer: 4 experts, 2 a token, so B * T * 2 (token,
    # choice) rows; what ``models/moe._sorted_experts`` names.
    "moe": sorted([
        ((B, T, E), F32), ((B, T, E), F32), ((B, T, E), F32),
        ((B, T, E // 2), F32), ((B, T, E // 2), F32),
        ((B, H, 1, T), F32),
        ((B * T, 4), F32),                      # router logits
        ((B * T * 2,), "int32"), ((B * T * 2,), "int32"),  # order, inverse
        ((4,), "int32"),                        # group sizes
        ((B * T * 2, E), F32),                  # rows in expert order
        ((B * T * 2, F), F32), ((B * T * 2, F), F32),  # up, gate products
        ((B * T * 2, E), F32),                  # rows back in token order
    ]),
}
MOE_NAMES = ["moe_in", "moe_order", "moe_out"]


def _expert_layer_calls(jaxpr):
    """(``moe_gmm`` calls, ``moe_tgmm`` calls, ``sort`` equations) of
    every copy of the layer the gradient's jaxpr holds: one a scan's
    body, one an in-line call (models/layers.py)."""
    eqns = list(_eqns(jaxpr))
    kernels = [
        str(e.params.get("name")) for e in eqns
        if e.primitive.name == "pallas_call"
    ]
    return (
        kernels.count("moe_gmm"), kernels.count("moe_tgmm"),
        sum(e.primitive.name == "sort" for e in eqns),
    )


def _kept_events(loss_fn, params, *batch):
    """The ``remat.kept`` events of one trace of the loss's gradient."""
    from dlrover_tpu import obs

    tracer = obs.configure_tracer()
    try:
        jax.jit(jax.value_and_grad(loss_fn)).lower(params, *batch)
        return [e for e in tracer.events() if e["name"] == "remat.kept"]
    finally:
        obs.disable_tracer()


class TestFullKeepsTheFlashOutputs:
    """What ``remat=True`` is for is structural: the flash forward
    kernel is traced ONCE a layer (its kept (o, lse) feed the
    backward) and the out-projection's output is not among the
    residuals. Assert that on the jaxpr: a numerics test alone would
    pass even if the policy silently stopped working. Read on both
    forms of the stack: the scan's body and the three in-line calls
    (``BODIES`` copies of the block in the gradient's jaxpr)."""

    @pytest.mark.parametrize("family", FAMILIES)
    def test_fwd_kernel_not_recomputed(self, family):
        calls = _flash_calls(_grad_jaxpr(family, "full"), [])
        assert sorted(calls) == BODIES * ["flash_attention_bwd"] + (
            BODIES * ["flash_attention_fwd"]
        ), calls

    def test_a_policy_by_type_runs_the_fwd_kernel_twice(self):
        """The contrast, so the test above cannot pass by accident:
        "dots" keeps by primitive type and cannot see inside the
        flash custom_vjp."""
        calls = _flash_calls(_grad_jaxpr("gpt", "dots"), [])
        assert sorted(calls) == BODIES * ["flash_attention_bwd"] + (
            2 * BODIES * ["flash_attention_fwd"]
        ), calls

    @pytest.mark.parametrize("family", FAMILIES)
    def test_residuals_are_exactly_the_named_set(self, family):
        jaxpr = _grad_jaxpr(family, "full")
        got = _stacked_residuals(jaxpr)
        assert got == KEPT_SHAPES[family], got
        in_line = _in_line_residuals(jaxpr)
        assert in_line == layers.IN_LINE * [
            _without_x(KEPT_SHAPES[family])
        ], in_line

    def test_expert_layer_runs_no_product_twice(self):
        """A gated expert layer needs three grouped products forward,
        three input gradients (``moe_gmm``) and three weight gradients
        (``moe_tgmm``), and its two sorts once: with the named values
        kept, the backward holds no second forward of them."""
        assert _expert_layer_calls(_grad_jaxpr("moe", "full")) == (
            6 * BODIES, 3 * BODIES, 2 * BODIES
        )

    def test_a_policy_by_type_runs_the_expert_forward_twice(self):
        """The contrast: "dots" cannot see inside ``gmm``'s custom_vjp
        either, and runs the three forward products and both sorts a
        second time inside the backward."""
        assert _expert_layer_calls(_grad_jaxpr("moe", "dots")) == (
            9 * BODIES, 3 * BODIES, 4 * BODIES
        )

    def test_kept_expert_values_are_not_rounded_a_second_time(self):
        """``jax.checkpoint`` puts a ``reduce_precision`` behind a kept
        float value that its block's own equations consume (the dense
        MLP's products, where XLA fuses it into the product). What
        the expert layer keeps comes out of Pallas calls and gathers,
        which take no such fusion: on the chip each was a pass of its
        own over every row. Inside the layer's own call
        (``models/moe._sorted_moe``) none is inserted."""
        def rounded(family):
            return [
                e.outvars[0].aval.shape
                for e in _eqns(_grad_jaxpr(family, "full"))
                if e.primitive.name == "reduce_precision"
            ]

        assert (B, T, F) in rounded("llama_gqa_window")
        assert not [s for s in rounded("moe") if s[0] == B * T * 2]

    def test_kept_lse_is_compact(self):
        """[B, H, 1, T], rows along the lanes, as the forward kernel
        wrote it: a [B, H, T, 1] column would be padded to 128 lanes
        in the chip's memory."""
        shapes = [
            s for s, _ in _stacked_residuals(_grad_jaxpr("gpt", "full"))
        ]
        assert (B, 2, 1, T) in shapes
        assert not [s for s in shapes if s[-1] == 1]

    @pytest.mark.parametrize("family", FAMILIES)
    def test_no_column_between_the_kernels(self, family):
        """Out of the forward kernel, through the scan's stack, into
        the backward kernel the rows stay rows: the only
        [B, H, T, 1] value of the block's gradient is the forward
        kernel's own result (compacted at once); XLA spent two
        copies a layer on the backward's columns."""
        jaxpr = _grad_jaxpr(family, "full")
        heads = 2 if family == "gpt" else H
        columns = [
            eqn.primitive.name
            for eqn in _eqns(jaxpr) for v in eqn.outvars
            if v.aval.shape[-3:] == (heads, T, 1)
        ]
        # The kernel's result and the slice that drops its unit lane.
        assert columns == BODIES * ["pallas_call", "slice"], columns

    def test_o_kept_as_the_kernel_wrote_it_at_head_size_128(self):
        """Where the head size fills the chip's 128 lanes the kernel's
        [B, H, T, D] is not padded, so ``o`` is kept as it is and no
        transposition is paid; below that (the cases above) it is
        kept in the model's layout."""
        jaxpr = _grad_jaxpr("gpt", "full", n_embd=128, n_head=1)
        kept = sorted([
            ((B, T, 128), F32),                  # x alone
            ((B, 1, T, 128), F32),               # flash_o, one head
            ((B, 1, 1, T), F32),                 # flash_lse
            ((B, T, 384), F32), ((B, T, 512), F32),
        ])
        assert _stacked_residuals(jaxpr) == kept
        assert _in_line_residuals(jaxpr) == layers.IN_LINE * [
            _without_x(kept, 128)
        ]
        calls = _flash_calls(jaxpr, [])
        assert calls.count("flash_attention_fwd") == BODIES, calls

    @pytest.mark.parametrize("family", FAMILIES)
    def test_grads_match_no_remat_with_flash(self, family):
        def grads(remat):
            loss_fn, params = _flash_family(family, remat)
            return jax.jit(jax.value_and_grad(loss_fn))(
                params, *_tokens()
            )

        loss, got = grads("full")
        base_loss, want = grads("none")
        np.testing.assert_allclose(
            float(loss), float(base_loss), rtol=1e-6
        )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
            )

    def test_prefix_lm_keeps_both_calls_outputs(self):
        """ops/prefix_lm.py calls the plain entry twice a layer (the
        prefix's rows and the rest): each keeps its own (o, lse),
        together one ``o``, and neither forward runs again."""
        from dlrover_tpu.accelerate.remat import full_policy
        from dlrover_tpu.ops.prefix_lm import prefix_lm_attention

        def loss(q, k, v):
            block = functools.partial(prefix_lm_attention, prefix_len=32)
            out = jax.checkpoint(block, policy=full_policy())(q, k, v)
            return out.sum()

        q = jnp.ones((1, 64, 2, 16), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
        calls = _flash_calls(jaxpr.jaxpr, [])
        assert calls.count("flash_attention_fwd") == 2, calls
        assert calls.count("flash_attention_bwd") == 2, calls


class TestRetiredNameAndEvent:
    def test_five_policies_and_no_alias(self):
        assert POLICY_NAMES == (
            "none", "full", "attention", "dots", "offload"
        )
        # What it named is what True means now; the name has no alias.
        with pytest.raises(ValueError, match="unknown remat"):
            canonical("save" + "_attn")

    def test_full_policy_is_by_name(self):
        from dlrover_tpu.accelerate import remat

        assert remat.KEPT == (
            "attn_in", "flash_o", "flash_lse", "mlp_hidden",
            "router_logits", "moe_order", "moe_in", "moe_out",
            "ssm_in", "ssd_y", "ssd_states",
            "kda_in", "kda_o", "kda_states", "mla_latent",
            "selscan_y", "selscan_states", "layer_memory", "shared_kv",
        )
        assert remat.BLOCK_OUT not in remat.KEPT

    @pytest.mark.parametrize("flash", [True, False])
    def test_remat_kept_fires_once_a_trace(self, flash):
        if flash:
            loss_fn, params = _flash_family("gpt", "full")
            tokens, targets = _tokens()
        else:
            cfg = _cfg("full")
            loss_fn = functools.partial(gpt.loss_fn, cfg=cfg)
            params = gpt.init_params(jax.random.PRNGKey(0), cfg)
            tokens = targets = jnp.zeros((2, 32), jnp.int32)
        (ev,) = _kept_events(loss_fn, params, tokens, targets)
        assert ev["flash_residuals"] is flash
        want = ["attn_in", "mlp_hidden"]
        if flash:
            want = ["attn_in", "flash_lse", "flash_o", "mlp_hidden"]
        assert ev["names"] == want

    def test_remat_kept_lists_the_expert_layers_names(self):
        (ev,) = _kept_events(*_flash_family("moe", "full"), *_tokens())
        assert ev["names"] == sorted(
            ["attn_in", "flash_lse", "flash_o", "mlp_hidden",
             "router_logits"] + MOE_NAMES
        )

    @pytest.mark.parametrize("family", ["gpt", "llama_gqa_window"])
    def test_a_block_with_no_expert_layer_lists_none_of_them(self, family):
        (ev,) = _kept_events(*_flash_family(family, "full"), *_tokens())
        assert "mlp_hidden" in ev["names"]
        assert not set(ev["names"]) & {"router_logits", *MOE_NAMES}

    @pytest.mark.parametrize("policy", ["none", "dots", "attention"])
    def test_remat_kept_is_fulls_alone(self, policy):
        loss_fn, params = _flash_family("gpt", policy)
        assert _kept_events(loss_fn, params, *_tokens()) == []


class TestScanUnroll:
    """cfg.scan_unroll is a pure scheduling knob: loss and gradients
    must be bit-comparable across unroll factors, including a factor
    that does not divide n_layer and one larger than it."""

    @pytest.mark.parametrize("unroll", [2, 3])
    def test_unroll_parity(self, unroll):
        base_loss, base_g = _loss_and_grads(True, scan_unroll=1)
        loss, g = _loss_and_grads(True, scan_unroll=unroll)
        np.testing.assert_allclose(
            float(loss), float(base_loss), rtol=1e-6
        )
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(base_g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
            )

    def test_unroll_exceeding_layers_ok(self):
        cfg = dataclasses.replace(_cfg(False), scan_unroll=8)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((1, cfg.block_size), jnp.int32)
        out = gpt.forward(params, tokens, cfg)
        assert out.shape == (1, cfg.block_size, cfg.vocab_size)
