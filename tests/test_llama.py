"""Llama model family: RoPE, GQA, SwiGLU, sharded training.

Parity targets: the reference trains Llama-2 through HF modules +
atorch auto_accelerate (/root/reference/atorch/examples/llama2/
fsdp_llama2.py); here the model is native (models/llama.py) and the
same logical-axis rule table shards it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.step import (
    make_sharded_init,
    make_train_step,
    shard_batch,
)


@pytest.fixture(scope="module")
def tiny():
    return llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def tiny_params(tiny):
    return llama.init_params(jax.random.PRNGKey(0), tiny)


def test_forward_shape_and_finite(tiny, tiny_params):
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, tiny.block_size), 0, tiny.vocab_size
    )
    logits = llama.forward(tiny_params, tokens, tiny)
    assert logits.shape == (2, tiny.block_size, tiny.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_rope_preserves_norm(tiny):
    cos, sin = llama.rope_table(tiny, 16)
    x = jax.random.normal(
        jax.random.PRNGKey(0), (1, 16, 2, tiny.head_dim)
    )
    rot = llama.apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        jnp.linalg.norm(rot, axis=-1),
        jnp.linalg.norm(x, axis=-1),
        rtol=1e-5,
    )
    # position 0 is the identity rotation
    np.testing.assert_allclose(rot[:, 0], x[:, 0], atol=1e-6)


def test_rope_relative_shift_invariance(tiny):
    """Attention scores under RoPE depend only on relative offsets:
    rotating (q at p+s, k at p'+s) gives the same dot product."""
    d = tiny.head_dim
    cos, sin = llama.rope_table(tiny, 32)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 1, d))
    qr = llama.apply_rope(q, cos, sin)[0, :, 0]
    kr = llama.apply_rope(k, cos, sin)[0, :, 0]
    # score(5, 3) computed at positions (5,3) vs the same vectors
    # rotated as if at (15, 13): equal because offset is equal.
    q2 = jnp.broadcast_to(q[0, 5, 0], (1, 32, 1, d))
    k2 = jnp.broadcast_to(k[0, 3, 0], (1, 32, 1, d))
    q2r = llama.apply_rope(q2, cos, sin)[0, :, 0]
    k2r = llama.apply_rope(k2, cos, sin)[0, :, 0]
    s_a = jnp.dot(q2r[15], k2r[13])
    s_b = jnp.dot(q2r[5], k2r[3])
    np.testing.assert_allclose(s_a, s_b, rtol=1e-4)
    # and sanity: the in-context score at (5,3) uses those vectors
    np.testing.assert_allclose(
        jnp.dot(qr[5], kr[3]), s_b, rtol=1e-4, atol=1e-5
    )


def test_gqa_matches_explicit_head_broadcast(tiny, tiny_params):
    """GQA forward == an MHA forward whose k/v weights are the kv
    weights tiled over each query group."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2, tiny.block_size), 0, tiny.vocab_size
    )
    out_gqa = llama.forward(tiny_params, tokens, tiny)

    import dataclasses

    mha = dataclasses.replace(tiny, n_kv_head=tiny.n_head)
    D, Hkv, g = tiny.head_dim, tiny.n_kv_head, tiny.q_per_kv
    p2 = jax.tree.map(lambda x: x, tiny_params)

    def tile(w):  # [L, E, Hkv*D] -> [L, E, H*D] repeating per group
        L, E = w.shape[0], w.shape[1]
        w = w.reshape(L, E, Hkv, D)
        w = jnp.repeat(w, g, axis=2)
        return w.reshape(L, E, Hkv * g * D)

    p2["blocks"] = dict(p2["blocks"])
    p2["blocks"]["wk"] = tile(tiny_params["blocks"]["wk"])
    p2["blocks"]["wv"] = tile(tiny_params["blocks"]["wv"])
    out_mha = llama.forward(p2, tokens, mha)
    np.testing.assert_allclose(out_gqa, out_mha, atol=1e-4, rtol=1e-4)


def test_fused_loss_matches_plain(tiny, tiny_params):
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (2, tiny.block_size), 0, tiny.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    plain = llama.loss_fn(tiny_params, tokens, targets, tiny)
    fused = llama.loss_fn_fused(
        tiny_params, tokens, targets, tiny, num_chunks=4
    )
    np.testing.assert_allclose(fused, plain, rtol=1e-5)


@pytest.mark.slow
def test_remat_policies_grad_parity(tiny, tiny_params):
    import dataclasses

    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (2, tiny.block_size), 0, tiny.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    base = jax.grad(
        lambda p: llama.loss_fn(p, tokens, targets, tiny)
    )(tiny_params)
    for policy in (True, "attention", "dots"):
        cfg = dataclasses.replace(tiny, remat=policy)
        g = jax.grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg)
        )(tiny_params)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(base)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-3)


def test_sharded_train_step_tp_fsdp(tiny):
    """Full sharded train step on the 8-device CPU mesh: fsdp=2 x
    tensor=2 x data=2, loss finite and decreasing over steps."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    optimizer = optax.adamw(1e-3)
    loss = functools.partial(llama.loss_fn, cfg=tiny)
    init, _ = make_sharded_init(
        mesh,
        functools.partial(llama.init_params, cfg=tiny),
        llama.param_logical_axes(tiny),
        optimizer,
    )
    params, opt_state = init(jax.random.PRNGKey(0))
    step = make_train_step(mesh, loss, optimizer)
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (8, tiny.block_size), 0, tiny.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    tokens, targets = shard_batch(mesh, tokens, targets)
    losses = []
    for _ in range(4):
        params, opt_state, metrics = step(
            params, opt_state, tokens, targets
        )
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


class TestLlamaMoE:
    """Mixtral-shaped family: Llama blocks with expert-routed MLPs."""

    @pytest.fixture(scope="class")
    def moe_cfg(self):
        return llama.LlamaConfig.moe_tiny()

    @pytest.fixture(scope="class")
    def moe_params(self, moe_cfg):
        return llama.init_params(jax.random.PRNGKey(0), moe_cfg)

    def test_forward_and_loss_finite(self, moe_cfg, moe_params):
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, moe_cfg.block_size), 0,
            moe_cfg.vocab_size,
        )
        logits = llama.forward(moe_params, tokens, moe_cfg)
        assert logits.shape == (
            2, moe_cfg.block_size, moe_cfg.vocab_size
        )
        assert bool(jnp.all(jnp.isfinite(logits)))
        targets = jnp.roll(tokens, -1, axis=1)
        loss = llama.loss_fn(moe_params, tokens, targets, moe_cfg)
        assert bool(jnp.isfinite(loss))
        # aux loss contributes: plain CE from logits differs from loss
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1)
        )
        assert float(loss) > float(ce)

    def test_fused_matches_plain(self, moe_cfg, moe_params):
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (2, moe_cfg.block_size), 0,
            moe_cfg.vocab_size,
        )
        targets = jnp.roll(tokens, -1, axis=1)
        plain = llama.loss_fn(moe_params, tokens, targets, moe_cfg)
        fused = llama.loss_fn_fused(
            moe_params, tokens, targets, moe_cfg, num_chunks=4
        )
        np.testing.assert_allclose(fused, plain, rtol=1e-5)

    def test_expert_sharded_train_step(self, moe_cfg):
        """expert x data mesh: one sharded train step, loss decreasing."""
        mesh = build_mesh(MeshConfig(data=2, expert=4))
        optimizer = optax.adamw(1e-3)
        loss = functools.partial(llama.loss_fn, cfg=moe_cfg)
        init, _ = make_sharded_init(
            mesh,
            functools.partial(llama.init_params, cfg=moe_cfg),
            llama.param_logical_axes(moe_cfg),
            optimizer,
        )
        params, opt_state = init(jax.random.PRNGKey(0))
        step = make_train_step(mesh, loss, optimizer)
        tokens = jax.random.randint(
            jax.random.PRNGKey(3), (4, moe_cfg.block_size), 0,
            moe_cfg.vocab_size,
        )
        targets = jnp.roll(tokens, -1, axis=1)
        tokens, targets = shard_batch(mesh, tokens, targets)
        losses = []
        for _ in range(3):
            params, opt_state, m = step(
                params, opt_state, tokens, targets
            )
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_cached_decode_matches_forward(self, moe_cfg, moe_params):
        """Parity needs no capacity dropping: the training forward
        drops over batch*seq while decode sees one token at a time, so
        pin a capacity factor high enough that neither path drops."""
        import dataclasses

        from dlrover_tpu.models import generate

        cfg = dataclasses.replace(moe_cfg, moe_capacity_factor=8.0)
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (2, 12), 0, cfg.vocab_size
        )
        got = generate.decode_logits_sequential(moe_params, cfg, tokens)
        want = llama.forward(moe_params, tokens, cfg)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-3
        )

    def test_moe_flops_counts_active_experts_only(self, moe_cfg):
        got = llama.flops_per_token(moe_cfg)
        E, L, I = moe_cfg.n_embd, moe_cfg.n_layer, moe_cfg.intermediate
        kv = moe_cfg.n_kv_head * moe_cfg.head_dim
        # active SwiGLU experts (top_k of n_experts, 3 matmuls each)
        # + router, NOT all experts
        mlp = 3 * moe_cfg.moe_top_k * E * I + E * moe_cfg.n_experts
        want = 6.0 * (
            L * (2 * E * E + 2 * E * kv + mlp)
            + moe_cfg.vocab_size * E
        ) + 12 * L * moe_cfg.block_size * E
        assert got == want
        # sanity: all-experts accounting would be strictly larger
        all_experts = got + 6.0 * L * 3 * (
            moe_cfg.n_experts - moe_cfg.moe_top_k
        ) * E * I
        assert got < all_experts


def test_flops_per_token_matches_analytic(tiny):
    got = llama.flops_per_token(tiny)
    E, L, I = tiny.n_embd, tiny.n_layer, tiny.intermediate
    kv = tiny.n_kv_head * tiny.head_dim
    want = 6.0 * (
        L * (2 * E * E + 2 * E * kv + 3 * E * I)
        + tiny.vocab_size * E
    ) + 12 * L * tiny.block_size * E
    assert got == want


class TestSlidingWindow:
    """Mistral-shaped family: Llama backbone + sliding-window band
    (models/llama.py LlamaConfig.sliding_window, mistral_7b preset)."""

    def test_windowed_forward_matches_manual_band_mask(self):
        import dataclasses

        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), sliding_window=24
        )
        params = llama.init_params(jax.random.PRNGKey(3), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (2, cfg.block_size), 0,
            cfg.vocab_size,
        )
        out = llama.forward(params, tokens, cfg)

        # Same params through an explicit band-masked attention.
        from dlrover_tpu.models.gpt import _default_attention

        manual = llama.forward(
            params, tokens, cfg,
            attn_fn=functools.partial(
                _default_attention, causal=True, window=24
            ),
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(manual), atol=1e-5, rtol=1e-5
        )
        # And the band must actually matter: full-causal differs.
        full = llama.forward(
            params, tokens, cfg,
            attn_fn=functools.partial(_default_attention, causal=True),
        )
        assert not np.allclose(np.asarray(out), np.asarray(full))

    def test_windowed_train_step_decreases_loss(self):
        import dataclasses

        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), sliding_window=16
        )
        params = llama.init_params(jax.random.PRNGKey(5), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(6), (4, cfg.block_size), 0,
            cfg.vocab_size,
        )
        targets = jnp.roll(tokens, -1, axis=1)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(
                lambda p: llama.loss_fn(p, tokens, targets, cfg)
            )(params)
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_mistral_7b_preset_shape(self):
        cfg = llama.LlamaConfig.mistral_7b()
        assert cfg.sliding_window == 4096
        assert cfg.n_kv_head == 8 and cfg.q_per_kv == 4
        assert cfg.block_size == 8192
