"""MoE gating + expert-parallel layer tests (8-device CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_logical_axes,
    moe_mlp,
    switch_gating,
    top_k_gating,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import tree_shardings


def test_switch_gating_routes_to_argmax():
    logits = jnp.asarray(
        [[2.0, 0.0, 0.0], [0.0, 3.0, 0.0], [0.0, 0.0, 1.0]]
    )
    dispatch, combine, metrics = switch_gating(logits, capacity=2)
    # each token routed to its argmax expert at slot 0
    for tok, exp in [(0, 0), (1, 1), (2, 2)]:
        assert bool(dispatch[tok, exp, 0])
    assert float(metrics["dropped_fraction"]) == 0.0


def test_topk_gating_two_experts_per_token():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (16, 4))
    dispatch, combine, _ = top_k_gating(logits, top_k=2, capacity=16)
    per_token = jnp.sum(dispatch, axis=(1, 2))
    np.testing.assert_array_equal(per_token, np.full(16, 2))
    # combine weights are the softmax probs of the chosen experts
    probs = jax.nn.softmax(logits, axis=-1)
    tok0_experts = np.argsort(np.asarray(logits[0]))[-2:]
    got = float(jnp.sum(combine[0]))
    want = float(probs[0, tok0_experts[0]] + probs[0, tok0_experts[1]])
    assert abs(got - want) < 1e-5


def test_capacity_drops_overflow():
    # all tokens want expert 0; capacity 2 keeps exactly 2
    logits = jnp.tile(jnp.asarray([[5.0, 0.0]]), (8, 1))
    dispatch, combine, metrics = switch_gating(logits, capacity=2)
    assert int(jnp.sum(dispatch[:, 0, :])) == 2
    assert float(metrics["dropped_fraction"]) == pytest.approx(0.75)


def test_moe_mlp_forward_and_aux_loss():
    cfg = MoEConfig(n_embd=32, n_experts=4, top_k=2, dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y, aux = moe_mlp(params, x, cfg)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    assert float(aux) > 0.0  # aux losses active


def test_moe_grads_flow_to_all_param_groups():
    cfg = MoEConfig(n_embd=16, n_experts=4, top_k=2, dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))

    def loss(p):
        y, aux = moe_mlp(p, x, cfg)
        return jnp.mean(y**2) + aux

    grads = jax.grad(loss)(params)
    for name in ("router", "wi", "wo"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0.0, name


def test_moe_expert_parallel_on_mesh():
    """Expert-sharded weights + data-sharded tokens: GSPMD compiles the
    dispatch einsums with collectives; results match single-device."""
    mesh = build_mesh(
        MeshConfig(data=2, expert=4), devices=jax.devices()[:8]
    )
    cfg = MoEConfig(n_embd=32, n_experts=4, top_k=2, dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    shardings = tree_shardings(mesh, moe_logical_axes())
    params_sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, s), params, shardings
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    x_sharded = jax.device_put(
        x, NamedSharding(mesh, P(("data", "fsdp"), None, None))
    )

    y_ref, aux_ref = moe_mlp(params, x, cfg)
    with jax.set_mesh(mesh):
        y, aux = jax.jit(lambda p, x: moe_mlp(p, x, cfg))(
            params_sharded, x_sharded
        )
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(aux, aux_ref, rtol=1e-5)


@pytest.mark.slow
def test_moe_expert_parallel_composes_with_seq_ring():
    """EP x SP co-activation (no prior test ran both at once): a
    Mixtral-shaped Llama-MoE trains one step on a data x seq x expert
    mesh with ring attention over ``seq`` and experts sharded over
    ``expert``; the loss must match the single-device oracle."""
    import functools

    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.seq_attention import make_seq_attention
    from dlrover_tpu.trainer.step import (
        make_sharded_init,
        make_train_step,
        shard_batch,
    )

    mesh = build_mesh(
        MeshConfig(data=2, seq=2, expert=2), devices=jax.devices()[:8]
    )
    cfg = llama.LlamaConfig.moe_tiny()
    attn = make_seq_attention(mesh, causal=True, seq_impl="ring")
    loss = functools.partial(llama.loss_fn, cfg=cfg, attn_fn=attn)
    opt = optax.adamw(1e-3)
    init, _ = make_sharded_init(
        mesh,
        functools.partial(llama.init_params, cfg=cfg),
        llama.param_logical_axes(cfg),
        opt,
    )
    params, opt_state = init(jax.random.PRNGKey(0))
    step = make_train_step(mesh, loss, opt)
    tok = jax.random.randint(
        jax.random.PRNGKey(2), (4, cfg.block_size), 0, cfg.vocab_size
    )
    tgt = jnp.roll(tok, -1, axis=1)

    # Single-device oracle from the same init, BEFORE the donating
    # step consumes the buffers.
    dense_params = llama.init_params(jax.random.PRNGKey(0), cfg)
    want = float(llama.loss_fn(dense_params, tok, tgt, cfg=cfg))

    stok, stgt = shard_batch(mesh, tok, tgt)
    params, opt_state, m = step(params, opt_state, stok, stgt)
    got = float(m["loss"])
    assert got == got, "EP x SP loss is NaN"
    np.testing.assert_allclose(got, want, rtol=5e-4)


def test_moe_deterministic_under_jit():
    cfg = MoEConfig(n_embd=16, n_experts=2, top_k=1, dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    y1, _ = jax.jit(lambda p, x: moe_mlp(p, x, cfg))(params, x)
    y2, _ = moe_mlp(params, x, cfg)
    np.testing.assert_allclose(y1, y2, atol=1e-6)
