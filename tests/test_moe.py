"""MoE gating + expert-parallel layer tests (8-device CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models import moe
from dlrover_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_logical_axes,
    moe_mlp,
    routing_stats,
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
    # Off the mesh the layer is dropless; capacity n_experts / top_k
    # (every token fits every expert) lets the one-hot path agree.
    cfg = MoEConfig(n_embd=32, n_experts=4, top_k=2, dtype=jnp.float32,
                    capacity_factor=2.0)
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

    import dataclasses

    mesh = build_mesh(
        MeshConfig(data=2, seq=2, expert=2), devices=jax.devices()[:8]
    )
    # The oracle is dropless; capacity n_experts / top_k never drops.
    cfg = dataclasses.replace(
        llama.LlamaConfig.moe_tiny(), moe_capacity_factor=2.0
    )
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


# -- the sorted, dropless path against a dense per-expert loop ----------


def _dense_loop(params, x, cfg):
    """Every expert applied to every token, masked by the top-k
    choice: the layer's definition, with no sort and no capacity."""
    flat = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(flat @ params["router"], axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_top_k:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    y = jnp.zeros_like(flat)
    for e in range(cfg.n_experts):
        h = flat @ params["wi"][e]
        if cfg.gated:
            h = jax.nn.silu(flat @ params["wg"][e]) * h
        else:
            h = jax.nn.gelu(h)
        gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        y = y + gate[:, None] * (h @ params["wo"][e])
    return y.reshape(x.shape)


def _uneven_layer(cfg, tokens=(2, 24)):
    """Random weights and inputs with a router that sends every token
    to expert 0 and none to the last one."""
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    common = jnp.ones((cfg.n_embd,)) / np.sqrt(cfg.n_embd)
    x = jax.random.normal(
        jax.random.PRNGKey(1), tokens + (cfg.n_embd,)
    ) + 4.0 * common
    router = params["router"] * 40.0
    router = router.at[:, 0].add(10.0 * common)
    router = router.at[:, -1].add(-20.0 * common)
    params = dict(params, router=router)
    params = {
        k: v * 5.0 if k != "router" else v for k, v in params.items()
    }
    return params, x


@pytest.mark.parametrize("experts,top_k,gated,renorm", [
    (4, 1, False, False),
    (8, 2, True, True),
    (16, 4, False, True),
    (64, 8, True, False),  # OLMoE's shape
])
def test_sorted_path_matches_dense_loop(experts, top_k, gated, renorm):
    cfg = MoEConfig(
        n_embd=32, n_experts=experts, expert_hidden=24, top_k=top_k,
        gated=gated, renorm_top_k=renorm, dtype=jnp.float32,
    )
    params, x = _uneven_layer(cfg)
    n = x.shape[0] * x.shape[1]
    stats = routing_stats(x.reshape(n, -1) @ params["router"], top_k)
    assert int(stats["tokens_per_expert"][0]) >= 0.9 * n  # most tokens
    assert int(stats["tokens_per_expert"][-1]) == 0  # none
    assert int(jnp.sum(stats["tokens_per_expert"])) == n * top_k
    assert float(stats["max_over_mean"]) >= 0.9 * experts / top_k
    assert float(stats["empty_share"]) > 0.0
    assert float(stats["dropped_share"]) == 0.0

    def out_sum(fn):
        # A loss that weighs every output element differently, so a
        # row that went to the wrong place shows in the gradients.
        w = jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape) / x.size
        return lambda p, x: jnp.sum(fn(p, x) * (1.0 + w))

    sorted_fn = jax.jit(lambda p, x: moe_mlp(p, x, cfg)[0])
    dense_fn = lambda p, x: _dense_loop(p, x, cfg)  # noqa: E731
    got, want = sorted_fn(params, x), dense_fn(params, x)
    g_got = jax.jit(jax.grad(out_sum(sorted_fn), (0, 1)))(params, x)
    g_want = jax.grad(out_sum(dense_fn), (0, 1))(params, x)
    # Both sides are float32 and the same sums in another order (a
    # ragged product against a dense one, 8 choices summed after a
    # permutation): a few units in the last place of the largest
    # element, 1e-5 of it with room. A misplaced row is of order 1.
    for a, b in zip(jax.tree.leaves((got, g_got)),
                    jax.tree.leaves((want, g_want))):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0.0
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, rtol=0)


def test_one_hot_path_drops_where_the_sorted_path_does_not():
    """Every token's first choice is expert 0: at capacity factor 1.0
    the one-hot path has 24 slots for 48 tokens and drops; the sorted
    path has no capacity."""
    cfg = MoEConfig(
        n_embd=32, n_experts=4, expert_hidden=24, top_k=2, gated=True,
        capacity_factor=1.0, dtype=jnp.float32,
    )
    params, x = _uneven_layer(cfg)
    flat = x.reshape(-1, 32)
    want = _dense_loop(params, x, cfg).reshape(-1, 32)
    logits = moe.router_logits(flat, params["router"])
    dropped, metrics = moe._onehot_moe(params, flat, logits, cfg)
    assert float(metrics["dropped_fraction"]) >= 0.25
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(dropped - want))) > 0.1 * scale
    got = moe_mlp(params, x, cfg)[0].reshape(-1, 32)
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)


def test_router_losses_count_every_choice():
    """huggingface's load_balancing_loss_func on one layer: experts x
    sum_e (pairs sent to e / tokens) x mean probability of e; both
    paths report the same."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (40, 8)) * 3.0
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, 3)
    mask = jax.nn.one_hot(chosen, 8)  # [n, k, E]
    want = 8 * float(jnp.sum(jnp.mean(mask, axis=0) * jnp.mean(probs, axis=0)[None]))
    counts = moe.expert_counts(chosen, 8)
    got = moe.router_losses(logits, probs, counts)
    assert float(got["aux_loss"]) == pytest.approx(want, rel=1e-6)
    _, _, onehot = top_k_gating(logits, top_k=3, capacity=40)
    assert float(onehot["aux_loss"]) == pytest.approx(want, rel=1e-6)
    assert float(onehot["z_loss"]) == pytest.approx(
        float(got["z_loss"]), rel=1e-6
    )


@pytest.mark.parametrize("axes", [
    {"data": 2}, {"fsdp": 2}, {"data": 2, "fsdp": 2},
])
def test_sorted_path_on_a_mesh_sorts_each_shard(axes):
    """Tokens sharded over ``data`` / ``fsdp`` and no ``expert`` axis:
    each device sorts its own tokens, the outputs, the auxiliary loss
    (a mean over all tokens) and the weights' gradients (summed over
    the mesh) are the single device's."""
    from dlrover_tpu.parallel.mesh import under_mesh

    size = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:size])
    cfg = MoEConfig(
        n_embd=32, n_experts=8, expert_hidden=24, top_k=3, gated=True,
        dtype=jnp.float32,
    )
    params, x = _uneven_layer(cfg, tokens=(4, 12))

    def loss(p, x):
        y, aux = moe_mlp(p, x, cfg)
        return jnp.sum(y * y) + 100.0 * aux

    want, g_want = jax.value_and_grad(loss)(params, x)
    shardings = tree_shardings(mesh, moe_logical_axes(gated=True))
    p_sh = jax.tree.map(jax.device_put, params, shardings)
    x_sh = jax.device_put(
        x, NamedSharding(mesh, P(("data", "fsdp"), None, None))
    )
    fn = jax.jit(jax.value_and_grad(under_mesh(loss, mesh)))
    assert "shard_map" in str(jax.make_jaxpr(under_mesh(loss, mesh))(p_sh, x_sh))
    got, g_got = fn(p_sh, x_sh)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in g_want:
        scale = float(jnp.max(jnp.abs(g_want[name])))
        np.testing.assert_allclose(
            g_got[name], g_want[name], atol=1e-5 * scale, rtol=0
        )
