"""models/kimi_linear.py, ops/kda.py and the held path of
models/moe.py against benchmark/reference/kimi_linear.py, at a small
size on seeded weights: the chunked delta rule against the
recurrence (forward and every gradient, float32 and bf16, at decays
strong enough that a naive ``exp(-G)`` would overflow), latent
attention against plain attention, the router's choice with and
without its bias, every share's routed part plus the shared expert
against the uncut layer, and the trainer's accumulation through the
family."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear as reference
from dlrover_tpu.models import kimi_linear as model
from dlrover_tpu.models import mla, moe
from dlrover_tpu.ops import kda, rows_sum
from dlrover_tpu.ops.flash_attention import flash_attention

TOY = os.path.join(cell_files.HERE, "testdata", "cells", "configs")


def _rule_operands(seed, dtype, t=128, heads=3, dk=16, dv=8, strongest=3.0):
    """Normalised q and k, and log decays down to ``-strongest`` a
    token: over a chunk of 64 their running sum passes -100, where
    ``exp(-G)`` is beyond float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, t, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (2, t, heads, dk)))
    v = jax.random.normal(ks[2], (2, t, heads, dv))
    g = -jnp.exp(jax.random.uniform(
        ks[3], (2, t, heads, dk), minval=np.log(0.5), maxval=np.log(strongest)
    ))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, heads)))
    w = jax.random.normal(ks[5], (2, t, heads, dv))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta), w


@pytest.mark.parametrize("t", [128, 72])
@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)]
)
def test_chunked_rule_is_the_recurrence(dtype, tol, t):
    operands, w = _rule_operands(1, dtype, t=t)
    g = operands[3]
    # The hazard is there: a naive factorisation would overflow.
    assert float(jnp.min(jnp.cumsum(g[:, :64], axis=1))) < -89.0
    chunked = lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32) * w)
    plain = lambda *a: jnp.sum(reference.delta_rule(*a) * w)
    o = kda.kda(*operands).astype(jnp.float32)
    want = reference.delta_rule(*(x.astype(jnp.float32) for x in operands))
    assert np.isfinite(np.asarray(o)).all()
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(o - want))) < tol * scale
    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*operands)
    ref = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(
        *(x.astype(jnp.float32) for x in operands)
    )
    for name, a, b in zip("q k v g beta".split(), got, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert np.isfinite(np.asarray(a)).all(), name
        assert float(jnp.max(jnp.abs(a - b))) < tol * float(
            jnp.max(jnp.abs(b))
        ), name


def test_the_program_s_recurrence_is_the_reference_s():
    operands, _ = _rule_operands(2, jnp.float32, t=40)
    np.testing.assert_allclose(
        kda.recurrence(*operands), reference.delta_rule(*operands),
        rtol=1e-5, atol=1e-6,
    )


def test_unit_lower_inverse_and_its_backward():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * 0.2
    inverse = kda._unit_lower_inverse(a)
    eye = jnp.eye(64)
    np.testing.assert_allclose(inverse @ (eye + a), jnp.broadcast_to(eye, a.shape), atol=1e-4)
    w = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda a: jnp.sum(kda._unit_lower_inverse(a) * w))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * w))(a)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("beta", [0.5, 0.9, 0.99])
def test_the_inverse_is_stable_where_keys_repeat(beta):
    """A run of one token: every key the same, ``A`` = beta x ones
    below the diagonal. The inverse's entries are at most 1; the
    product (I + N)(I + N^2)... of the nilpotent N = -A reads 1e2 to
    1e10 there in float32, which is what a step of training on the
    benchmark's stream met on the chip (PERF.md section 6, PR 53)."""
    a = jnp.tril(jnp.full((2, 64, 64), beta, jnp.float32), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(a[0], np.float64))
    got = np.asarray(kda._unit_lower_inverse(a))[0]
    assert np.abs(got).max() <= 1.0 + 1e-6
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_rule_stays_finite_on_a_run_of_one_token():
    """Identical keys and values along a whole sequence, weak decay,
    beta near 1: output and gradients finite and the recurrence's."""
    t, heads, d = 128, 2, 16
    k = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(0), (1, 1, heads, d)), (1, t, heads, d)
    )
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, v = k * d ** -0.5, jnp.ones((1, t, heads, d))
    g = jnp.full((1, t, heads, d), -1e-3)
    beta = jnp.full((1, t, heads), 0.97)
    o = kda.kda(q, k, v, g, beta)
    want = reference.delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    grads = jax.grad(lambda *a: jnp.sum(kda.kda(*a) ** 2), (0, 1, 2, 3, 4))(
        q, k, v, g, beta
    )
    ref = jax.grad(lambda *a: jnp.sum(reference.delta_rule(*a) ** 2), (0, 1, 2, 3, 4))(
        q, k, v, g, beta
    )
    for a, b in zip(grads, ref):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("flash", [False, True])
def test_latent_attention_is_plain_attention(flash):
    """The mixer against the reference's, and the flash kernels at a
    query/key head size that is not the value head size."""
    cfg = dataclasses.replace(model.KimiLinearConfig.tiny(), use_flash_attention=flash)
    config = _toy_config()
    shapes = model._layer_shapes(cfg, model.MLA, model.DENSE)
    keys = jax.random.split(jax.random.PRNGKey(5), len(shapes))
    lp = {
        name: model._init_leaf(k, name, shape, dataclasses.replace(cfg, init_std=0.2))
        for (name, (shape, _)), k in zip(sorted(shapes.items()), keys)
    }
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.n_embd))
    attn = model.default_attention_for(cfg)
    if flash:
        attn = lambda q, k, v, scale: flash_attention(
            q, k, v, scale=scale, block_q=16, block_k=32
        )
    scale = (cfg.qk_nope + cfg.qk_rope) ** -0.5
    mixer = lambda u, lp: mla.mla_mixer(u, lp, attn, cfg, scale)
    loss = lambda u, lp: jnp.sum(jnp.sin(mixer(u, lp)))
    want = lambda u, lp: jnp.sum(jnp.sin(reference.mla_mixer(u, lp, config)))
    np.testing.assert_allclose(
        mixer(u, lp), reference.mla_mixer(u, lp, config),
        rtol=2e-4, atol=2e-5,
    )
    got, ref = jax.grad(loss, (0, 1))(u, lp), jax.grad(want, (0, 1))(u, lp)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def _toy_config():
    with open(os.path.join(TOY, "toy-kimi.json")) as f:
        return json.load(f)


def _moe_cfg(**changed):
    return dataclasses.replace(
        moe.MoEConfig(
            n_embd=32, n_experts=16, expert_hidden=24, top_k=4, gated=True,
            renorm_top_k=True, scoring="sigmoid", choice_bias=True,
            routed_scale=2.446, shared_hidden=24, first_expert=4, held=4,
            dtype=jnp.float32,
        ),
        **changed,
    )


def _moe_params(cfg, seed=0):
    params = moe.init_moe_params(jax.random.PRNGKey(seed), cfg)
    params = jax.tree.map(lambda a: a * 20 if a.ndim == 3 else a, params)
    params["router"] = params["router"] * 30
    params["router_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 5), (cfg.n_experts,)
    )
    return params


def test_the_bias_chooses_and_does_not_weigh():
    cfg = _moe_cfg()
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    scores = jax.nn.sigmoid(logits)
    weights, experts = moe.route(logits, bias, cfg)
    _, want = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(experts, want)
    # Another choice than without it, and weights that never saw it.
    _, unbiased = moe.route(logits, jnp.zeros_like(bias), cfg)
    assert not np.array_equal(np.asarray(experts), np.asarray(unbiased))
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        weights, 2.446 * picked / jnp.sum(picked, -1, keepdims=True), rtol=1e-6
    )
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.446, rtol=1e-5)
    stats = moe.routing_stats(logits, 4, cfg, bias)
    here = np.isin(np.asarray(experts), np.arange(4, 8)).sum() / 64
    assert float(stats["held_pairs_per_token"]) == pytest.approx(here)
    # Counted by hand: 64 tokens x 3 covered choices are one buffer,
    # which these pairs stay within; with none held it still runs one.
    assert moe.rows_cap(64, cfg) == 192 and 0 < here * 64 <= 192
    assert int(stats["held_row_blocks"]) == 1
    flat = jnp.zeros_like(bias)
    none = moe.routing_stats(logits.at[:, 4:8].set(-1e9), 4, cfg, flat)
    assert float(none["held_pairs_per_token"]) == 0.0
    assert int(none["held_row_blocks"]) == 1
    every = moe.routing_stats(logits.at[:, 4:8].set(1e9), 4, cfg, flat)
    assert float(every["held_pairs_per_token"]) == 4.0
    assert int(every["held_row_blocks"]) == 2 == -(-64 * 4 // 192)
    # Without a configuration: every expert here, one buffer.
    assert int(moe.routing_stats(logits, 4)["held_row_blocks"]) == 1
    # No gradient reaches the bias.
    grad = jax.grad(lambda b: jnp.sum(moe.route(logits, b, cfg)[0] ** 2))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def _reference_layer(params, x, config, first):
    flat = x.reshape(-1, x.shape[-1])
    return reference.routed_experts(flat, params, config, first).reshape(x.shape)


_LAYER_CONFIG = {
    "moe_router_activation_func": "sigmoid", "num_experts_per_token": 4,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
}


@pytest.mark.parametrize(
    "first,held,rows",
    [(4, 4, None), (0, 16, None), (8, 4, 16), (4, 4, 32)],
    ids=["a_share", "every_expert", "past_the_buffer", "two_buffers"],
)
def test_held_experts_are_the_reference_s(monkeypatch, first, held, rows):
    """Forward and every gradient; ``past_the_buffer`` and
    ``two_buffers`` shrink the buffer under the held pairs (a
    ``rows_cap`` of their own in the rule's place: 16 and 32 rows for
    a mean load of 48), so the layer runs several blocks of sorted
    rows (and skips the rest of the blocks there can be) and still
    drops nothing."""
    if rows:
        monkeypatch.setattr(moe, "rows_cap", lambda n, cfg: rows)
    cfg = _moe_cfg(first_expert=first, held=held)
    params = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    logits = x.reshape(-1, 32) @ params["router"]
    stats = moe.routing_stats(logits, 4, cfg, params["router_bias"])
    pairs = round(float(stats["held_pairs_per_token"]) * 48)
    cap = moe.rows_cap(48, cfg)
    assert cap == (rows or (144 if held == 4 else 192))
    assert (pairs > cap) == bool(rows)
    # Blocks with a row in them, of the blocks there can be.
    assert int(stats["held_row_blocks"]) == -(-pairs // cap)
    if rows:
        assert 1 < -(-pairs // cap) < -(-48 * 4 // cap)

    def want(params, x):
        shared = reference.swiglu(x, params["shared"])
        return _reference_layer(params, x, _LAYER_CONFIG, first) + shared

    got = moe.moe_mlp(params, x, cfg)[0]
    np.testing.assert_allclose(got, want(params, x), rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda p, x: jnp.sum(moe.moe_mlp(p, x, cfg)[0] * w), (0, 1))(params, x)
    ref = jax.grad(lambda p, x: jnp.sum(want(p, x) * w), (0, 1))(params, x)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref)
    ):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-3 * float(jnp.max(jnp.abs(b)) + 1e-6),
            err_msg=jax.tree_util.keystr(path),
        )


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Four shares of 4 of 16 experts: the routed parts the four chips
    compute, with the shared expert every chip computes alike counted
    once, are the uncut reference's layer."""
    whole = _moe_cfg(first_expert=0, held=16)
    params = _moe_params(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32))
    config = dict(_LAYER_CONFIG)
    uncut = _reference_layer(params, x, config, 0) + reference.swiglu(
        x, params["shared"]
    )
    total = jnp.zeros_like(x)
    for share in range(4):
        cfg = _moe_cfg(first_expert=4 * share, held=4, shared_hidden=0)
        mine = {
            k: (v[4 * share: 4 * share + 4] if k in ("wi", "wg", "wo") else v)
            for k, v in params.items() if k != "shared"
        }
        total = total + moe.moe_mlp(mine, x, cfg)[0]
    total = total + reference.swiglu(x, params["shared"])
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("changed", [
    {"scoring": "sigmoid"}, {"choice_bias": True}, {"routed_scale": 2.446},
    {"shared_hidden": 16},
])
def test_what_the_held_path_alone_knows_is_refused_without_it(changed):
    """The sorted and the one-hot paths would drop a sigmoid score, a
    choice bias, a scale or a shared expert without a word; with
    ``held`` (all the experts, if need be) the layer has them."""
    plain = dict(n_embd=32, n_experts=8, expert_hidden=16, top_k=2, gated=True)
    with pytest.raises(ValueError, match="held path"):
        moe.MoEConfig(**plain, **changed)
    cfg = moe.MoEConfig(**plain, **changed, held=8)
    assert cfg.experts_here == 8
    with pytest.raises(ValueError, match="scoring"):
        moe.MoEConfig(**plain, scoring="tanh", held=8)
    with pytest.raises(ValueError, match="not among"):
        moe.MoEConfig(**plain, first_expert=4, held=8)


def test_a_softmax_layer_with_every_expert_held_is_unchanged():
    """``held`` 0 and softmax scoring: models/moe.py takes the sorted
    path it took before the held one existed."""
    cfg = moe.MoEConfig(n_embd=32, n_experts=8, expert_hidden=16, top_k=2,
                        gated=True, dtype=jnp.float32)
    assert cfg.experts_here == 8 and not cfg.held
    params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"router", "wi", "wo", "wg"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    text = jax.jit(lambda p, x: moe.moe_mlp(p, x, cfg)).lower(params, x).as_text()
    assert "moe_routed" not in text and "moe_shared" not in text


@pytest.fixture(scope="module")
def toy():
    built = family.build(_toy_config())
    params = jax.jit(built["init"])(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, built["seq_len"] + 1), 0, built["vocab"]
    )
    return built, params, (tok[:, :-1], tok[:, 1:])


def test_the_model_is_the_reference(toy):
    built, params, batch = toy
    got = float(jax.jit(built["loss"])(params, *batch))
    want = float(built["reference_loss"](params, *batch))
    assert abs(got - want) < 3e-4 * want
    cfg = built["cfg"]
    logits = model.forward(params, batch[0], cfg)
    ref = reference.logits(params, batch[0], _toy_config())
    # bf16 activations through four layers against float32.
    assert float(jnp.max(jnp.abs(logits - ref))) < 0.1 * float(jnp.std(ref))


@pytest.mark.parametrize("heads,d", [(3, 16), (4, 128)])
def test_per_head_products_are_the_reductions_over_a_4d_view(heads, d):
    """``unit()`` and the output's RMS norm as the mixer forms them,
    a head's sum and its broadcast back as products with the heads'
    0/1 membership on ``[B, T, H*d]``, against the reductions over
    ``[B, T, H, d]``'s last axis: values and gradients to float32
    rounding."""
    head_sum, on_channels = model._per_head(heads, d)
    x, w = jax.random.normal(jax.random.PRNGKey(7), (2, 2, 24, heads * d))
    x = x * jnp.exp(jax.random.normal(jax.random.PRNGKey(8), x.shape))

    def wide(x, eps, mean):
        total = head_sum(jnp.square(x)) / (d if mean else 1)
        return x * on_channels(jax.lax.rsqrt(total + eps))

    def viewed(x, eps, mean):
        x = x.reshape(x.shape[:-1] + (heads, d))
        total = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(total / (d if mean else 1) + eps)
        return x.reshape(x.shape[:-2] + (heads * d,))

    for eps, mean in ((model.L2_EPS, False), (1e-5, True)):
        for fn in (
            lambda f, x: f(x, eps, mean),
            jax.grad(lambda f, x: jnp.sum(f(x, eps, mean) * w), argnums=1),
        ):
            np.testing.assert_allclose(
                fn(wide, x), fn(viewed, x), rtol=2e-6, atol=2e-6
            )
    np.testing.assert_array_equal(
        on_channels(jnp.arange(heads, dtype=jnp.float32)),
        jnp.repeat(jnp.arange(heads, dtype=jnp.float32), d),
    )


def test_the_mixer_is_the_reference_s(toy):
    """``kda_mixer`` alone, float32, on the toy's first layer: its
    ``[B, T, H*d]`` columns against the reference's 4-D heads and
    token-by-token rule, output and the gradient of every leaf."""
    built, params, _ = toy
    cfg = dataclasses.replace(built["cfg"], dtype=jnp.float32)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    lp = f32(params["layers"][cfg.layer_names[0]])
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 96, cfg.n_embd))
    w = jax.random.normal(jax.random.PRNGKey(6), u.shape)

    def both(mixer, last):
        def loss(u, lp):
            y = mixer(u, lp, last)
            return jnp.sum(y * w), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        ))(u, lp)
        return y, grads

    y, grads = both(model.kda_mixer, cfg)
    y_ref, grads_ref = both(reference.kda_mixer, _toy_config())
    scale = float(jnp.max(jnp.abs(y_ref)))
    assert float(jnp.max(jnp.abs(y - y_ref))) < 2e-5 * scale
    used = set(jax.tree.leaves(jax.tree.map(
        lambda g: bool(jnp.any(g != 0)), grads_ref[1]
    )))
    assert used == {True, False}  # the MLP's leaves are not the mixer's
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * max(
            float(jnp.max(jnp.abs(want))), 1e-30
        )


def test_every_parameter_but_the_bias_gets_a_gradient(toy):
    built, params, batch = toy
    grads = jax.jit(jax.grad(built["loss"]))(params, *batch)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        name = jax.tree_util.keystr(path)
        g = g.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert (float(jnp.max(jnp.abs(g))) == 0.0) == ("router_bias" in name), name


def test_parameter_count_and_axes_at_the_published_widths():
    with open(os.path.join(
        cell_files.HERE, "configs", "kimi-linear-48b-a3b.json"
    )) as f:
        config = json.load(f)
    built = family.build(config)
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert count(shapes) == 602_434_432
    layers = shapes["layers"]
    assert count(layers["0_kda_dense"]) == 103_219_872
    assert count(layers["3_mla_moe"]) == 93_410_560
    mixer = lambda tree: count({
        k: v for k, v in tree.items()
        if k not in ("moe", "rms1", "rms2", "w_gate", "w_up", "w_down")
    })
    assert mixer(layers["1_kda_moe"]) == 39_514_272
    assert mixer(layers["3_mla_moe"]) == 29_114_880
    axes = built["axes"]
    assert jax.tree.structure(
        jax.tree.map(lambda x: 0, shapes)
    ) == jax.tree.structure(
        jax.tree.map(lambda x: 0, axes, is_leaf=lambda x: isinstance(x, tuple))
    )
    for shape, ax in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(shape.shape) == len(ax)


def test_remat_full_keeps_by_name_and_gives_the_same_gradients(toy):
    from dlrover_tpu.accelerate import remat

    built, params, batch = toy
    cfg = built["cfg"]
    assert cfg.remat == "full"
    plain = dataclasses.replace(cfg, remat="none")
    full = jax.jit(jax.grad(lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)))(params)
    none = jax.jit(jax.grad(lambda p: model.loss_fn_fused(p, *batch, cfg=plain)))(params)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(none)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        # bf16 activations: what is kept is rounded once more or less.
        assert float(jnp.linalg.norm(a - b)) <= 2e-2 * float(
            jnp.linalg.norm(b)
        ) + 1e-12
    for name in ("KDA_IN", "KDA_O", "KDA_STATES", "MLA_LATENT"):
        assert getattr(remat, name) in remat.KEPT
    kept = set(remat.last_kept())
    assert kept, "a block under full names what it keeps"


@pytest.mark.parametrize(
    "mesh_shape,rows",
    [((("data", 1),), 2), ((("data", 2), ("fsdp", 2)), 4)],
    ids=["accumulates_two_micro_batches", "data2_fsdp2"],
)
def test_normal_path_takes_steps_and_the_loss_falls(mesh_shape, rows):
    """auto_accelerate and ElasticTrainer.train_step on the family's
    parameter tree: on one device two micro-batches are accumulated a
    step, on four the kernels and the held experts run once a device;
    one step program either way."""
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    built = family.build(_toy_config())
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (rows, built["seq_len"] + 1), 0, built["vocab"]
    )
    tok, tgt = tok[:, :-1], tok[:, 1:]
    devices = int(np.prod([n for _, n in mesh_shape]))
    res = auto_accelerate(
        built["init"], built["loss"], built["axes"], (tok[:2], tgt[:2]),
        learning_rate=3e-3,
        strategy=Strategy(
            mesh_shape=mesh_shape, optimizer="adamw", micro_batch_size=1,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, built["loss"], res.optimizer, global_batch_size=rows,
        micro_batch_size=1,
    )
    assert trainer.samples_per_step == rows
    assert rows // devices == (2 if devices == 1 else 1)
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    bias = np.asarray(params["layers"]["1_kda_moe"]["moe"]["router_bias"])
    losses = []
    for _ in range(4):
        params, opt_state, step_loss = trainer.train_step(
            params, opt_state, np.asarray(tok), np.asarray(tgt)
        )
        losses.append(float(step_loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer._compiled._cache_size() == 1
    # No gradient reaches the bias: only AdamW's decay touches it.
    moved = np.asarray(
        params["layers"]["1_kda_moe"]["moe"]["router_bias"]
    ) - bias
    assert float(np.max(np.abs(moved))) < 1e-5


def _events(tracer, name):
    return [e for e in tracer.events() if e["name"] == name]


def test_events_say_what_was_traced(toy):
    from dlrover_tpu import obs

    built, params, batch = toy
    cfg = built["cfg"]
    tracer = obs.configure_tracer()
    try:
        jax.jit(jax.value_and_grad(built["loss"])).lower(params, *batch)
        (pattern,) = _events(tracer, "hybrid.pattern")
        assert pattern["layer_types"] == [
            "kda+dense", "kda+moe", "mla+moe", "kda+moe"
        ]
        assert pattern["in_line"] == 4 and pattern["scanned"] == 0
        scan = _events(tracer, "kda.scan")[0]
        assert scan["chunk"] == 64 and scan["sub_block"] == 16
        assert scan["heads"] == cfg.n_head
        assert scan["state_dtype"] == "float32" and scan["states_kept"]
        # The mixer's columns go over as they lie; head size 16 is the
        # plain form's.
        assert scan["wide"] is True and scan["kernel"] is False
        attn = _events(tracer, "mla.attn")[0]
        assert (attn["d_qk"], attn["d_v"]) == (24, 16)
        held = _events(tracer, "moe.held")[0]
        assert held["router_experts"] == 16 and held["held"] == 4
        assert held["first_expert"] == 4 and held["top_k"] == 4
        assert held["scoring"] == "sigmoid"
        assert held["rows_cap"] == moe.rows_cap(held["tokens"], cfg.moe_cfg)
        assert held["row_blocks"] == -(
            -held["tokens"] * held["top_k"] // held["rows_cap"]
        ) == 2
        # 4 of 16 held, 4 a token: rows for three held choices a
        # token, three times the even load's; four in 1 draw of 1,820.
        assert held["covered_choices"] == 3 and held["cap_over_mean"] == 3.0
        assert held["tail"] == pytest.approx(1 / 1820)
        # The rows' sum is the kernel's at every shape: one tile of
        # the toy's tokens, a visit a chunk and one more a held expert.
        sizes = rows_sum.layout(held["tokens"], held["rows_cap"], 4)
        assert held["sum_tile"] == sizes["tile"] == held["tokens"]
        assert held["sum_chunk_visits"] == sizes["visits"]
        names = set().union(*(e["names"] for e in _events(tracer, "remat.kept")))
        from dlrover_tpu.accelerate import remat

        assert {remat.KDA_IN, remat.KDA_O, remat.KDA_STATES,
                remat.MLA_LATENT, remat.ATTN_IN, remat.MLP_HIDDEN,
                remat.ROUTER_LOGITS} <= names
    finally:
        obs.disable_tracer()
