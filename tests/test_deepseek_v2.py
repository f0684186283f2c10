"""models/deepseek_v2.py against benchmark/reference/deepseek_v2.py, at
a small size on seeded weights: the loss and every gradient for a
dense layer and two expert layers, the rotation against the
reference's pair-by-pair form at the published parameters and through
the stated column permutation, the shared key part rotated once, the
softmax scale with YaRN's ``m^2``, the balance term a sequence, the
eight shares against the uncut layer, remat, and the normal path
through the trainer."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2 as reference
from dlrover_tpu.models import deepseek_v2 as model
from dlrover_tpu.models import llama, mellum, mla, moe
from dlrover_tpu.ops import rows_sum

TOY = os.path.join(cell_files.HERE, "testdata", "cells", "configs")
CELL = os.path.join(cell_files.HERE, "configs", "deepseek-v2-lite.json")


def _config(path=None, **changed):
    with open(path or os.path.join(TOY, "toy-deepseek.json")) as f:
        return dict(json.load(f), **changed)


@pytest.fixture(scope="module")
def float32():
    """The toy family in float32 and without remat, so that the
    comparison with the reference is of the mathematics, and one
    jitted loss-and-gradient on it. Weights large enough that every
    path weighs in the loss (at 0.02 and a width of 64 a score is a
    hundredth and the rotation moves the loss in its seventh digit)."""
    config = _config()
    config["assumed"] = dict(config["assumed"], initializer_range=0.1)
    cfg = dataclasses.replace(
        family.build(config)["cfg"], dtype=jnp.float32, remat="none"
    )
    params = jax.jit(lambda k: model.init_params(k, cfg))(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, cfg.block_size + 1), 0, cfg.vocab_size
    )
    batch = tok[:, :-1], tok[:, 1:]
    got = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)
    ))(params)
    return config, cfg, params, batch, got


def test_loss_and_every_gradient_are_the_reference_s(float32):
    """A dense layer and two expert layers; the reference is handed
    the weights in the published column order and its gradients come
    back through the same permutation."""
    config, cfg, params, batch, (got, grads) = float32
    assert cfg.ffns == ("dense", "moe", "moe")
    assert sorted(params["layers"]) == ["0_mla_dense", "1_mla_moe", "2_mla_moe"]
    want, ref = jax.value_and_grad(lambda p: reference.loss(
        model.published_layout(p, cfg), *batch, config=config
    ))(params)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    plain = jax.jit(lambda p: model.loss_fn(p, *batch, cfg=cfg))(params)
    assert abs(float(plain) - float(want)) < 2e-6 * float(want)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref)
    ):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0.0, name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(b))),
            err_msg=name,
        )
    # Without the permutation the two are different models: the
    # reference turns adjacent pairs and the program split halves.
    other = reference.loss(params, *batch, config=config)
    assert abs(float(other) - float(want)) > 1e-5 * float(want)


def test_the_rotation_is_the_published_pairing_through_the_permutation():
    """``llama.apply_rope`` on the program's column order is the
    reference's pair-by-pair rotation on the published order, at the
    published parameters (theta 10000, YaRN 40 over 4096, beta 32 and
    1, both mscales 0.707): the same numbers in permuted columns, so
    every dot product of a rotated query and key part is the same."""
    config = _config(CELL)
    cfg = family.build(config)["cfg"]
    assert (cfg.qk_rope, cfg.rope.rope_type, cfg.rope.factor) == (64, "yarn", 40)
    assert cfg.rope.attention_factor == 1.0  # m(0.707) / m(0.707)
    t = 6000  # past the 4,096 original positions
    cos, sin = mellum.rope_table(cfg.rope, 64, t)
    columns = model.rope_columns(64)
    assert columns.tolist() == list(range(0, 64, 2)) + list(range(1, 64, 2))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 3, 64))
    published = reference.rotate(x, config)
    here = llama.apply_rope(x[..., columns], cos, sin)
    np.testing.assert_allclose(here, published[..., columns], atol=2e-3, rtol=0)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, t, 1, 64))
    dots = lambda a, b: jnp.einsum("bthd,bsgd->bhts", a[:, -8:], b[:, ::500])
    np.testing.assert_allclose(
        dots(here, llama.apply_rope(y[..., columns], cos, sin)),
        dots(published, reference.rotate(y, config)), atol=2e-2, rtol=0,
    )
    # ... and by hand for one pair: channels (2i, 2i + 1) turn by t f_i.
    f = reference.frequencies(config)
    pos, i = 5000, 20
    a, b = float(x[0, pos, 0, 2 * i]), float(x[0, pos, 0, 2 * i + 1])
    c, s = math.cos(pos * f[i]), math.sin(pos * f[i])
    np.testing.assert_allclose(
        published[0, pos, 0, 2 * i: 2 * i + 2], [a * c - b * s, b * c + a * s],
        atol=2e-3,
    )


def test_yarn_frequencies_at_the_published_parameters():
    """dim(beta) = 64 ln(4096 / (2 pi beta)) / (2 ln 10000): 10.47 and
    22.51, so the ramp rises from dimension 10 to 23: a frequency
    below keeps its plain value, one above is divided by 40."""
    config = _config(CELL)
    f = np.asarray(reference.frequencies(config))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    ratio = f[11:23] / plain[11:23]
    assert np.all(np.diff(ratio) < 0) and 1 / 40 < ratio[-1] < ratio[0] < 1
    cfg = family.build(config)["cfg"]
    _, sin = mellum.rope_table(cfg.rope, 64, 2)
    np.testing.assert_allclose(np.arcsin(np.asarray(sin[1])), f, rtol=2e-5)
    # At factor 1 the table is the plain one, the scale plain too.
    one = dataclasses.replace(cfg, rope_factor=1.0)
    assert one.rope == mellum.Rope(theta=10000.0)
    assert one.softmax_scale == 192 ** -0.5
    unscaled = dict(config, rope_scaling=dict(config["rope_scaling"], factor=1))
    np.testing.assert_allclose(reference.frequencies(unscaled), plain, rtol=1e-12)
    assert reference.softmax_scale(unscaled) == 192 ** -0.5
    np.testing.assert_allclose(
        reference.frequencies(dict(config, rope_scaling=None)), plain, rtol=1e-12
    )


def test_the_softmax_scale_carries_mscale_squared():
    config = _config(CELL)
    cfg = family.build(config)["cfg"]
    m = 0.1 * 0.707 * math.log(40) + 1
    assert f"{m:.6f} {192 ** -0.5 * m * m:.6f}" == "1.260804 0.114721"
    assert cfg.softmax_mscale == pytest.approx(m, rel=1e-12)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert reference.softmax_scale(config) == pytest.approx(
        cfg.softmax_scale, rel=1e-12
    )


def test_the_mixer_rotates_the_key_part_once_and_gives_the_scale():
    """The attention callable is handed the rotated ``k_r`` as every
    head's last columns, the same numbers a head (one rotation on
    ``[B, T, 1, d_r]``, then the broadcast), the unrotated parts as
    projected, values of their own width, and the scale with ``m^2``."""
    cfg = model.DeepseekV2Config.tiny()
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    lp = params["layers"]["1_mla_moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 40, cfg.n_embd))
    cos, sin = mellum.rope_table(cfg.rope, cfg.qk_rope, 40)
    seen = {}

    def attn_fn(q, k, v, scale=None):
        seen.update(q=q, k=k, v=v, scale=scale)
        return jnp.zeros(q.shape[:3] + (cfg.v_head,), q.dtype)

    calls = []
    honest = llama.apply_rope
    try:
        llama.apply_rope = lambda x, c, s: (calls.append(x.shape), honest(x, c, s))[1]
        mla.mla_mixer(u, lp, attn_fn, cfg, cfg.softmax_scale, (cos, sin))
    finally:
        llama.apply_rope = honest
    assert sorted(calls) == [(2, 40, 1, 16), (2, 40, 4, 16)]
    assert seen["scale"] == cfg.softmax_scale
    assert seen["scale"] == pytest.approx(
        32 ** -0.5 * (0.1 * 0.707 * math.log(4) + 1) ** 2
    )
    q, k, v = seen["q"], seen["k"], seen["v"]
    assert q.shape == k.shape == (2, 40, 4, 32) and v.shape == (2, 40, 4, 16)
    latent = u @ lp["w_kva"]
    k_r = honest(latent[..., None, cfg.kv_rank:], cos, sin)
    for head in range(cfg.n_head):
        np.testing.assert_array_equal(k[:, :, head, 16:], k_r[:, :, 0])
    assert float(jnp.max(jnp.abs(k_r[:, 1:] - latent[:, 1:, None, cfg.kv_rank:]))) > 1e-3
    np.testing.assert_array_equal(k_r[:, 0], latent[:, 0, None, cfg.kv_rank:])
    plain_q = (u @ lp["wq"]).reshape(2, 40, 4, 32)
    np.testing.assert_array_equal(q[..., :16], plain_q[..., :16])
    np.testing.assert_allclose(
        q[..., 16:], honest(plain_q[..., 16:], cos, sin), rtol=1e-6
    )
    # k_r is not normed; the latent is.
    c = llama._rms_norm(latent[..., :cfg.kv_rank], lp["kv_norm"], cfg.rms_eps)
    kv = (c @ lp["w_kvb"]).reshape(2, 40, 4, 32)
    np.testing.assert_allclose(k[..., :16], kv[..., :16], rtol=1e-6)
    np.testing.assert_allclose(v, kv[..., 16:], rtol=1e-6)


def test_the_balance_term_is_per_sequence_and_over_top_k():
    """Two sequences routed unequally (one's tokens pushed onto one
    expert): the program's term is the reference's, the mean of the
    sequences' own ``sum_e f_e P_e``, and not the batch's."""
    cfg = model.DeepseekV2Config.tiny()
    config = _config()
    router = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (cfg.n_embd, 16))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, cfg.n_embd))
    h = h.at[0, :, 0].set(3.0)
    router = router.at[0, 7].add(4.0)
    got = float(model.balance_loss(h, router, cfg))
    _, balance = reference.router_weights(h, router, config)
    assert got == pytest.approx(cfg.aux_loss_weight * float(balance), rel=1e-5)

    def one(tokens):  # a sequence's own term, by hand
        p = jax.nn.softmax(tokens @ router, axis=-1)
        _, chosen = jax.lax.top_k(p, 4)
        count = np.bincount(np.asarray(chosen).reshape(-1), minlength=16)
        f = count * 16 / (tokens.shape[0] * 4)
        return float(np.sum(f * np.asarray(jnp.mean(p, axis=0))))

    by_hand = (one(h[0]) + one(h[1])) / 2
    assert float(balance) == pytest.approx(by_hand, rel=1e-5)
    assert one(h[0]) > 1.2 * one(h[1])  # unequal routing
    batch = one(h.reshape(-1, cfg.n_embd))
    assert abs(batch - by_hand) > 1e-3 * by_hand
    # An even router reads 1: every choice counted, over top_k.
    even = float(model.balance_loss(
        jnp.zeros_like(h), jnp.zeros_like(router), cfg
    ))
    assert even == pytest.approx(cfg.aux_loss_weight * 1.0, rel=1e-6)


_LAYER = {
    "num_experts_per_tok": 4, "norm_topk_prob": False, "scoring_func": "softmax",
    "topk_method": "greedy", "routed_scaling_factor": 1,
}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Shares of 2 of 16 experts from expert 0, 2, ..., 14 (the cell's
    0, 8, ..., 56 of 64 at the test's scale), softmax scoring, the
    chosen weights as they are: the eight chips' routed parts and the
    shared experts counted once add up to the uncut reference's layer,
    and each share alone is the reference's share."""
    whole = moe.MoEConfig(
        n_embd=32, n_experts=16, expert_hidden=16, top_k=4, gated=True,
        renorm_top_k=False, scoring="softmax", shared_hidden=32, held=16,
        dtype=jnp.float32,
    )
    params = moe.init_moe_params(jax.random.PRNGKey(3), whole)
    params = jax.tree.map(lambda a: a * 10 if a.ndim == 3 else a, params)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32))
    uncut, _ = reference.expert_layer(x, params, _LAYER, 0)
    shared = reference.swiglu(x, params["shared"])
    no_shared = {k: v for k, v in params.items() if k != "shared"}
    routed = jnp.zeros_like(x)
    for first in range(0, 16, 2):
        cfg = dataclasses.replace(whole, first_expert=first, held=2)
        mine = {
            k: (v[first: first + 2] if k in ("wi", "wg", "wo") else v)
            for k, v in params.items()
        }
        part, aux = moe.moe_mlp(mine, x, cfg)
        want, _ = reference.expert_layer(x, mine, _LAYER, first)
        np.testing.assert_allclose(part, want, rtol=1e-4, atol=1e-6)
        assert float(aux) == 0.0  # the held path returns no router loss
        # What every chip computes alike is counted once below.
        routed = routed + part - shared
    np.testing.assert_allclose(routed + shared, uncut, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(routed))) > 0.05 * float(jnp.max(jnp.abs(uncut)))
    # With the renormalisation the layer is another one.
    other, _ = reference.expert_layer(
        x, no_shared, dict(_LAYER, norm_topk_prob=True), 0
    )
    assert float(jnp.max(jnp.abs(other - (uncut - shared)))) > 1e-2 * float(
        jnp.max(jnp.abs(uncut - shared))
    )


def test_the_cell_s_tree_and_its_count():
    built = family.build(_config(CELL))
    cfg = built["cfg"]
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    assert sorted(shapes["layers"]) == [
        "0_mla_dense", "1_mla_moe", "2_mla_moe", "3_mla_moe", "4_mla_moe",
        "5_mla_moe",
    ]
    layer = shapes["layers"]["3_mla_moe"]
    assert layer["wq"].shape == (2048, 3072)
    assert layer["w_kva"].shape == (2048, 576)
    assert layer["w_kvb"].shape == (512, 4096)
    assert layer["w_o"].shape == (2048, 2048)
    assert layer["moe"]["wi"].shape == (8, 2048, 1408)
    assert layer["moe"]["router"].shape == (2048, 64)
    assert layer["moe"]["shared"]["w_gate"].shape == (2048, 2816)
    assert "router_bias" not in layer["moe"]
    assert shapes["layers"]["0_mla_dense"]["w_gate"].shape == (2048, 10944)
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert count(shapes) == 635_466_752
    assert count(layer) == 100_405_760
    assert count(shapes["layers"]["0_mla_dense"]) == 81_007_104
    for shape, ax in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(built["axes"], is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(shape.shape) == len(ax)
    mcfg = cfg.moe_cfg
    assert (mcfg.scoring, mcfg.renorm_top_k, mcfg.routed_scale) == (
        "softmax", False, 1.0
    )
    assert not mcfg.choice_bias and mcfg.shared_hidden == 2816
    # 8 of 64 held, 6 a token: rows for two held choices a token.
    assert moe.covered_choices(mcfg)[0] == 2
    assert moe.covered_choices(mcfg)[1] == pytest.approx(0.0222, abs=1e-4)
    assert moe.rows_cap(8192, mcfg) == 16384
    # The rows' sum at the cell's widths: tiles of 256 tokens, chunks
    # of 128 rows, a list of 128 + 32 x 8 visits.
    assert rows_sum.layout(8192, 16384, 8) == {
        "tile": 256, "visits": 384
    }
    with pytest.raises(ValueError, match="dense layers"):
        model.DeepseekV2Config(n_layer=2, first_dense=3)


@pytest.fixture(scope="module")
def toy():
    built = family.build(_config())
    params = jax.jit(built["init"])(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, built["seq_len"] + 1), 0, built["vocab"]
    )
    return built, params, (tok[:, :-1], tok[:, 1:])


def test_the_model_is_the_reference_in_bf16(toy):
    built, params, batch = toy
    got = float(jax.jit(built["loss"])(params, *batch))
    want = float(built["reference_loss"](params, *batch))
    assert abs(got - want) < 3e-4 * want


def test_remat_full_keeps_by_name_and_gives_the_same_gradients(toy):
    from dlrover_tpu.accelerate import remat

    built, params, batch = toy
    assert built["cfg"].remat == "full"
    # The dense layer and one expert layer: both kinds of block.
    cfg = dataclasses.replace(built["cfg"], n_layer=2)
    params = dict(params, layers={
        name: params["layers"][name] for name in cfg.layer_names
    })
    plain = dataclasses.replace(cfg, remat="none")
    full = jax.jit(jax.grad(lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)))(params)
    none = jax.jit(jax.grad(lambda p: model.loss_fn_fused(p, *batch, cfg=plain)))(params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(full), jax.tree.leaves(none)
    ):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        name = jax.tree_util.keystr(path)
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a))) > 0.0, name
        # bf16 activations: what is kept is rounded once more or less.
        assert float(jnp.linalg.norm(a - b)) <= 2e-2 * float(
            jnp.linalg.norm(b)
        ) + 1e-12, name
    assert {
        remat.ATTN_IN, remat.MLA_LATENT, remat.MLP_HIDDEN, remat.ROUTER_LOGITS
    } <= set(remat.last_kept())


def test_normal_path_takes_steps_and_the_loss_falls():
    """auto_accelerate and ElasticTrainer.train_step on the family's
    parameter tree, two micro-batches accumulated a step; one step
    program."""
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    built = family.build(_config())
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, built["seq_len"] + 1), 0, built["vocab"]
    )
    tok, tgt = tok[:, :-1], tok[:, 1:]
    res = auto_accelerate(
        built["init"], built["loss"], built["axes"], (tok, tgt),
        learning_rate=3e-3,
        strategy=Strategy(
            mesh_shape=(("data", 1),), optimizer="adamw", micro_batch_size=1,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, built["loss"], res.optimizer, global_batch_size=2,
        micro_batch_size=1,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(4):
        params, opt_state, step_loss = trainer.train_step(
            params, opt_state, np.asarray(tok), np.asarray(tgt)
        )
        losses.append(float(step_loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer._compiled._cache_size() == 1


def test_events_and_scopes_say_what_was_traced(toy):
    from dlrover_tpu import obs
    from dlrover_tpu.obs import profiling

    built, params, batch = toy
    cfg = built["cfg"]
    tracer = obs.configure_tracer()
    try:
        lowered = jax.jit(jax.value_and_grad(built["loss"])).lower(
            params, *batch
        )
        events = lambda name: [
            e for e in tracer.events() if e["name"] == name
        ]
        (pattern,) = events("hybrid.pattern")
        assert pattern["layer_types"] == ["mla+dense", "mla+moe", "mla+moe"]
        assert (pattern["mla_layers"], pattern["dense_layers"]) == (3, 1)
        assert (pattern["moe_layers"], pattern["in_line"]) == (2, 3)
        assert pattern["scanned"] == 0 and pattern["rotation"] == "yarn"
        attn = events("mla.attn")[0]
        assert (attn["d_qk"], attn["d_v"], attn["heads"]) == (32, 16, 4)
        assert attn["rotated"] is True and attn["rope_dim"] == 16
        assert attn["padded_to"] == 32
        assert attn["scale"] == cfg.softmax_scale
        assert attn["mscale"] == cfg.softmax_mscale
        held = events("moe.held")[0]
        assert held["router_experts"] == 16 and held["held"] == 2
        assert held["first_expert"] == 4 and held["scoring"] == "softmax"
        assert held["rows_cap"] == moe.rows_cap(held["tokens"], cfg.moe_cfg)
        assert held["covered_choices"] == 2
        sizes = rows_sum.layout(held["tokens"], held["rows_cap"], 2)
        assert held["sum_tile"] == sizes["tile"] == held["tokens"]
        assert held["sum_chunk_visits"] == sizes["visits"]
    finally:
        obs.disable_tracer()
    assert {
        "mla", "mla_rope", "moe_routed", "moe_shared", "moe_balance"
    } <= profiling.SCOPES
    text = lowered.as_text(debug_info=True)
    for scope in ("attn/mla/mla_rope", "mlp/moe_routed", "mlp/moe_shared",
                  "mlp/moe_balance"):
        assert scope in text, scope
    assert "moe_balance/moe_route" not in text
    assert "moe_route/moe_balance" not in text
    assert profiling.scope_of("jit(f)/layers/attn/mla/mla_rope/mul")[
        "scope"
    ] == "layers/attn/mla/mla_rope"
