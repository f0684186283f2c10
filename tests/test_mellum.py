"""models/mellum.py against benchmark/reference/mellum.py, at a small
size on seeded weights: the loss and every gradient for two periods
(scanned) and for one (in line), the band mask at sequence lengths
that are and are not multiples of the window, the YaRN table against
the reference's own at the published parameters and at factor 1, a
head size that is not ``hidden / heads``, the four shares' results
against the uncut layer, and the normal path through the trainer."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.families import mellum as family
from benchmark.reference import mellum as reference
from dlrover_tpu.models import llama, mellum as model, moe
from dlrover_tpu.ops import rows_sum

TOY = os.path.join(cell_files.HERE, "testdata", "cells", "configs")
CELL = os.path.join(cell_files.HERE, "configs", "mellum2-12b-a2.5b.json")


def _config(path=None, **changed):
    with open(path or os.path.join(TOY, "toy-mellum.json")) as f:
        return dict(json.load(f), **changed)


def _float32(config, layers):
    """The toy family on ``layers`` layers, float32 and without remat:
    the comparison with the reference is then of the mathematics."""
    config = dict(config, num_hidden_layers=layers)
    built = family.build(config)
    cfg = dataclasses.replace(built["cfg"], dtype=jnp.float32, remat="none")
    params = jax.jit(lambda k: model.init_params(k, cfg))(jax.random.PRNGKey(3))
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, cfg.block_size + 1), 0, cfg.vocab_size
    )
    return config, cfg, params, (tok[:, :-1], tok[:, 1:])


@pytest.mark.parametrize("layers,periods", [(6, 2), (3, 1)])
def test_loss_and_every_gradient_are_the_reference_s(layers, periods):
    """Two periods run under the scan over periods, one in line; the
    reference is a loop over the layers either way."""
    config, cfg, params, batch = _float32(_config(), layers)
    assert cfg.periods == periods and len(cfg.period) == 3
    assert jax.tree.leaves(params["periods"])[0].shape[0] == periods
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)
    ))(params)
    want, ref = jax.value_and_grad(
        lambda p: reference.loss(p, *batch, config=config)
    )(params)
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


@pytest.mark.parametrize("t", [48, 64, 41])
@pytest.mark.parametrize("flash", [False, True])
def test_the_band_is_the_reference_s_mask(t, flash):
    """Query i sees keys (i - 24, i]: at two whole windows, at a length
    that is no multiple of the window, and at an odd one; through the
    plain attention and the flash kernel, interpreted here."""
    from dlrover_tpu.ops.flash_attention import flash_attention

    cfg = dataclasses.replace(
        model.MellumConfig.tiny(), block_size=t, use_flash_attention=flash,
    )
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(ks[0], (2, t, 4, 24))
    k = jax.random.normal(ks[1], (2, t, 2, 24))
    v = jax.random.normal(ks[2], (2, t, 2, 24))
    attn_fn = model.default_attention_for(cfg)
    assert (getattr(attn_fn, "func", None) is flash_attention) == flash
    repeat = lambda x: jnp.repeat(x, 2, axis=2)
    for window in (24, None):
        got = (
            attn_fn(q, repeat(k), repeat(v), window=window) if window
            else attn_fn(q, repeat(k), repeat(v))
        )
        want = reference.attention(q, k, v, window)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # The band is a mask of its own: dropping it moves the result.
    assert float(jnp.max(jnp.abs(
        reference.attention(q, k, v, 24) - reference.attention(q, k, v)
    ))) > 1e-2
    # ... and by hand for one query: the last sees the last 24 keys.
    s = jnp.einsum("bhd,bkhd->bhk", q[:, -1], repeat(k)) / np.sqrt(24)
    p = jax.nn.softmax(s[..., t - 24:], axis=-1)
    by_hand = jnp.einsum("bhk,bkhd->bhd", p, repeat(v)[:, t - 24:])
    np.testing.assert_allclose(
        reference.attention(q, k, v, 24)[:, -1], by_hand, rtol=1e-4, atol=1e-5
    )


def test_yarn_table_at_the_published_parameters():
    """The program's table against the reference's own writing of the
    published formula, and the numbers the formula gives by hand:
    theta 500000, factor 16 from 8192 positions, beta 32 and 1."""
    published = _config(CELL)["rope_parameters"]
    for kind, entry in published.items():
        rope = family._rope(model, entry)
        cos, sin = model.rope_table(rope, 128, 8192)
        ref_cos, ref_sin = reference.rotation(entry, 128, 8192)
        np.testing.assert_allclose(cos, ref_cos, rtol=0, atol=2e-3)
        np.testing.assert_allclose(sin, ref_sin, rtol=0, atol=2e-3)
    yarn = published["full_attention"]
    assert yarn["rope_type"] == "yarn"
    assert published["sliding_attention"]["rope_type"] == "default"
    # dim(beta) = 128 ln(8192 / (2 pi beta)) / (2 ln 500000): 18.08 and
    # 34.98, so the ramp runs from dimension 18 to 35.
    cos, sin = model.rope_table(family._rope(model, yarn), 128, 2)
    _, plain = model.rope_table(model.Rope(theta=500000.0), 128, 2)
    factor = yarn["attention_factor"]
    assert abs(factor - (0.1 * np.log(16) + 1)) < 1e-12
    ratio = np.arcsin(np.asarray(sin[1]) / factor) / np.arcsin(
        np.asarray(plain[1])
    )
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-4)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-4)
    inside = ratio[19:35]
    assert np.all(np.diff(inside) < 0) and 1 / 16 < inside[-1] < inside[0] < 1
    np.testing.assert_allclose(np.asarray(cos[0]), factor, rtol=1e-6)


def test_yarn_at_factor_one_is_the_plain_table():
    yarn = model.Rope(
        rope_type="yarn", theta=500000.0, factor=1.0,
        original_max_position=8192, beta_fast=32.0, beta_slow=1.0,
        attention_factor=1.0,
    )
    for got, want in zip(
        model.rope_table(yarn, 128, 512),
        model.rope_table(model.Rope(theta=500000.0), 128, 512),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    entry = {"rope_type": "yarn", "rope_theta": 500000, "factor": 1,
             "original_max_position_embeddings": 8192, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.0}
    for got, want in zip(
        reference.rotation(entry, 128, 512),
        reference.rotation(
            {"rope_type": "default", "rope_theta": 500000}, 128, 512
        ),
    ):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="rope_type"):
        model.Rope(rope_type="linear")
    with pytest.raises(ValueError, match="original_max_position"):
        model.Rope(rope_type="yarn", factor=4.0, attention_factor=1.1)
    # ``attention_factor`` is read as published, by the program, the
    # family and the reference alike: none is made up where it is absent.
    with pytest.raises(ValueError, match="attention_factor"):
        model.Rope(rope_type="yarn", factor=4.0, original_max_position=32)
    del entry["attention_factor"]
    with pytest.raises(KeyError, match="attention_factor"):
        family._rope(model, entry)
    with pytest.raises(KeyError, match="attention_factor"):
        reference.rotation(entry, 128, 512)


def test_a_head_size_of_its_own():
    """32/4 heads of 128 on a hidden size of 2304: the query and
    output projections are 2304 x 4096 and back, and
    ``llama.attention_half`` takes the width from the heads, not from
    the hidden size. At the toy's 4/2 heads of 24 on 64 the half-block
    is the reference's."""
    built = family.build(_config(CELL))
    shapes = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    layer = shapes["periods"]["3_full_attention"]
    assert layer["wq"].shape == (1, 2304, 4096)
    assert layer["wk"].shape == layer["wv"].shape == (1, 2304, 512)
    assert layer["wo"].shape == (1, 4096, 2304)
    assert layer["moe"]["wi"].shape == (1, 16, 2304, 896)
    assert layer["moe"]["router"].shape == (1, 2304, 64)
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert count(shapes) == 595_153_152
    assert count(layer) == 120_476_160
    assert sorted(shapes["periods"]) == [
        "0_sliding_attention", "1_sliding_attention", "2_sliding_attention",
        "3_full_attention",
    ]
    axes = built["axes"]
    for shape, ax in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(shape.shape) == len(ax)

    cfg = dataclasses.replace(model.MellumConfig.tiny(), dtype=jnp.float32)
    assert cfg.n_head * cfg.head_dim == 96 != cfg.n_embd
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["periods"]["2_full_attention"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    cos, sin = model.rope_table(cfg.rope_full, cfg.head_dim, 40)
    got = llama.attention_half(
        h, lp, cfg, model.default_attention_for(cfg), cos, sin
    )
    q = reference.rotate((h @ lp["wq"]).reshape(2, 40, 4, 24), cos, sin)
    k = reference.rotate((h @ lp["wk"]).reshape(2, 40, 2, 24), cos, sin)
    v = (h @ lp["wv"]).reshape(2, 40, 2, 24)
    want = reference.attention(q, k, v).reshape(2, 40, 96) @ lp["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


_LAYER = {"num_experts_per_tok": 4, "norm_topk_prob": True}


def _whole_layer(seed=3):
    cfg = moe.MoEConfig(
        n_embd=32, n_experts=16, expert_hidden=16, top_k=4, gated=True,
        renorm_top_k=True, scoring="softmax", held=16, dtype=jnp.float32,
    )
    return cfg, moe.init_moe_params(jax.random.PRNGKey(seed), cfg)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Shares of 4 of 16 experts from expert 0, 4, 8 and 12 (the
    cell's 0, 16, 32, 48 of 64 at the test's scale), softmax scoring,
    the chosen weights over their sum: what the four chips compute
    adds up to the uncut reference's layer, and each share alone is
    the reference's share."""
    whole, params = _whole_layer()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32))
    flat = x.reshape(-1, 32)
    uncut, _ = reference.expert_layer(flat, params, _LAYER, 0)
    total = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(whole, first_expert=first, held=4)
        mine = {
            k: (v[first: first + 4] if k in ("wi", "wg", "wo") else v)
            for k, v in params.items()
        }
        part, aux = moe.moe_mlp(mine, x, cfg)
        want, _ = reference.expert_layer(flat, mine, _LAYER, first)
        np.testing.assert_allclose(
            part.reshape(-1, 32), want, rtol=1e-4, atol=1e-6
        )
        assert float(aux) == 0.0  # the held path returns no router loss
        total = total + part
    np.testing.assert_allclose(
        total.reshape(-1, 32), uncut, rtol=1e-4, atol=1e-6
    )
    # Without the renormalisation the layer is another one.
    other, _ = reference.expert_layer(
        flat, params, dict(_LAYER, norm_topk_prob=False), 0
    )
    assert float(jnp.max(jnp.abs(other - uncut))) > 1e-3 * float(
        jnp.max(jnp.abs(uncut))
    )


def test_the_router_loss_is_the_reference_s_and_the_llama_family_s():
    """``balance_loss`` on a share's layer is what ``moe_mlp`` returns
    for the sorted layer of every expert (``llama.mlp_tail`` adds that
    over the layers), and the reference's."""
    cfg = dataclasses.replace(model.MellumConfig.tiny(), dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(5), cfg)
    lp = jax.tree.map(lambda a: a[1], params["periods"]["0_sliding_attention"])
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64))
    got = float(model.balance_loss(h, lp["moe"]["router"], cfg)) * cfg.n_layer
    _, balance = reference.router_weights(
        h.reshape(-1, 64), lp["moe"]["router"], _LAYER
    )
    assert abs(got - cfg.aux_loss_weight * float(balance)) < 1e-6 * got
    sorted_cfg = moe.MoEConfig(
        n_embd=64, n_experts=16, expert_hidden=32, top_k=4, gated=True,
        renorm_top_k=True, aux_loss_weight=cfg.aux_loss_weight,
        z_loss_weight=0.0, dtype=jnp.float32,
    )
    every = moe.init_moe_params(jax.random.PRNGKey(8), sorted_cfg)
    every["router"] = lp["moe"]["router"]
    _, aux = moe.moe_mlp(every, h, sorted_cfg)
    assert abs(got - float(aux)) < 1e-6 * got


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
    logits = model.forward(params, batch[0], built["cfg"])
    ref = reference.logits(params, batch[0], _config())
    # bf16 activations through six layers against float32.
    assert float(jnp.max(jnp.abs(logits - ref))) < 0.1 * float(jnp.std(ref))


def test_every_parameter_gets_a_gradient(toy):
    built, params, batch = toy
    grads = jax.jit(jax.grad(built["loss"]))(params, *batch)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        name = jax.tree_util.keystr(path)
        g = g.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.max(jnp.abs(g))) > 0.0, name


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
    assert {remat.ATTN_IN, remat.ROUTER_LOGITS} <= set(remat.last_kept())


@pytest.mark.parametrize(
    "mesh_shape,rows",
    [((("data", 1),), 2), ((("data", 2), ("fsdp", 2)), 4)],
    ids=["accumulates_two_micro_batches", "data2_fsdp2"],
)
def test_normal_path_takes_steps_and_the_loss_falls(mesh_shape, rows):
    """auto_accelerate and ElasticTrainer.train_step on the family's
    parameter tree: on one device two micro-batches are accumulated a
    step, on four the held experts run once a device; one step program
    either way."""
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    built = family.build(_config())
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (rows, built["seq_len"] + 1), 0, built["vocab"]
    )
    tok, tgt = tok[:, :-1], tok[:, 1:]
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
        assert pattern["layer_types"] == [
            "sliding_attention", "sliding_attention", "full_attention"
        ] * 2
        assert pattern["period"] == 3 and pattern["periods"] == 2
        assert pattern["in_line"] == 3 and pattern["scanned"] is True
        assert pattern["windows"] == {
            "sliding_attention": 24, "full_attention": None
        }
        assert pattern["rotations"] == {
            "sliding_attention": "default", "full_attention": "yarn"
        }
        held = events("moe.held")[0]
        assert held["router_experts"] == 16 and held["held"] == 4
        assert held["first_expert"] == 4 and held["scoring"] == "softmax"
        assert held["rows_cap"] == moe.rows_cap(held["tokens"], cfg.moe_cfg)
        mean = held["tokens"] * held["top_k"] * 4 / 16
        assert held["cap_over_mean"] == held["rows_cap"] / mean == 3.0
        # 4 of 16 held, 4 a token: rows for three held choices a
        # token; all four are held in 1 draw of 1,820.
        assert held["covered_choices"] == 3
        assert held["tail"] == pytest.approx(1 / 1820)
        assert held["row_blocks"] == 2
        sizes = rows_sum.layout(held["tokens"], held["rows_cap"], 4)
        assert held["sum_tile"] == sizes["tile"] == held["tokens"]
        assert held["sum_chunk_visits"] == sizes["visits"]
    finally:
        obs.disable_tracer()
    assert {
        "attn_window", "attn_full", "moe_routed", "moe_balance"
    } <= profiling.SCOPES
    text = lowered.as_text(debug_info=True)
    for scope in ("attn/attn_window", "attn/attn_full", "mlp/moe_routed",
                  "mlp/moe_balance"):
        assert scope in text, scope
    # The balancing loss stands beside the held path, not under its
    # routing's name.
    assert "moe_balance/moe_route" not in text
    assert "moe_route/moe_balance" not in text
    assert profiling.scope_of("jit(f)/layers/attn/attn_window/dot")[
        "scope"
    ] == "layers/attn/attn_window"


def _share(n_experts, held, top_k, first=0):
    return moe.MoEConfig(
        n_embd=32, n_experts=n_experts, expert_hidden=16, top_k=top_k,
        gated=True, renorm_top_k=True, held=held, first_expert=first,
        dtype=jnp.float32,
    )


@pytest.mark.parametrize(
    "n_experts,held,top_k,n,choices,rows",
    [
        (256, 8, 8, 8192, 1, 8192),
        (64, 16, 8, 8192, 4, 32768),
        (64, 64, 8, 8192, 8, 65536),
        (64, 8, 8, 8192, 3, 24576),
        (16, 4, 4, 48, 3, 144),
        (16, 16, 4, 48, 4, 192),
        (16, 4, 4, 40, 3, 128),
        (8, 2, 2, 64, 2, 128),
    ],
    ids=["kimi_s_cell", "mellum_s_cell", "every_expert", "an_eighth",
         "toy", "toy_every_expert", "toy_rounded_up", "two_of_eight"],
)
def test_the_buffer_follows_the_share(
    n_experts, held, top_k, n, choices, rows
):
    """``rows_cap`` reads the tokens and the router's three numbers
    and nothing else: ``n x h*``, h* the fewest held choices a token
    that all but one draw in forty stay within (hypergeometric), a
    multiple of 16 between the even load and every pair."""
    cfg = _share(n_experts, held, top_k)
    covered, tail = moe.covered_choices(cfg)
    assert covered == choices and 0.0 <= tail <= moe.ROWS_CAP_TAIL
    by_hand = lambda h: sum(
        math.comb(held, j) * math.comb(n_experts - held, top_k - j)
        for j in range(h + 1, top_k + 1)
    ) / math.comb(n_experts, top_k)
    assert tail == pytest.approx(by_hand(covered), abs=1e-12)
    # One choice fewer would leave more than the tail (or none at all).
    assert covered == 1 or by_hand(covered - 1) > moe.ROWS_CAP_TAIL
    got = moe.rows_cap(n, cfg)
    assert got == rows and got % 16 == 0
    assert n * top_k * held / n_experts <= got <= n * top_k
    # Where the share starts changes nothing.
    assert moe.rows_cap(n, _share(
        n_experts, held, top_k, first=n_experts - held
    )) == rows


def test_the_two_cells_buffers():
    """Mellum's cell (16 of 64 held, 8 a token): 32,768 rows, twice
    the even load's 16,384 and half the layer's pairs, so two blocks
    of which the second is behind the ``lax.cond``; Kimi's (8 of 256):
    8,192, what it had under four times the mean. The ``moe.held``
    event of each, traced at the cell's shapes, says so."""
    from benchmark.families import kimi_linear as kimi_family
    from dlrover_tpu import obs

    kimi = os.path.join(cell_files.HERE, "configs", "kimi-linear-48b-a3b.json")
    # cell: (layer, rows, h*, its tail, blocks there can be, rows over
    # the even load's, visits of the rows' sum: a chunk of 128 rows
    # each, rows / 128 + 32 tiles x the held experts)
    cells = {
        "mellum": (family.build(_config(CELL))["cfg"].moe_cfg,
                   32768, 4, 0.019237, 2, 2.0, 256 + 32 * 16),
        "kimi": (kimi_family.build(_config(kimi))["cfg"].moe_cfg,
                 8192, 1, 0.021833, 8, 4.0, 64 + 32 * 8),
    }
    for name, (cfg, rows, choices, tail, blocks, over_mean, visits) in cells.items():
        assert moe.rows_cap(8192, cfg) == rows, name
        params = jax.eval_shape(
            lambda k: moe.init_moe_params(k, cfg), jax.random.PRNGKey(0)
        )
        x = jax.ShapeDtypeStruct((1, 8192, cfg.n_embd), cfg.dtype)
        tracer = obs.configure_tracer()
        try:
            jax.eval_shape(lambda p, x: moe.moe_mlp(p, x, cfg), params, x)
            (held,) = [e for e in tracer.events() if e["name"] == "moe.held"]
        finally:
            obs.disable_tracer()
        assert held["tokens"] == 8192 and held["rows_cap"] == rows, name
        assert held["covered_choices"] == choices, name
        assert held["row_blocks"] == blocks, name
        assert held["cap_over_mean"] == over_mean, name
        assert held["tail"] == pytest.approx(tail, abs=1e-6), name
        assert held["sum_tile"] == 256, name
        assert held["sum_chunk_visits"] == visits, name


def test_five_held_choices_a_token_run_two_blocks():
    """At the cell's share (16 of 64, 8 a token) the buffer has rows
    for four held choices a token. A router that sends EVERY token to
    the same five held experts (and three absent ones) is past it: the
    layer runs both blocks, drops nothing and is the reference's,
    forward and every gradient. Nothing is patched."""
    cfg = _share(64, 16, 8, first=16)
    params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda a: a * 20 if a.ndim == 3 else a, params)
    chosen = jnp.asarray([17, 20, 23, 26, 31, 2, 40, 63])
    router = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    params["router"] = router.at[0, chosen].add(12.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32)).at[..., 0].set(1.0)
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    cap = moe.rows_cap(48, cfg)
    assert cap == 48 * 4 and -(-48 * 8 // cap) == 2
    stats = moe.routing_stats(x.reshape(-1, 32) @ params["router"], 8, cfg)
    assert float(stats["held_pairs_per_token"]) == 5.0
    assert int(stats["held_row_blocks"]) == 2 == -(-48 * 5 // cap)
    config = {"num_experts_per_tok": 8, "norm_topk_prob": True}

    def want(params, x):
        return reference.expert_layer(
            x.reshape(-1, 32), params, config, cfg.first_expert
        )[0].reshape(x.shape)

    got = moe.moe_mlp(params, x, cfg)[0]
    assert float(jnp.max(jnp.abs(got))) > 0.0
    np.testing.assert_allclose(got, want(params, x), rtol=1e-4, atol=1e-5)
    grads = jax.grad(
        lambda p, x: jnp.sum(moe.moe_mlp(p, x, cfg)[0] * w), (0, 1)
    )(params, x)
    ref = jax.grad(lambda p, x: jnp.sum(want(p, x) * w), (0, 1))(params, x)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref)
    ):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0.0, name
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-3 * float(jnp.max(jnp.abs(b))),
            err_msg=name,
        )
