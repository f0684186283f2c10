"""models/ouro.py, the looped language model, at a small size on the
CPU with seeded weights: loss and every gradient against the plain
reference (benchmark/reference/ouro.py, float32, written from the
equations); the shared weights' gradients against the same stack
unrolled into tied copies; remat policies; a host-device mesh; the
exit distribution by hand; the events."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro as reference
from dlrover_tpu import obs
from dlrover_tpu.accelerate import remat
from dlrover_tpu.models import llama, ouro

F32_TOL = 2e-5


def _config(cfg: ouro.OuroConfig) -> dict:
    """The published keys the reference reads, for ``cfg``."""
    return {
        "num_attention_heads": cfg.n_head,
        "num_key_value_heads": cfg.n_kv_head,
        "num_hidden_layers": cfg.n_layer,
        "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta,
        "sliding_window": None,
        "total_ut_steps": cfg.ut_steps,
        "assumed": {"exit_entropy_coef": cfg.exit_entropy_coef},
    }


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def toy():
    cfg = ouro.OuroConfig.tiny()
    params = ouro.init_params(jax.random.PRNGKey(1), cfg)
    # Weights large enough that the gate opens differently from
    # position to position and the passes' losses differ.
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(
            jax.random.PRNGKey(2), x.shape, x.dtype
        ),
        params,
    )
    tok = jax.random.randint(
        jax.random.PRNGKey(3), (2, cfg.block_size + 1), 0, cfg.vocab_size
    )
    return cfg, params, tok[:, :-1], tok[:, 1:]


@pytest.mark.parametrize("ut_steps", [1, 4])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_loss_and_gradients_agree_with_the_reference(toy, ut_steps, fused):
    cfg, params, tok, tgt = toy
    cfg = dataclasses.replace(cfg, ut_steps=ut_steps)
    loss = ouro.loss_fn_fused if fused else ouro.loss_fn
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(
            functools.partial(loss, cfg=cfg)
        ))(params, tok, tgt)
    want, want_grads = jax.value_and_grad(
        functools.partial(reference.loss, config=_config(cfg))
    )(params, tok, tgt)
    assert float(got) == pytest.approx(float(want), rel=F32_TOL)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if ut_steps == 1 and name in ("['gate_w']", "['gate_b']"):
            # One pass: all the mass is left to it whatever the gate says.
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert _rel(g, w) < 20 * F32_TOL, name


@pytest.mark.parametrize("left_out", [
    "rms2", "rms4", "rmsf", "gate_b", "gate_w",
])
def test_the_reference_sees_every_norm_and_the_gate(toy, left_out):
    """Both norms of a half, the norm that closes a pass, the gate's
    weight and its bias: each set to its neutral value moves the
    reference's loss (gains are drawn off 1 and the bias off 0)."""
    cfg, params, tok, tgt = toy
    honest = float(reference.loss(params, tok, tgt, _config(cfg)))
    where = params["blocks"] if left_out in params["blocks"] else params
    neutral = (
        jnp.ones_like if left_out.startswith("rms") else jnp.zeros_like
    )(where[left_out])
    if left_out in params["blocks"]:
        broken = dict(params, blocks=dict(params["blocks"], **{left_out: neutral}))
    else:
        broken = dict(params, **{left_out: neutral})
    assert float(reference.loss(broken, tok, tgt, _config(cfg))) != (
        pytest.approx(honest, rel=1e-4)
    )


def test_shared_weights_gradient_is_the_sum_over_tied_copies(toy):
    """The looped stack's weight gradients equal the sum, over the
    passes, of the gradients of the same stack unrolled into
    ``ut_steps x n_layer`` layers, each pass with a copy of its own."""
    cfg, params, tok, tgt = toy
    attn_fn = ouro.default_attention_for(cfg)
    cos, sin = llama.rope_table(cfg.attention_cfg, tok.shape[1])

    def unrolled(copies, rest):
        x = rest["wte"][tok].astype(cfg.dtype)
        hs = []
        for blocks in copies:
            for i in range(cfg.n_layer):
                lp = jax.tree.map(lambda a: a[i], blocks)
                x = ouro._block(x, lp, attn_fn, cfg=cfg, cos=cos, sin=sin)
            x, h = ouro._close_pass(x, rest, cfg)
            hs.append(h)
        hs = jnp.stack(hs, axis=1)
        p, entropy = ouro.exit_distribution(rest, hs)
        logp = jax.nn.log_softmax(llama.head_logits(rest, hs), axis=-1)
        gold = jnp.broadcast_to(tgt[:, None, :], hs.shape[:-1])
        nll = -jnp.take_along_axis(logp, gold[..., None], axis=-1)[..., 0]
        return jnp.mean(
            jnp.sum(p * nll, axis=1) - cfg.exit_entropy_coef * entropy
        )

    rest = {k: v for k, v in params.items() if k != "blocks"}
    copies = [params["blocks"]] * cfg.ut_steps
    with jax.default_matmul_precision("highest"):
        want_loss, by_copy = jax.value_and_grad(unrolled)(copies, rest)
        got_loss, grads = jax.value_and_grad(
            functools.partial(ouro.loss_fn_fused, cfg=cfg)
        )(params, tok, tgt)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=F32_TOL)
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    for name in summed:
        assert _rel(grads["blocks"][name], summed[name]) < 10 * F32_TOL, name
        # A pass's share is not the whole: the sum is over four uses.
        assert _rel(by_copy[0][name], summed[name]) > 0.01, name


def test_one_set_of_weights_whatever_the_passes():
    """The benchmark's cut: 444,665,857 parameters with 4 passes as
    with 1, not four times the layers."""
    count = lambda cfg: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
            functools.partial(ouro.init_params, cfg=cfg),
            jax.random.PRNGKey(0),
        ))
    )
    cut = ouro.OuroConfig(n_layer=8, vocab_size=8192)
    assert count(cut) == 444_665_857
    assert count(dataclasses.replace(cut, ut_steps=1)) == 444_665_857
    assert ouro.OuroConfig().ut_steps == 4  # the published total_ut_steps
    axes = ouro.param_logical_axes(cut)
    shapes = jax.eval_shape(
        functools.partial(ouro.init_params, cfg=cut), jax.random.PRNGKey(0)
    )
    for a, s in zip(
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
        jax.tree.leaves(shapes),
    ):
        assert len(a) == len(s.shape)


@pytest.mark.parametrize("policy", ["full", "attention", "dots"])
def test_remat_policies_give_the_same_values(toy, policy):
    cfg, params, tok, tgt = toy
    f = lambda cfg: jax.jit(jax.value_and_grad(  # noqa: E731
        functools.partial(ouro.loss_fn_fused, cfg=cfg)
    ))(params, tok, tgt)
    with jax.default_matmul_precision("highest"):
        want, want_grads = f(dataclasses.replace(cfg, remat="none"))
        got, grads = f(dataclasses.replace(cfg, remat=policy))
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(g, w) < F32_TOL


def _events(tracer, name):
    return [e for e in tracer.events() if e["name"] == name]


def test_full_remat_keeps_the_blocks_names_and_the_loop_says_so(toy):
    """``remat.kept`` lists what the block named; ``ouro.loop`` says
    once a trace how many passes ran over how many layers and what each
    layer pass keeps; the head says its rows carried weights."""
    cfg, params, tok, tgt = toy
    full = dataclasses.replace(cfg, remat="full")
    tracer = obs.configure_tracer()
    try:
        jax.jit(jax.value_and_grad(
            functools.partial(ouro.loss_fn_fused, cfg=full)
        )).lower(params, tok, tgt)
        (kept,) = _events(tracer, "remat.kept")
        assert kept["names"] == sorted([remat.ATTN_IN, remat.MLP_HIDDEN])
        assert set(kept["names"]) <= set(remat.KEPT)
        (loop,) = _events(tracer, "ouro.loop")
        assert loop["ut_steps"] == 4 and loop["layers"] == cfg.n_layer
        assert loop["kept_names"] == kept["names"]
        (rows,) = _events(tracer, "head.weighted_rows")
        assert rows["rows"] == 4 * tok.size and rows["chunks"] == 8
        (fwd,) = _events(tracer, "head.grads_in_forward")
        assert fwd["rows"] == rows["rows"]
        # Under "none" nothing is kept by name.
        jax.jit(functools.partial(ouro.loss_fn_fused, cfg=cfg)).lower(
            params, tok, tgt
        )
        assert _events(tracer, "ouro.loop")[-1]["kept_names"] == []
    finally:
        obs.disable_tracer()


def _avals(jaxpr):
    """Every array of ``jaxpr`` and of the jaxprs its equations call
    (the jitted pass, the scans' bodies, the checkpointed block)."""
    for v in (*jaxpr.invars, *jaxpr.constvars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_the_gradient_keeps_the_named_residuals_a_pass_a_layer(toy):
    """Under "full" a layer pass's kept residuals are stacked once,
    ``[n_layer, ...]`` by the layers' scan whose backward reads them,
    a set a pass: no array anywhere in the gradient is ``[ut_steps,
    n_layer, ...]``."""
    cfg, params, tok, tgt = toy
    full = dataclasses.replace(cfg, remat="full")
    jaxpr = jax.make_jaxpr(jax.grad(
        functools.partial(ouro.loss_fn_fused, cfg=full)
    ))(params, tok, tgt)
    shapes = [getattr(a, "shape", ()) for a in _avals(jaxpr.jaxpr)]
    twice = (cfg.ut_steps, cfg.n_layer, *tok.shape)
    assert not [s for s in shapes if s[:4] == twice]
    once = (cfg.n_layer, *tok.shape)
    widths = {s[3:] for s in shapes if s[:3] == once}
    # q, k, v and the carried input at the hidden width; gate and up
    # at the MLP's.
    assert {(cfg.n_embd,), (cfg.intermediate,)} <= widths, widths


@pytest.mark.parametrize("ut_steps", [1, 2, 4])
def test_the_block_is_traced_once_whatever_the_passes(toy, ut_steps):
    """``ut_steps`` layer scans in the program, one trace of the block:
    ``remat.kept`` fires once a trace of the loss and ``ouro.loop``
    counts the scans."""
    cfg, params, tok, tgt = toy
    cfg = dataclasses.replace(cfg, remat="full", ut_steps=ut_steps)
    tracer = obs.configure_tracer()
    try:
        jaxpr = jax.make_jaxpr(jax.grad(
            functools.partial(ouro.loss_fn_fused, cfg=cfg)
        ))(params, tok, tgt)
        assert len(_events(tracer, "remat.kept")) == 1
        (loop,) = _events(tracer, "ouro.loop")
    finally:
        obs.disable_tracer()
    assert loop["layer_scans"] == loop["ut_steps"] == ut_steps
    # One forward and one backward call of the pass for each.
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"
             and e.params["name"] == "one_pass"]
    assert len(calls) == 2 * ut_steps


def test_what_stands_in_close_pass_place_at_trace_time_runs(toy):
    """benchmark/controls/ouro.py ``no_pass_norm`` puts a broken
    ``_close_pass`` in the module's place while the loss is traced: the
    pass is jitted inside ``passes``, a trace at a time, so the program
    runs what stood there."""
    from benchmark.controls import ouro as controls

    cfg, params, tok, tgt = toy
    honest = jax.jit(functools.partial(ouro.loss_fn_fused, cfg=cfg))
    want = float(honest(params, tok, tgt))
    close_pass = ouro._close_pass
    got = float(jax.jit(controls.broken("no_pass_norm", cfg))(
        params, tok, tgt
    ))
    assert ouro._close_pass is close_pass
    assert abs(got - want) > 1e-3 * abs(want), (got, want)
    # And the honest function again afterwards, traced anew.
    again = jax.jit(functools.partial(ouro.loss_fn_fused, cfg=cfg))
    assert float(again(params, tok, tgt)) == want


def test_same_loss_on_a_host_device_mesh(toy):
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, under_mesh
    from dlrover_tpu.parallel.sharding import tree_shardings
    from dlrover_tpu.trainer.step import shard_batch

    cfg, params, tok, tgt = toy
    mesh = build_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    loss = functools.partial(ouro.loss_fn_fused, cfg=cfg)
    tok4, tgt4 = jnp.tile(tok, (2, 1)), jnp.tile(jnp.flip(tgt, 0), (2, 1))
    want, want_grads = jax.jit(jax.value_and_grad(loss))(params, tok4, tgt4)
    sharded = jax.tree.map(
        jax.device_put, params,
        tree_shardings(mesh, ouro.param_logical_axes(cfg)),
    )
    tracer = obs.configure_tracer()
    try:
        got, grads = jax.jit(jax.value_and_grad(under_mesh(loss, mesh)))(
            sharded, *shard_batch(mesh, np.asarray(tok4), np.asarray(tgt4))
        )
        # The weighted head ran on each device's own rows of all passes.
        (ev,) = _events(tracer, "head.per_device")
        assert ev["rows_per_device"] == cfg.ut_steps * tok.shape[1]
    finally:
        obs.disable_tracer()
    assert float(got) == pytest.approx(float(want), rel=F32_TOL)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(a, b) < 10 * F32_TOL


def test_exit_distribution_by_hand():
    """p_1 = l_1, p_2 = l_2 (1 - l_1), p_3 = l_3 (1 - l_1)(1 - l_2),
    p_4 the mass left, whatever the fourth gate says; float32 even
    from bfloat16 states."""
    e = 8
    hs = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 5, e), jnp.bfloat16)
    params = {
        "gate_w": jax.random.normal(jax.random.PRNGKey(1), (e,)),
        "gate_b": jnp.asarray([0.3]),
    }
    p, entropy = ouro.exit_distribution(params, hs)
    assert p.dtype == entropy.dtype == jnp.float32
    lam = jax.nn.sigmoid(
        np.asarray(hs, np.float32) @ np.asarray(params["gate_w"]) + 0.3
    )
    want = np.stack([
        lam[:, 0],
        lam[:, 1] * (1 - lam[:, 0]),
        lam[:, 2] * (1 - lam[:, 0]) * (1 - lam[:, 1]),
        (1 - lam[:, 0]) * (1 - lam[:, 1]) * (1 - lam[:, 2]),
    ], axis=1)
    np.testing.assert_allclose(p, want, rtol=2e-5)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        entropy, -(want * np.log(want)).sum(axis=1), rtol=2e-5
    )
    # A gate shut hard leaves nothing, and no NaN, to the later passes.
    shut = dict(params, gate_w=jnp.zeros((e,)), gate_b=jnp.asarray([200.0]))
    p, entropy = ouro.exit_distribution(shut, hs)
    np.testing.assert_allclose(p[:, 0], 1.0)
    assert np.all(np.isfinite(entropy)) and np.allclose(entropy, 0.0)
    g = jax.grad(lambda b: ouro.exit_distribution(
        dict(shut, gate_b=b), hs)[1].sum())(shut["gate_b"])
    assert np.all(np.isfinite(g))


def test_forward_gives_every_passs_logits(toy):
    cfg, params, tok, _ = toy
    logits, p = ouro.forward(params, tok, cfg)
    assert logits.shape == (2, 4, cfg.block_size, cfg.vocab_size)
    assert logits.dtype == jnp.float32 and p.shape == logits.shape[:-1]
    # The passes differ: the loop is not a fixed point at these weights.
    assert _rel(logits[:, 0], logits[:, 3]) > 1e-2
