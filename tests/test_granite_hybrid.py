"""models/granite_hybrid.py: the pattern's period and runs, one step
program whatever the depth, what ``remat="full"`` keeps of a Mamba-2
mixer (``ssd_fwd`` once a layer), the events a trace leaves, the
convolution's hand-written backward against autodiff of the plain
shifted form, and the stack on the trainer's normal path."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import granite_hybrid as model
from dlrover_tpu.ops import causal_conv
from dlrover_tpu.models.granite_hybrid import ATTENTION as A
from dlrover_tpu.models.granite_hybrid import MAMBA as M

TINY = model.GraniteHybridConfig.tiny()


def _batch(cfg, rows=2):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (rows, cfg.block_size + 1))
    tok = tok.astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def test_period_and_runs_of_the_published_pattern():
    cfg = model.GraniteHybridConfig()
    assert cfg.n_layer == 40
    assert [i for i, k in enumerate(cfg.layer_types) if k == A] == [5, 15, 25, 35]
    assert cfg.period == (M,) * 5 + (A,) + (M,) * 4
    assert cfg.runs == (("0_mamba", M, 5), ("1_attention", A, 1), ("2_mamba", M, 4))
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 4352)


@pytest.mark.parametrize("types,period,runs", [
    ((M,), (M,), [(M, 1)]),
    ((A, A, A), (A,), [(A, 1)]),
    ((M, A, M, A), (M, A), [(M, 1), (A, 1)]),
    ((M, M, A, M, M, A, M), (M, M, A, M, M, A, M), [(M, 2), (A, 1), (M, 2), (A, 1), (M, 1)]),
])
def test_period_is_the_shortest_repeating_prefix(types, period, runs):
    cfg = dataclasses.replace(TINY, layer_types=types)
    assert cfg.period == period
    assert [(kind, n) for _, kind, n in cfg.runs] == runs


def test_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=(M, "window"))


def test_parameters_are_stacked_by_run_and_named_for_sharding():
    params = model.init_params(jax.random.PRNGKey(0), TINY)
    axes = model.param_logical_axes(TINY)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
    )
    assert sorted(params["runs"]) == ["0_mamba", "1_attention", "2_mamba"]
    run = params["runs"]["0_mamba"]
    assert run["w_in"].shape == (2, 2, 64, 128 + 256 + 8)  # periods, layers
    assert params["runs"]["1_attention"]["wk"].shape == (2, 1, 64, 32)
    assert "lm_head" not in params  # the table is tied
    flat = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    shapes = jax.tree.leaves(params)
    assert all(len(a) == s.ndim for a, s in zip(flat, shapes))
    assert axes["runs"]["0_mamba"]["w_in"] == ("layers", "layers", "embed", None)
    assert axes["runs"]["0_mamba"]["w_out"] == ("layers", "layers", None, "embed")
    # A_log = log(1..heads); the steps' biases inside softplus^-1 of
    # [dt_min, dt_max]; no gain, D or bias at its neutral value.
    np.testing.assert_allclose(np.exp(run["A_log"][0, 0]), np.arange(1, 9), rtol=1e-6)
    step = jax.nn.softplus(run["dt_bias"])
    assert float(step.min()) >= 0.999e-3 and float(step.max()) <= 0.1001
    for leaf in ("rms1", "D", "ssm_norm"):
        assert float(jnp.min(jnp.abs(run[leaf] - 1.0))) > 0
    assert float(jnp.min(jnp.abs(run["conv_b"]))) > 0


def _pallas_calls(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc.append(str(eqn.params.get("name", "")))
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else [v]:
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    _pallas_calls(x, acc)
    return acc


def _grad_jaxpr(cfg):
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    return jax.make_jaxpr(jax.grad(loss))(params, *_batch(cfg)).jaxpr


@pytest.mark.parametrize("remat,forwards", [("full", 2), (False, 2), ("dots", 4)])
def test_ssd_fwd_appears_once_a_mamba_run_under_full(remat, forwards):
    """One period's two Mamba runs are two scan bodies. Under "full"
    each holds the forward kernel once and the backward scan's body
    holds ``ssd_bwd`` alone: the scan's output and chunk states are
    kept by name. A policy that cannot see inside the ``custom_vjp``
    ("dots") runs the forward kernel again beside the backward one."""
    calls = _pallas_calls(_grad_jaxpr(dataclasses.replace(TINY, remat=remat)), [])
    assert calls.count("ssd_bwd") == 2, calls
    assert calls.count("ssd_fwd") == forwards, calls


def test_step_text_does_not_grow_with_depth():
    """Periods are scanned: four periods lower to the text of one (a
    scan's trip count and the stacked shapes differ, nothing else)."""
    def lowered(periods):
        cfg = dataclasses.replace(
            TINY, layer_types=(M, M, A, M) * periods, remat="full"
        )
        params = jax.eval_shape(
            functools.partial(model.init_params, cfg=cfg), jax.random.PRNGKey(0)
        )
        loss = functools.partial(model.loss_fn_fused, cfg=cfg)
        return jax.jit(jax.value_and_grad(loss)).lower(
            params, *_batch(cfg)
        ).as_text()

    one, four = lowered(1), lowered(4)
    assert one.count("stablehlo.") == four.count("stablehlo.")
    assert one.count("tpu_custom_call") == four.count("tpu_custom_call")


def test_a_trace_says_what_it_runs():
    from dlrover_tpu import obs

    cfg = dataclasses.replace(TINY, remat="full")
    tracer = obs.configure_tracer()
    try:
        params = model.init_params(jax.random.PRNGKey(0), cfg)
        jax.jit(jax.value_and_grad(
            functools.partial(model.loss_fn_fused, cfg=cfg)
        )).lower(params, *_batch(cfg))
        events = tracer.events()
    finally:
        obs.disable_tracer()
    (pattern,) = [e for e in events if e["name"] == "hybrid.pattern"]
    assert pattern["layer_types"] == list(cfg.layer_types)
    assert (pattern["mamba_layers"], pattern["attention_layers"],
            pattern["period"]) == (6, 2, 4)
    scans = [e for e in events if e["name"] == "ssd.scan"]
    # Once a trace: both Mamba runs call one checkpointed layer at
    # the same shapes, and JAX traces it once.
    assert len(scans) == 1
    assert all((e["chunk"], e["chunks"], e["heads"], e["state"],
                e["per_device"]) == (16, 4, 8, 32, False) for e in scans)
    kept = sorted(
        tuple(e["names"]) for e in events if e["name"] == "remat.kept"
    )
    assert kept == [
        ("attn_in", "mlp_hidden"),  # XLA attention on the CPU: no flash_o
        ("mlp_hidden", "ssd_states", "ssd_y", "ssm_in"),
    ]


def _plain_conv_silu(x, w, bias):
    """The mixer's convolution as it stood before PR 52, which autodiff
    differentiated: pad, shifted slices, multiply-adds, SiLU."""
    width, t = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for k in range(width):
        out = out + xf[:, k:k + t] * w[k].astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def _conv_case(width, t, batch, dtype, only, channels=24):
    keys = jax.random.split(jax.random.PRNGKey(width * 1000 + t), 4)
    x = jax.random.normal(keys[0], (batch, t, channels))
    dy = jax.random.normal(keys[1], (batch, t, channels))
    # Only the terms at one end of the sequence: an input in the
    # first K-1 tokens, or a cotangent in the last K-1 (the
    # anti-causal edge of ``dx``).
    rows = jnp.arange(t)[None, :, None]
    if only == "first_inputs":
        x = jnp.where(rows < width - 1, x, 0.0)
    if only == "last_cotangents":
        dy = jnp.where(rows >= t - (width - 1), dy, 0.0)
    w = jax.random.uniform(keys[2], (width, channels), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(keys[3], (channels,), minval=-0.5, maxval=0.5)
    return tuple(v.astype(dtype) for v in (x, w, bias, dy))


@pytest.mark.parametrize("only", [None, "first_inputs", "last_cotangents"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("t", [8, 13, 256])
@pytest.mark.parametrize("width", [2, 4])
def test_conv_backward_is_autodiff_of_the_plain_form(
    width, t, batch, dtype, only
):
    """``ops/causal_conv.py``'s backward kernel gives the ``dx``,
    ``dw`` and ``dbias`` that ``jax.vjp`` gives of the plain shifted
    form: in float32 to a relative 1e-5, in bf16 to the rounding of
    the one cast that ends each. This is what guards the backward:
    the benchmark's ``correct`` reads the forward loss and a falling
    trend, and would pass a rule that lost the anti-causal edge."""
    x, w, bias, dy = _conv_case(width, t, batch, dtype, only)
    y, pull = jax.vjp(causal_conv.conv_silu, x, w, bias)
    want_y, want_pull = jax.vjp(_plain_conv_silu, x, w, bias)
    # The forward kernel against the plain form: in bf16 the same
    # values; float32 differs in the last digit (the CPU contracts the
    # plain form's multiply-adds).
    exact = dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want_y, np.float32),
        rtol=0 if exact else 2e-6, atol=0 if exact else 1e-6,
    )
    # One bf16 cast is off by at most 2**-8 of the value either way.
    rtol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for name, got, want in zip(
        ("dx", "dw", "dbias"), pull(dy), want_pull(dy)
    ):
        assert got.dtype == want.dtype == dtype, name
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.any(want != 0), name
        np.testing.assert_allclose(
            got, want, rtol=rtol, atol=1e-5 * np.max(np.abs(want)),
            err_msg=name,
        )


def test_conv_keeps_its_inputs_and_the_block_its_names():
    """The rule's residuals are its inputs as they came in, the
    mixer's ``xbc`` (a slice of the kept ``ssm_in`` projection),
    ``conv_w`` and ``conv_b``: no pre-activation, no float32
    [B, T, C]. The block under ``remat="full"`` names what it named
    before the rule."""
    from dlrover_tpu import obs

    x, w, bias, _ = _conv_case(4, 16, 2, jnp.bfloat16, None)
    _, res = causal_conv._conv_silu_fwd(x, w, bias, 0, True)
    assert len(res) == 3
    assert all(r is v for r, v in zip(res, (x, w, bias)))
    _, pull = jax.vjp(causal_conv.conv_silu, x, w, bias)
    held = sorted((v.shape, v.dtype.name) for v in jax.tree.leaves(pull))
    assert held == sorted((v.shape, v.dtype.name) for v in (x, w, bias))

    cfg = dataclasses.replace(TINY, remat="full")
    tracer = obs.configure_tracer()
    try:
        params = jax.eval_shape(
            functools.partial(model.init_params, cfg=cfg),
            jax.random.PRNGKey(0),
        )
        jax.jit(jax.value_and_grad(
            functools.partial(model.loss_fn_fused, cfg=cfg)
        )).lower(params, *_batch(cfg))
        events = tracer.events()
    finally:
        obs.disable_tracer()
    convs = [e for e in events if e["name"] == "ssm.conv"]
    # x's columns of the projection, then B|C's: a call each, once a
    # trace of the checkpointed layer.
    gn = cfg.ssm_groups * cfg.ssm_state
    assert [(e["width"], e["channels"], e["start"]) for e in convs] == [
        (cfg.ssm_conv, cfg.d_inner, cfg.d_inner),
        (cfg.ssm_conv, 2 * gn, 2 * cfg.d_inner),
    ]
    assert all(e["residuals"] == ["x", "w", "bias"] for e in convs)
    assert not any(e["per_device"] for e in convs)
    kept = {n for e in events if e["name"] == "remat.kept" for n in e["names"]}
    assert kept == {"attn_in", "mlp_hidden", "ssd_states", "ssd_y", "ssm_in"}


def test_full_remat_gives_the_same_gradients():
    tok, tgt = _batch(TINY)
    params = model.init_params(jax.random.PRNGKey(0), TINY)

    def grads(remat):
        cfg = dataclasses.replace(TINY, remat=remat)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                functools.partial(model.loss_fn_fused, cfg=cfg)
            ))(params, tok, tgt)

    (l0, g0), (l1, g1) = grads(False), grads("full")
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_normal_path_takes_a_step_and_the_loss_falls():
    """auto_accelerate, ElasticTrainer.train_step and a flash
    checkpoint's round trip on the hybrid parameter tree."""
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    cfg = dataclasses.replace(TINY, remat="full")
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    tok, tgt = _batch(cfg, rows=4)
    res = auto_accelerate(
        functools.partial(model.init_params, cfg=cfg), loss,
        model.param_logical_axes(cfg), (tok[:2], tgt[:2]),
        learning_rate=3e-3,
        strategy=Strategy(
            mesh_shape=(("data", 2), ("fsdp", 2)), optimizer="adamw",
            micro_batch_size=1,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, loss, res.optimizer, global_batch_size=4,
        micro_batch_size=1,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    w_in = params["runs"]["0_mamba"]["w_in"]
    assert "fsdp" in str(w_in.sharding.spec)
    losses = []
    for _ in range(4):
        params, opt_state, step_loss = trainer.train_step(
            params, opt_state, np.asarray(tok), np.asarray(tgt)
        )
        losses.append(float(step_loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer._compiled._cache_size() == 1
