"""Fused chunked cross-entropy vs naive log-softmax path."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu import obs
from dlrover_tpu.models import gpt, llama
from dlrover_tpu.ops.cross_entropy import fused_cross_entropy
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, under_mesh
from dlrover_tpu.parallel.sharding import prune_specs_to_mesh, tree_specs
from dlrover_tpu.trainer.step import batch_spec, shard_batch


def _naive(x, wte, targets):
    logits = jnp.einsum(
        "ne,ve->nv", x, wte, preferred_element_type=jnp.float32
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, targets[:, None], axis=-1)
    )


@pytest.mark.parametrize("num_chunks", [1, 4])
def test_fused_xent_matches_naive(num_chunks):
    key = jax.random.PRNGKey(0)
    n, e, v = 64, 16, 96
    x = jax.random.normal(key, (n, e), jnp.float32)
    wte = jax.random.normal(jax.random.PRNGKey(1), (v, e), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
    got = fused_cross_entropy(x, wte, targets, num_chunks)
    want = _naive(x, wte, targets)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("g", [1.0, 0.25, 3.0])
def test_fused_xent_grads_match_naive(g):
    """The gradients are formed in the forward rule for an upstream
    cotangent of 1 and scaled by the real one in the backward."""
    n, e, v = 32, 8, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (n, e), jnp.float32)
    wte = jax.random.normal(jax.random.PRNGKey(1), (v, e), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
    g1 = jax.grad(
        lambda x, w: g * fused_cross_entropy(x, w, targets, 4),
        argnums=(0, 1),
    )(x, wte)
    g2 = jax.grad(
        lambda x, w: g * _naive(x, w, targets), argnums=(0, 1)
    )(x, wte)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


def test_fused_xent_bf16_grads_close_to_f32_naive():
    """bf16 activations and table: the gradients (bf16 cotangent into
    both products, float32 table-gradient accumulator cast once) agree
    with the float32 naive head's to bf16-rounding tolerance."""
    n, e, v = 64, 32, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (n, e), jnp.bfloat16)
    wte = jax.random.normal(jax.random.PRNGKey(1), (v, e), jnp.bfloat16)
    targets = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
    got = jax.grad(
        lambda x, w: fused_cross_entropy(x, w, targets, 4), argnums=(0, 1)
    )(x, wte)
    want = jax.grad(_naive, argnums=(0, 1))(
        x.astype(jnp.float32), wte.astype(jnp.float32), targets
    )
    for a, b, like in zip(got, want, (x, wte)):
        assert a.dtype == like.dtype
        a32 = np.asarray(a, np.float32)
        b32 = np.asarray(b, np.float32)
        denom = np.maximum(np.abs(b32), 1e-4)
        assert np.median(np.abs(a32 - b32) / denom) < 0.05


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr nested
    in it (a scan's body counts once, as the program holds it)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, primitive)
    return total


@pytest.mark.parametrize(
    "differentiate,products",
    [(False, 1), (True, 3)],
    ids=["forward-only", "value-and-grad"],
)
def test_products_over_the_vocabulary(differentiate, products):
    """The forward-only call holds the logits product alone; under
    differentiation the step holds three (logits, dx, the table's
    gradient) and not a fourth that forms the logits again."""
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    fn = lambda x, w: fused_cross_entropy(x, w, targets, 4)  # noqa: E731
    if differentiate:
        fn = jax.value_and_grad(fn, argnums=(0, 1))
    jaxpr = jax.make_jaxpr(fn)(x, wte).jaxpr
    assert _count(jaxpr, "dot_general") == products


@pytest.mark.parametrize("width,once", [(64, False), (2048, True)])
def test_cotangent_formed_once_for_a_wide_table(width, once):
    """From a table 2048 wide the bf16 cotangent is held behind an
    optimization barrier, so both gradient products read one array;
    a narrow table leaves the fusion to XLA. The gradients are the
    naive head's either way."""
    x, wte, targets = _head_inputs(16, width, 96, jnp.float32)
    grad = jax.grad(
        lambda x, w: fused_cross_entropy(x, w, targets, 2), argnums=(0, 1)
    )
    jaxpr = jax.make_jaxpr(grad)(x, wte).jaxpr
    assert _count(jaxpr, "optimization_barrier") == int(once)
    want = jax.grad(_naive, argnums=(0, 1))(x, wte, targets)
    for a, b in zip(jax.jit(grad)(x, wte), want):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


def test_grads_in_forward_event():
    """A trace under differentiation emits ``head.grads_in_forward``
    once, with all rows and the chunk count; the forward-only call
    emits none."""
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    loss = lambda x, w: fused_cross_entropy(x, w, targets, 4)  # noqa: E731
    tracer = obs.configure_tracer()
    try:
        def events():
            return [
                e for e in tracer.events()
                if e["name"] == "head.grads_in_forward"
            ]

        jax.jit(loss).lower(x, wte)
        assert events() == []
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, wte)
        (ev,) = events()
        assert ev["rows"] == 64 and ev["chunks"] == 4
    finally:
        obs.disable_tracer()


def test_gpt_fused_loss_matches_plain():
    cfg = gpt.GPTConfig(
        vocab_size=128, block_size=32, n_layer=2, n_head=2, n_embd=32,
        dtype=jnp.float32, remat=False,
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    targets = jnp.roll(tokens, -1, axis=1)
    plain = gpt.loss_fn(params, tokens, targets, cfg)
    fused = gpt.loss_fn_fused(params, tokens, targets, cfg, num_chunks=4)
    np.testing.assert_allclose(fused, plain, rtol=1e-5)


def test_gpt_fused_loss_grads_under_remat():
    cfg = gpt.GPTConfig(
        vocab_size=128, block_size=32, n_layer=2, n_head=2, n_embd=32,
        dtype=jnp.float32, remat="attention",
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    targets = jnp.roll(tokens, -1, axis=1)
    g1 = jax.grad(
        lambda p: gpt.loss_fn_fused(p, tokens, targets, cfg, num_chunks=2)
    )(params)
    cfg2 = gpt.GPTConfig(
        vocab_size=128, block_size=32, n_layer=2, n_head=2, n_embd=32,
        dtype=jnp.float32, remat=False,
    )
    g2 = jax.grad(lambda p: gpt.loss_fn(p, tokens, targets, cfg2))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-3)


# -- the head under a mesh: each device's own rows (ISSUE 27) ---------------

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) "
    r"(all-reduce|all-gather|reduce-scatter)(-start)?\("
)


def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def _tiny_model(name):
    if name == "llama":
        cfg = llama.LlamaConfig(
            vocab_size=384, block_size=32, n_layer=1, n_head=4,
            n_kv_head=2, n_embd=64, intermediate=128,
            dtype=jnp.float32, remat=False,
        )
        return llama, cfg
    cfg = gpt.GPTConfig(
        vocab_size=384, block_size=32, n_layer=1, n_head=4, n_embd=64,
        dtype=jnp.float32, remat=False, use_flash_attention=False,
    )
    return gpt, cfg


@pytest.mark.parametrize(
    "model_name,axes",
    [
        ("llama", {"fsdp": 4}),
        ("gpt", {"data": 4}),
        ("gpt", {"data": 2, "fsdp": 2}),
    ],
    ids=["llama-fsdp4", "gpt-data4", "gpt-data2xfsdp2"],
)
def test_no_collective_on_the_logits(model_name, axes):
    """The compiled value_and_grad of loss_fn_fused, parameters laid
    out by the model's own logical axes: no all-reduce, all-gather or
    reduce-scatter gives a [rows, vocab] array or comes from the
    logits product. Left to XLA, the table's embed dim split over
    fsdp makes every chip form partial logits of all rows of a chunk
    and all-reduce them (f32[4096,32000] twice a step in
    mistral-7b-host4.fsdp4)."""
    model, cfg = _tiny_model(model_name)
    mesh = _mesh(**axes)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    specs = prune_specs_to_mesh(
        mesh, tree_specs(model.param_logical_axes(cfg), None)
    )
    params = jax.tree.map(
        lambda s, p: jax.device_put(p, NamedSharding(mesh, s)),
        specs, params, is_leaf=lambda x: isinstance(x, P),
    )
    tokens = jnp.zeros((8, cfg.block_size), jnp.int32)
    tok, tgt = shard_batch(mesh, tokens, tokens)
    loss = functools.partial(model.loss_fn_fused, cfg=cfg, num_chunks=4)
    fn = jax.jit(jax.value_and_grad(under_mesh(loss, mesh)))
    text = fn.lower(params, tok, tgt).compile().as_text()
    seen = 0
    for line in text.splitlines():
        m = _COLLECTIVE.match(line)
        if not m:
            continue
        seen += 1
        assert "ce,ve->cv" not in line, line
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            assert not (
                len(dims) >= 2 and dims[-1] == cfg.vocab_size
            ), line
    assert seen  # the step has collectives: the pattern reads them


def _head_inputs(n, e, v, dtype):
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (n, e), jnp.float32).astype(dtype)
    wte = (0.1 * jax.random.normal(kw, (v, e), jnp.float32)).astype(dtype)
    return x, wte, jax.random.randint(kt, (n,), 0, v)


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize(
    "axes,table_spec,dtype,rows,scale",
    [
        ({"fsdp": 4}, P(None, "fsdp"), jnp.float32, 256, 1.0),
        ({"data": 2, "fsdp": 2}, P(None, "fsdp"), jnp.float32, 256, 1.0),
        ({"data": 2, "tensor": 2}, P("tensor", None), jnp.float32, 256, 1.0),
        ({"fsdp": 4}, P(None, "fsdp"), jnp.bfloat16, 256, 1.0),
        ({"data": 2, "tensor": 2}, P("tensor", None), jnp.bfloat16, 256, 1.0),
        # an upstream cotangent other than 1
        ({"fsdp": 4}, P(None, "fsdp"), jnp.float32, 256, 0.25),
        # 4 devices leave 36 rows each, which 8 chunks do not divide,
        # and 4 does not divide 250 rows: the plain call both times
        ({"fsdp": 4}, P(None, "fsdp"), jnp.float32, 144, 1.0),
        ({"fsdp": 4}, P(None, "fsdp"), jnp.float32, 250, 1.0),
    ],
    ids=["fsdp4-f32", "data2xfsdp2-f32", "data2xtensor2-f32", "fsdp4-bf16",
         "data2xtensor2-bf16", "fsdp4-cotangent", "fsdp4-chunks-left-over",
         "fsdp4-rows-left-over"],
)
def test_per_device_head_matches_one_device(
    axes, table_spec, dtype, rows, scale
):
    """Loss, dx and dwte of the head under a mesh against the same
    call on one device. In float32 only the order of the table
    gradient's sum over devices may differ (1e-6 relative); with
    bfloat16 inputs that sum is still float32 and cast once, so the
    table's gradient is equal after the cast in all but a handful of
    its 24,576 elements (a float32 sum that lands on a rounding
    boundary), each off by one bfloat16 step."""
    e, v = 64, 384
    chunks = 2 if rows == 250 else 8
    x, wte, targets = _head_inputs(rows, e, v, dtype)

    def loss(x, wte, targets):
        return fused_cross_entropy(x, wte, targets, chunks) * scale

    grad = functools.partial(jax.value_and_grad, argnums=(0, 1))
    want, (want_dx, want_dw) = jax.jit(grad(loss))(x, wte, targets)

    mesh = _mesh(**axes)
    rows_spec = P(batch_spec(mesh)[0] if rows % 4 == 0 else None)
    put = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))  # noqa: E731
    got, (dx, dw) = jax.jit(grad(under_mesh(loss, mesh)))(
        put(x, P(*rows_spec, None)), put(wte, table_spec),
        put(targets, rows_spec),
    )
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    if dtype == jnp.float32:
        assert _rel(dx, want_dx) < 1e-6
        assert _rel(dw, want_dw) < 1e-6
    else:
        assert dx.dtype == dw.dtype == jnp.bfloat16
        # vocab split over tensor sums dx over two devices' halves
        assert _rel(dx, want_dx) < (2e-3 if "tensor" in axes else 1e-6)
        differ = np.asarray(dw != want_dw)
        assert differ.sum() <= 32, differ.sum()
        assert _rel(dw, want_dw) < 2e-4


def test_head_per_device_event():
    """A trace under a four-device mesh emits ``head.per_device``
    once with the axes and the rows a device holds; one device, and
    rows the chunks do not divide on a device, emit none."""
    x, wte, targets = _head_inputs(256, 64, 384, jnp.float32)
    loss = lambda x, w, t: fused_cross_entropy(x, w, t, 8)  # noqa: E731
    tracer = obs.configure_tracer()
    try:
        def events():
            return [
                e for e in tracer.events()
                if e["name"] == "head.per_device"
            ]

        jax.jit(jax.value_and_grad(loss)).lower(x, wte, targets)
        one = _mesh(data=1)
        jax.jit(jax.value_and_grad(under_mesh(loss, one))).lower(
            x, wte, targets
        )
        assert events() == []
        mesh = _mesh(data=2, fsdp=2)
        jax.jit(jax.value_and_grad(under_mesh(loss, mesh))).lower(
            x, wte, targets
        )
        (ev,) = events()
        assert ev["axes"] == ["data", "fsdp"]
        assert ev["rows_per_device"] == 64
        assert ev["chunks"] == 8
        jax.jit(under_mesh(loss, mesh)).lower(x[:144], wte, targets[:144])
        assert len(events()) == 1
    finally:
        obs.disable_tracer()


# -- a weight a row (ISSUE 44): sum_i w_i nll_i --------------------------------


def _naive_weighted(x, wte, targets, weights):
    logits = jnp.einsum(
        "ne,ve->nv", x.astype(jnp.float32), wte.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * nll), nll


def _row_weights(n):
    """Positive, summing to 1, uneven: an exit distribution's shares."""
    return jax.nn.softmax(2.0 * jax.random.normal(jax.random.PRNGKey(7), (n,)))


@pytest.mark.parametrize("differentiate", [False, True])
@pytest.mark.parametrize("num_chunks", [1, 4])
def test_weighted_loss_matches_dense(differentiate, num_chunks):
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    weights = _row_weights(64)
    want, _ = _naive_weighted(x, wte, targets, weights)
    loss = lambda x: fused_cross_entropy(  # noqa: E731
        x, wte, targets, num_chunks, weights
    )
    got = jax.value_and_grad(loss)(x)[0] if differentiate else loss(x)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("g", [1.0, 0.25])
def test_weighted_grads_match_dense(g):
    """dx and the table's gradient carry each row's weight where the
    mean has 1/N; the weights' gradient is the rows' losses."""
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    weights = _row_weights(64)
    got = jax.grad(
        lambda x, w, wt: g * fused_cross_entropy(x, w, targets, 4, wt),
        argnums=(0, 1, 2),
    )(x, wte, weights)
    want = jax.grad(
        lambda x, w, wt: g * _naive_weighted(x, w, targets, wt)[0],
        argnums=(0, 1, 2),
    )(x, wte, weights)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5
    _, nll = _naive_weighted(x, wte, targets, weights)
    np.testing.assert_allclose(got[2], g * nll, rtol=1e-5)
    assert got[2].dtype == weights.dtype


def test_uniform_weights_are_the_mean():
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    grad = functools.partial(jax.value_and_grad, argnums=(0, 1))
    want, want_g = grad(lambda x, w: fused_cross_entropy(x, w, targets, 4))(
        x, wte
    )
    got, got_g = grad(lambda x, w: fused_cross_entropy(
        x, w, targets, 4, jnp.full((64,), 1.0 / 64)
    ))(x, wte)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(got_g, want_g):
        assert _rel(a, b) < 1e-6


def test_weighted_bf16_cotangents_stay_bf16():
    x, wte, targets = _head_inputs(64, 16, 96, jnp.bfloat16)
    weights = _row_weights(64)
    dx, dw, dwt = jax.grad(
        lambda x, w, wt: fused_cross_entropy(x, w, targets, 4, wt),
        argnums=(0, 1, 2),
    )(x, wte, weights)
    assert dx.dtype == dw.dtype == jnp.bfloat16 and dwt.dtype == jnp.float32
    want = jax.grad(
        lambda x, w, wt: _naive_weighted(x, w, targets, wt)[0],
        argnums=(0, 1, 2),
    )(x, wte, weights)
    assert _rel(dx, want[0]) < 2e-2 and _rel(dw, want[1]) < 2e-2
    assert _rel(dwt, want[2]) < 1e-5


def test_without_weights_the_trace_is_what_it_was():
    """No weights: the same jaxpr as the call that never had the
    operand (no multiply by a row's weight, no second residual pair),
    one event fewer; three products over the vocabulary either way."""
    x, wte, targets = _head_inputs(64, 16, 96, jnp.float32)
    weights = _row_weights(64)
    grad = functools.partial(jax.value_and_grad, argnums=(0, 1))
    plain = jax.make_jaxpr(grad(
        lambda x, w: fused_cross_entropy(x, w, targets, 4)
    ))(x, wte)
    none = jax.make_jaxpr(grad(
        lambda x, w: fused_cross_entropy(x, w, targets, 4, None)
    ))(x, wte)
    assert str(plain) == str(none)
    weighted = jax.make_jaxpr(grad(
        lambda x, w: fused_cross_entropy(x, w, targets, 4, weights)
    ))(x, wte)
    count = lambda j: str(j).count("dot_general")  # noqa: E731
    assert count(plain) == count(weighted) == 3
    tracer = obs.configure_tracer()
    try:
        names = lambda: [e["name"] for e in tracer.events()]  # noqa: E731
        jax.jit(grad(lambda x, w: fused_cross_entropy(x, w, targets, 4))).lower(
            x, wte
        )
        assert "head.weighted_rows" not in names()
        jax.jit(grad(
            lambda x, w: fused_cross_entropy(x, w, targets, 4, weights)
        )).lower(x, wte)
        (ev,) = [e for e in tracer.events() if e["name"] == "head.weighted_rows"]
        assert ev["rows"] == 64 and ev["chunks"] == 4
    finally:
        obs.disable_tracer()


@pytest.mark.parametrize("axes,table_spec", [
    ({"fsdp": 4}, P(None, "fsdp")),
    ({"data": 2, "fsdp": 2}, P(None, "fsdp")),
], ids=["fsdp4", "data2xfsdp2"])
def test_per_device_weights_go_with_their_rows(axes, table_spec):
    """Under a mesh each device runs on its own rows with their
    weights: loss and all three gradients as on one device."""
    rows, e, v = 256, 64, 384
    x, wte, targets = _head_inputs(rows, e, v, jnp.float32)
    weights = _row_weights(rows)

    def loss(x, wte, weights, targets):
        return fused_cross_entropy(x, wte, targets, 8, weights)

    grad = functools.partial(jax.value_and_grad, argnums=(0, 1, 2))
    want, want_g = jax.jit(grad(loss))(x, wte, weights, targets)
    mesh = _mesh(**axes)
    rows_spec = P(batch_spec(mesh)[0])
    put = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))  # noqa: E731
    tracer = obs.configure_tracer()
    try:
        got, got_g = jax.jit(grad(under_mesh(loss, mesh)))(
            put(x, P(*rows_spec, None)), put(wte, table_spec),
            put(weights, rows_spec), put(targets, rows_spec),
        )
        assert [e["rows_per_device"] for e in tracer.events()
                if e["name"] == "head.per_device"] == [64]
    finally:
        obs.disable_tracer()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(got_g, want_g):
        assert _rel(a, b) < 1e-6
