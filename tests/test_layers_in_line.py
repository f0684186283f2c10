"""The dense stacks' first layers in line (models/layers.py).

``gpt.backbone`` and ``llama.backbone_with_aux`` call the first
``min(n, IN_LINE)`` layers in a row and scan the others. It is the
same blocks on the same weights in the same order: held here to the
stack scanned whole (``IN_LINE`` 0, set by the test, the form the
models had before), to a plain loop over the layers for the expert
layers' router loss, and read on the gradient's jaxpr for what an
in-line layer keeps and what it does not stack.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu import obs
from dlrover_tpu.models import gpt, layers, llama
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import prune_specs_to_mesh, tree_specs
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
from dlrover_tpu.trainer.step import _match_opt_sharding
from tests.test_remat_policies import _eqns

B, T = 2, 32
DEPTHS = [1, 2, 4, 5, 12]


def _family(family, n_layer, remat, **overrides):
    """(model, cfg) at the smallest widths the other model tests use,
    float32 so that CPU gradients compare at float32 tolerance."""
    if family == "gpt":
        return gpt, gpt.GPTConfig(
            vocab_size=128, block_size=T, n_layer=n_layer, n_head=2,
            n_embd=32, dtype=jnp.float32, remat=remat, **overrides,
        )
    return llama, llama.LlamaConfig(
        vocab_size=128, block_size=T, n_layer=n_layer, n_head=4,
        n_kv_head=2, n_embd=32, intermediate=96, dtype=jnp.float32,
        remat=remat, n_experts=4 if family == "moe" else 0, **overrides,
    )


def _batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 128)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _loss_and_grads(model, cfg, params):
    loss = functools.partial(model.loss_fn, cfg=cfg)
    return jax.jit(jax.value_and_grad(loss))(params, *_batch())


@pytest.mark.parametrize("remat", [False, True, "full"])
@pytest.mark.parametrize("n_layer", DEPTHS)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_loss_and_gradients_are_the_scanned_stacks(
    monkeypatch, family, n_layer, remat
):
    model, cfg = _family(family, n_layer, remat)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    loss, grads = _loss_and_grads(model, cfg, params)
    monkeypatch.setattr(layers, "IN_LINE", 0)
    want_loss, want = _loss_and_grads(model, cfg, params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    # The gradient has ``params``' own tree and shapes: the in-line
    # layers' rows stand before the scan's in every stacked leaf.
    assert jax.tree.map(jnp.shape, grads) == jax.tree.map(jnp.shape, params)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6, rtol=1e-5
        )


@pytest.mark.parametrize("n_layer", [3, 6])
def test_router_loss_sums_over_scanned_and_in_line_layers(n_layer):
    """The Llama block's ``(x, aux)`` carry: ``aux`` is the sum of
    every layer's share, the scanned ones' and the in-line ones',
    against a plain loop over the layers outside any stack."""
    model, cfg = _family("moe", n_layer, "full")
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch()
    x, aux = jax.jit(
        functools.partial(llama.backbone_with_aux, cfg=cfg)
    )(params, tokens)

    cos, sin = llama.rope_table(cfg, T)
    attn = llama.default_attention_for(cfg)
    h = params["wte"][tokens].astype(cfg.dtype)
    shares = []
    for i in range(n_layer):
        lp = jax.tree.map(lambda a: a[i], params["blocks"])
        h, share = llama._block(h, lp, cfg, attn, cos, sin)
        shares.append(float(share))
    assert min(shares) > 0
    np.testing.assert_allclose(float(aux), sum(shares), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(x),
        np.asarray(llama._rms_norm(h, params["rmsf"], cfg.rms_eps)),
        atol=1e-5, rtol=1e-5,
    )


def _events_of_one_trace(model, cfg):
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    tracer = obs.configure_tracer()
    try:
        jax.jit(jax.value_and_grad(
            functools.partial(model.loss_fn, cfg=cfg)
        )).lower(params, *_batch())
        return tracer.events()
    finally:
        obs.disable_tracer()


@pytest.mark.parametrize("n_layer,in_line,scanned", [
    (1, 1, 0), (2, 2, 0), (4, 3, 1), (5, 3, 2), (12, 3, 9),
])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_the_event_says_how_the_stack_ran(family, n_layer, in_line, scanned):
    """``layers.in_line`` once a trace, and the block traced once
    whatever the depth (``remat.kept`` once): the scan's body and the
    in-line calls are one ``jax.jit``."""
    events = _events_of_one_trace(*_family(family, n_layer, "full"))
    (ev,) = [e for e in events if e["name"] == "layers.in_line"]
    assert (ev["in_line"], ev["scanned"], ev["n_layer"]) == (
        in_line, scanned, n_layer
    )
    assert len([e for e in events if e["name"] == "remat.kept"]) == 1


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_an_in_line_layer_keeps_what_it_names_and_stacks_nothing(family):
    """Seven layers under "full" with the flash kernels: three in
    line, four scanned. The scan stacks what its four layers keep and
    their inputs, ``[4, B, T, ...]``; an in-line layer's forward call hands
    the same set on as values of its own, ``[B, T, ...]`` (its input
    is the call before's result as it stands); nothing of the
    gradient is shaped ``[3, B, ...]`` or ``[7, B, ...]``; the flash
    forward runs once a layer (once in the scan's body, once in each
    in-line call) and not again beside the backward kernel."""
    model, cfg = _family(
        family, 7, "full", use_flash_attention=True,
        attn_blocks=(32, 32, 32, 32),
    )
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        functools.partial(model.loss_fn, cfg=cfg)
    ))(params, *_batch()).jaxpr

    def kept(eqn, carried, lead):
        return sorted(
            v.aval.shape[lead:] for v in eqn.outvars[carried:]
            if v.aval.ndim > lead + 1
        )

    scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
    assert scan.params["length"] == 4
    stacked = kept(scan, scan.params["num_carry"], 1)
    carried = 1 if family == "gpt" else 2  # x, or (x, aux)
    calls = [
        e for e in jaxpr.eqns
        if e.primitive.name == "jit"
        and sorted(kept(e, carried, 0) + [(B, T, 32)]) == stacked
        and e.outvars[0].aval.shape == (B, T, 32)
    ]
    assert len(calls) == 3 and stacked, (len(calls), stacked)
    assert not [
        v.aval.shape for e in jaxpr.eqns for v in e.outvars
        if v.aval.shape[:2] in ((3, B), (7, B))
    ]
    names = [
        str(e.params.get("name")) for e in _eqns(jaxpr)
        if e.primitive.name == "pallas_call"
    ]
    assert names.count("flash_attention_fwd") == 4, names
    assert names.count("flash_attention_bwd") == 4, names


@pytest.mark.parametrize("axis", ["data", "fsdp"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_the_step_is_one_program_on_a_mesh_of_four(family, axis):
    """Six layers, three in line and three scanned, through
    ``ElasticTrainer``'s step on ``data=4`` and on ``fsdp=4``: two
    steps, one compiled program, and the first step's loss is the
    one-device loss of the same batch."""
    model, cfg = _family(family, 6, "full")
    mesh = build_mesh(MeshConfig(**{axis: 4}), devices=jax.devices()[:4])
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    optimizer = optax.adamw(1e-3)
    trainer = ElasticTrainer(
        mesh, loss, optimizer, global_batch_size=4, micro_batch_size=1,
    )
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        prune_specs_to_mesh(
            mesh, tree_specs(model.param_logical_axes(cfg), None)
        ),
        is_leaf=lambda x: isinstance(x, P),
    )
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (4, T), 0, 128)
    )
    want = float(jax.jit(loss)(params, tokens, tokens))
    params, opt_state = jax.device_put(
        (params, optimizer.init(params)),
        (shardings, _match_opt_sharding(
            jax.eval_shape(optimizer.init, params), params, shardings, mesh
        )),
    )
    losses = []
    for _ in range(2):
        params, opt_state, step_loss = trainer.train_step(
            params, opt_state, tokens, tokens
        )
        losses.append(float(step_loss))
    assert trainer._compiled._cache_size() == 1
    np.testing.assert_allclose(losses[0], want, rtol=1e-5)
    assert losses[1] < losses[0]
