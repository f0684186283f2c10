"""models/phi4_flash.py against benchmark/reference/phi4_flash.py, at a
small size on seeded weights: the loss and every gradient for 8 and
12 layers by the rule (12 has two gated memory units and two
cross-attention layers, so the memory's and the shared keys' and
values' cotangents are sums), for a slice whose first layer is not 0
and with the cross-decoder's second pair under the scan; the window's
mask at a toy window of 4; ``lam0`` by the published index; slices
that lack a producer. (``remat``, the events and the trainer's path:
tests/test_phi4_flash_trainer.py.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.families import phi4_flash as family
from benchmark.reference import phi4_flash as reference
from dlrover_tpu.models import layers, phi4_flash as model

TOY = os.path.join(cell_files.HERE, "testdata", "cells", "configs")


def _config(n=12, first=2, count=8, **changed):
    """The toy configuration as layers ``[first, first + count)`` of an
    ``n``-layer stack."""
    with open(os.path.join(TOY, "toy-phi4.json")) as f:
        config = json.load(f)
    config.update(num_hidden_layers=count, reduced_from={"num_hidden_layers": n})
    config["assumed"] = dict(config["assumed"], first_layer=first)
    return dict(config, **changed)


def _float32(config, seed=3, **fields):
    """The family on ``config``, float32 and without remat: the
    comparison with the reference is then of the mathematics."""
    built = family.build(config)
    cfg = dataclasses.replace(
        built["cfg"], **dict(dict(dtype=jnp.float32, remat="none"), **fields)
    )
    params = jax.jit(lambda k: model.init_params(k, cfg))(
        jax.random.PRNGKey(seed)
    )
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, cfg.block_size + 1), 0, cfg.vocab_size
    )
    return cfg, params, (tok[:, :-1], tok[:, 1:])


def test_the_rule_at_the_published_depth():
    kinds = model.layer_kinds(32)
    of = lambda kind: [l for l, k in enumerate(kinds) if k == kind]
    assert of(model.MAMBA) == list(range(0, 16, 2))
    assert of(model.WINDOW) == list(range(1, 16, 2))
    assert (of(model.MEMORY), of(model.FULL)) == ([16], [17])
    assert of(model.GMU) == list(range(18, 32, 2))
    assert of(model.CROSS) == list(range(19, 32, 2))
    cfg = model.Phi4FlashConfig()
    assert [(unit, count, index) for _, unit, count, index in cfg.runs] == [
        ((model.MAMBA, model.WINDOW), 8, 0), ((model.MEMORY,), 1, 16),
        ((model.FULL,), 1, 17), ((model.GMU, model.CROSS), 7, 18),
    ]
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    # The benchmark's family has the rule written out a second time.
    assert family.layer_kinds(_config(32, 0, 32)) == list(kinds)
    assert family.layer_kinds(_config(32, 14, 6)) == list(kinds[14:20])
    with pytest.raises(ValueError, match="multiple of 4"):
        model.layer_kinds(10)


@pytest.mark.parametrize(
    "n,first,count,in_line",
    [(8, 0, 8, 3), (12, 0, 12, 1), (12, 4, 6, 3)],
    ids=["eight", "twelve", "slice_from_4"],
)
def test_loss_and_every_gradient_are_the_reference_s(
    n, first, count, in_line, monkeypatch
):
    """Eight layers and the slice (published layers 4 to 9 of twelve:
    every kind once and Mamba twice, as the benchmark's cell holds 14
    to 19 of 32) run in line. The twelve run with ``in_line`` 1, as
    the published 32 do past three units: the later pairs of each run
    are under the run's ``lax.scan``, and the memory and the shared
    keys and values are constants of it. The reference is a loop over
    the layers either way."""
    monkeypatch.setattr(layers, "IN_LINE", in_line)
    config = _config(n, first, count)
    cfg, params, batch = _float32(config)
    assert cfg.first_layer == first and cfg.n_layer == count
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)
    ))(params)
    want, ref = jax.value_and_grad(
        lambda p: reference.loss(p, *batch, config=config)
    )(params)
    # float32 against float32 at "highest": sums in another order (the
    # chunked scan, the fused head, XLA's fusions). A bf16 scan state
    # is a thousand times further off (tests/test_selective_scan.py).
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    if n == 8:
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


@pytest.mark.parametrize("flash", [False, True])
def test_the_window_is_the_reference_s_mask(flash):
    """A Mamba layer and a window layer, window 4 in 64 tokens, on the
    flash kernels (interpreted here) and on plain attention."""
    config = _config(8, 0, 2, sliding_window=4)
    cfg, params, batch = _float32(config, use_flash_attention=flash)
    assert cfg.kinds == (model.MAMBA, model.WINDOW) and cfg.sliding_window == 4
    got = jax.jit(lambda p: model.forward(p, batch[0], cfg))(params)
    want = reference.logits(params, batch[0], config)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.std(want))
    wide = reference.logits(params, batch[0], dict(config, sliding_window=64))
    assert float(jnp.max(jnp.abs(wide - want))) > 1e-3 * float(jnp.std(want))


def test_lam0_goes_by_the_published_index():
    assert [round(model.lam0(l), 4) for l in (15, 17, 19)] == [
        0.7933, 0.7963, 0.798,
    ]
    assert model.lam0(0) == pytest.approx(0.2)
    # The same two kinds and the same weights as layers 0-1 and as
    # layers 2-3: another lam0, another loss, each the reference's.
    losses = []
    for first in (0, 2):
        config = _config(12, first, 2)
        cfg, params, batch = _float32(config)
        assert cfg.kinds == (model.MAMBA, model.WINDOW)
        got = float(jax.jit(
            lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)
        )(params))
        want = float(reference.loss(params, *batch, config=config))
        assert abs(got - want) < 2e-6 * want
        losses.append(got)
    assert abs(losses[0] - losses[1]) > 1e-5 * losses[0]


@pytest.mark.parametrize(
    "first,count,what",
    [(8, 4, "memory"), (7, 5, "memory"), (9, 1, "kv"), (6, 1, None),
     (6, 2, None), (0, 6, None)],
)
def test_a_slice_must_hold_what_its_layers_read(first, count, what):
    """Of twelve layers, 6 makes the memory and 7 the shared keys and
    values; 8 and 10 read the one, 9 and 11 the other."""
    if what is None:
        assert model.Phi4FlashConfig.tiny(12, first, count).n_layer == count
        return
    with pytest.raises(ValueError, match=f"reads the {what}"):
        model.Phi4FlashConfig.tiny(12, first, count)
    with pytest.raises(ValueError, match="no layers"):
        model.Phi4FlashConfig.tiny(12, 10, 4)
