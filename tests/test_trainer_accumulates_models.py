"""The model families the cells run, under the trainer's accumulation.

Every cell runs ``ElasticTrainer``'s step at one microbatch, and the
trainer's own tests run it on a toy MLP. Here each family's
``loss_fn_fused`` (the head that forms its gradients in the forward
rule, the expert layer's and the looped stack's ``jax.jit`` inside the
loss) goes through the microbatch ``lax.scan`` at 1, 2 and 4
microbatches, on one device and on ``data=4``, where the mean of the
gradients over the shards is XLA's own collective. One
``train_step`` from seeded state is held to the step's definition,
computed outside the trainer on one device: ``jax.value_and_grad`` of
the same loss one microbatch at a time, the gradients' mean, one
``optimizer.update``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models import (
    deepseek_v2, gpt, granite_hybrid, kimi_linear, llama, mellum, ouro,
    phi4_flash,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

T = 64
ROWS = 4  # of a microbatch, over all shards
_FLASH = dict(use_flash_attention=True, attn_blocks=(64, 64, 64, 64))

# Each family at the smallest configuration its own tests use
# (tests/test_remat_policies.py, test_granite_hybrid.py, test_ouro.py),
# float32, under the remat policy its cell names (full), on the
# kernels, interpreted here: the flash kernel for the three dense
# stacks; the expert layer's grouped products and Granite's chunked
# scan with plain attention beside them, which keeps the file's
# fifty-four compiles inside its time.
_LLAMA = llama.LlamaConfig(
    vocab_size=128, block_size=T, n_layer=2, n_head=4, n_kv_head=2,
    n_embd=32, intermediate=96, dtype=jnp.float32, remat=True,
)
FAMILIES = {
    # the GPT-2 block with the tied fused head
    "gpt2": (gpt, gpt.GPTConfig(
        vocab_size=128, block_size=T, n_layer=2, n_head=2, n_embd=32,
        dtype=jnp.float32, remat=True, **_FLASH,
    )),
    # the Llama block with grouped queries and a window
    "llama": (llama, dataclasses.replace(
        _LLAMA, sliding_window=48, **_FLASH
    )),
    # the sorted expert layer with its two router losses
    "moe": (llama, dataclasses.replace(_LLAMA, n_experts=4)),
    # one Granite period with a Mamba-2 layer and an attention layer
    "granite": (granite_hybrid, dataclasses.replace(
        granite_hybrid.GraniteHybridConfig.tiny(),
        layer_types=(granite_hybrid.MAMBA, granite_hybrid.ATTENTION),
        remat="full",
    )),
    # Ouro with two passes
    "ouro": (ouro, dataclasses.replace(
        ouro.OuroConfig.tiny(), ut_steps=2, remat="full",
        use_flash_attention=True,
    )),
    # a KDA layer with the dense MLP and a latent-attention layer with
    # 4 of 16 experts held, a sigmoid router, a shared expert
    "kimi": (kimi_linear, dataclasses.replace(
        kimi_linear.KimiLinearConfig.tiny(),
        mixers=(kimi_linear.KDA, kimi_linear.MLA),
        ffns=(kimi_linear.DENSE, kimi_linear.MOE), remat="full",
    )),
    # two scanned periods of a sliding and a full layer, each kind with
    # its own rotation, 4 of 16 experts held and the router's loss
    "mellum": (mellum, dataclasses.replace(
        mellum.MellumConfig.tiny(),
        layer_types=(mellum.SLIDING, mellum.FULL) * 2, remat="full",
    )),
    # latent attention with a rotated key part on a dense layer and an
    # expert layer: 2 of 16 experts held, two shared, and the balance
    # loss a sequence
    "deepseek": (deepseek_v2, dataclasses.replace(
        deepseek_v2.DeepseekV2Config.tiny(), n_layer=2, remat="full",
    )),
    # eight layers by the rule: two Mamba-1 / window pairs, the layer
    # that makes the memory, the one that makes the shared keys and
    # values, and a gated memory unit and a cross-attention layer that
    # read them; the chunked selective scan with plain attention beside
    # it, as Granite's entry
    "phi4_flash": (phi4_flash, phi4_flash.Phi4FlashConfig.tiny(
        8, remat="full",
    )),
}
MESHES = {"one": 1, "data4": 4}
# This file's share of the families; the others' cases stand in
# tests/test_trainer_accumulates_hybrids.py, so that neither file is a
# tier-1 run's wall (every case is kept: 9 families x 3 x 2).
HERE = ("gpt2", "llama", "moe", "granite", "ouro")


def _loss(family):
    model, cfg = FAMILIES[family]
    return functools.partial(model.loss_fn_fused, cfg=cfg)


@functools.lru_cache(maxsize=None)
def _seeded_params(family):
    model, cfg = FAMILIES[family]
    init = jax.jit(functools.partial(model.init_params, cfg=cfg))
    host = jax.device_get(init(jax.random.PRNGKey(0)))
    assert all(x.dtype == np.float32 for x in jax.tree.leaves(host))
    return host


@functools.lru_cache(maxsize=None)
def _reference_grad(family):
    """The loss of one microbatch and its gradients, on one device and
    under no mesh: a family's router losses, exit gate and head are
    then the global ones by construction."""
    return jax.jit(jax.value_and_grad(_loss(family)))


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("accum", [1, 2, 4])
@pytest.mark.parametrize("family", HERE)
def test_train_step_is_the_mean_of_the_microbatches(family, accum, mesh_name):
    check_train_step(family, accum, mesh_name)


def check_train_step(family, accum, mesh_name):
    model, cfg = FAMILIES[family]
    n = MESHES[mesh_name]
    mesh = build_mesh(MeshConfig(data=n), devices=jax.devices()[:n])
    optimizer = optax.sgd(0.1, momentum=0.9)
    trainer = ElasticTrainer(
        mesh, _loss(family), optimizer,
        global_batch_size=ROWS * accum, micro_batch_size=ROWS // n,
    )
    assert trainer.accum_steps == accum and trainer.num_shards == n
    # Seeded state laid out on the mesh as a mesh with no fsdp axis
    # holds it, a whole copy a device; the step donates it, so the
    # reference below starts from the host's copy.
    host = _seeded_params(family)
    params, opt_state = jax.device_put(
        (host, optimizer.init(host)), NamedSharding(mesh, P())
    )

    rng = np.random.default_rng(accum)
    rows = rng.integers(0, cfg.vocab_size, (ROWS * accum, T + 1))
    rows = rows.astype(np.int32)
    tokens, targets = trainer.shard_microbatches(rows[:, :-1], rows[:, 1:])
    assert tokens.shape == (accum, ROWS, T)

    # The step's definition, from the microbatches as they were staged:
    # every shard's rows of a microbatch together (a jitted loss under
    # a mesh means the global one).
    staged = zip(jax.device_get(tokens), jax.device_get(targets))
    losses, grads = zip(*(
        _reference_grad(family)(host, tok, tgt) for tok, tgt in staged
    ))
    mean_grads = jax.tree.map(lambda *g: sum(g) / accum, *grads)
    updates, _ = optimizer.update(mean_grads, optimizer.init(host), host)
    want = optax.apply_updates(host, updates)

    params, opt_state, loss = trainer.train_step(
        params, opt_state, tokens, targets
    )
    # the tolerances of test_accumulated_step_equals_big_batch_step
    np.testing.assert_allclose(loss, sum(losses) / accum, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_allclose(
            got, ref, rtol=1e-5, atol=1e-6,
            err_msg=jax.tree_util.keystr(path),
        )

    # what the benchmark's ``step_programs.train`` reads
    trainer.train_step(params, opt_state, tokens, targets)
    assert trainer._compiled._cache_size() == 1
