"""Family ``olmoe`` on the CPU: the toy cell rehearsed end to end, the
yardstick's counts for the published configuration, and the program's
OLMoE block (sorted, dropless experts; normalised queries and keys;
unrenormalised top-k weights) against the plain reference at toy
width, on one device and on host-device meshes."""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark import flops
from benchmark.families import olmoe as family
from benchmark.kernel_work import moe_gmm
from benchmark.reference import olmoe as reference
from dlrover_tpu.models import llama

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")


def _config(name, root=cell_files.HERE):
    with open(os.path.join(root, "configs", name + ".json")) as f:
        return json.load(f)


def test_toy_olmoe_cell_rehearsal_prints_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cell_files.HERE, "run.py"),
         "--workload", "toy-olmoe.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", "0", "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=220,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p90"}
    assert line["detail"]["reference"]["rms_rel"] < 3e-4


def test_published_configuration_counts():
    config = _config("olmoe-1b-7b")
    shape = family.shape(config)
    # 4 x 2048^2 + 2048 x 64 + 8 x 3 x 2048 x 1024
    assert shape["layer_matmul_params"] == 67_239_936
    assert (shape["experts"], shape["experts_per_token"],
            shape["expert_width"]) == (64, 8, 1024)
    assert shape["head_dim"] == 128 and shape["window"] is None
    # 6 x (67,239,936 + 50304 x 2048) + 12 x 2048 x (4096 + 1) / 2
    assert flops.train_flops_per_token(config) == 1_071_919_104
    # Every width as published; only the depth is cut.
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["max_position_embeddings"], config["norm_topk_prob"],
            config["vocab_size"]) == (2048, 1024, 64, 8, 4096, False, 50304)
    cfg = family.build(config)["cfg"]
    want = dataclasses.replace(
        llama.LlamaConfig.olmoe_1b_7b(), n_layer=config["num_hidden_layers"]
    )
    assert cfg == want


def test_moe_gmm_work_by_hand():
    shape = family.shape(_config("olmoe-1b-7b"))
    work = moe_gmm.work(shape, 4)
    rows = 4 * 4096 * 8
    assert rows == 131_072
    assert work["flops"] == 2 * 131_072 * 2048 * 1024 == 549_755_813_888
    # rows x 2048 and rows x 1024 activations, 64 x 2048 x 1024 weights, bf16
    assert work["bytes"] == 2 * (268_435_456 + 134_217_728 + 134_217_728)
    assert flops.kernel_work("moe_gmm", _config("olmoe-1b-7b"), 4) == work


# -- the program's block against the plain reference, toy width ---------


@pytest.fixture(scope="module")
def toy():
    """The toy configuration, its float32 program and seeded weights
    with gains off 1 and a router scaled so that loads are uneven."""
    config = _config("toy-olmoe", TOY)
    cfg = dataclasses.replace(
        family.build(config)["cfg"], dtype=jnp.float32,
        use_flash_attention=False,
    )
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    noise = jax.tree.map(
        lambda x: 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
        params,
    )
    params = jax.tree.map(jnp.add, params, noise)
    # Every token's hidden state shares a direction, and the router
    # reads it: expert 0 is in almost every token's top 3, the last
    # expert in none.
    common = jnp.ones((cfg.n_embd,)) / np.sqrt(cfg.n_embd)
    params["wte"] = params["wte"] + 0.4 * common
    moe = params["blocks"]["moe"]
    router = moe["router"] * 10.0
    router = router.at[:, :, 0].add(common).at[:, :, -1].add(-2.0 * common)
    params["blocks"]["moe"] = dict(moe, router=router)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 256, (2, 65)).astype(np.int32)
    return config, cfg, params, jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _rel(got, want):
    """Largest difference over the largest magnitude of ``want``."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# What float32 on both sides leaves: the same sums in another order (a
# grouped product over sorted rows, a permutation and its inverse, a
# chunked loss head). Read on this test: loss 1.6e-7, logits 3.8e-7,
# gradients at most 1.2e-6 of each leaf's largest element (the query
# and key gains). 5e-6 holds them with four times the room, and the
# bf16 program below misses it six-fold.
F32_TOL = 5e-6
# The same program with bf16 parameters and activations (f32 router,
# norms and accumulation) reads 2.9e-5 on this test; the bound is the
# benchmark's own on the chip (kinds/common.REFERENCE_REL_TOL).
BF16_TOL = 3e-4


def test_olmoe_block_agrees_with_the_reference(toy):
    config, cfg, params, tok, tgt = toy
    # The loads are uneven: the load-balancing loss reads top_k (3)
    # when every expert holds the same share, and experts (8) when one
    # holds every token.
    _, balance, _ = reference.hidden_and_router_losses(params, tok, config)
    assert float(balance) > 1.5 * config["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            functools.partial(llama.loss_fn_fused, cfg=cfg)
        ))(params, tok, tgt)
        logits = llama.forward(params, tok, cfg)
    want, g_want = jax.value_and_grad(
        functools.partial(reference.loss, config=config)
    )(params, tok, tgt)
    assert float(got) == pytest.approx(float(want), rel=F32_TOL)
    assert _rel(logits, reference.logits(params, tok, config)) < F32_TOL
    flat_got = dict(jax.tree_util.tree_leaves_with_path(g_got))
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_want):
        assert float(jnp.max(jnp.abs(leaf))) > 0.0, path
        assert _rel(flat_got[path], leaf) < F32_TOL, path
    # The unrenormalised weights are in the reference: renormalised,
    # the loss differs.
    renormalised = dict(config, norm_topk_prob=True)
    assert float(reference.loss(params, tok, tgt, renormalised)) != pytest.approx(
        float(want), rel=F32_TOL
    )


def test_bf16_program_fails_the_float32_tolerance(toy):
    """The tolerance is tight enough that computing in the precision
    below would fail it, and the bf16 one is stated."""
    config, cfg, params, tok, tgt = toy
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim >= 2 and x.shape[-1] != cfg.n_experts else x,
        params,
    )
    got = float(jax.jit(functools.partial(llama.loss_fn_fused, cfg=cfg16))(
        params16, tok, tgt
    ))
    # The reference reads the same bf16-valued weights in float32.
    want = float(reference.loss(params16, tok, tgt, config))
    assert got != pytest.approx(want, rel=F32_TOL)
    assert got == pytest.approx(want, rel=BF16_TOL)


@pytest.mark.parametrize("axes", [
    {"data": 2}, {"fsdp": 2}, {"data": 2, "fsdp": 2},
])
def test_same_loss_on_host_device_meshes(toy, axes):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, under_mesh
    from dlrover_tpu.parallel.sharding import tree_shardings
    from dlrover_tpu.trainer.step import shard_batch

    config, cfg, params, tok, tgt = toy
    size = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:size])
    loss = functools.partial(llama.loss_fn_fused, cfg=cfg)
    tok4, tgt4 = jnp.tile(tok, (2, 1)), jnp.tile(jnp.flip(tgt, 0), (2, 1))
    want = float(jax.jit(loss)(params, tok4, tgt4))
    sharded = jax.tree.map(
        jax.device_put, params,
        tree_shardings(mesh, llama.param_logical_axes(cfg)),
    )
    got = float(jax.jit(under_mesh(loss, mesh))(
        sharded, *shard_batch(mesh, np.asarray(tok4), np.asarray(tgt4))
    ))
    assert got == pytest.approx(want, rel=F32_TOL)
