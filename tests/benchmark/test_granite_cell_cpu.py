"""Family ``granite_hybrid`` on the CPU: the toy cell rehearsed end to
end, the yardstick's counts for the published configuration by hand,
and the program's hybrid stack (Mamba-2 layers among attention layers
by pattern, the chunked scan) against the plain reference with its
token-by-token recurrence: loss and gradients, on one device and on
host-device meshes."""

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
from benchmark.families import granite_hybrid as family
from benchmark.kernel_work import ssd_bwd, ssd_fwd
from benchmark.readers import model_flops
from tests.benchmark import membership
from benchmark.reference import granite_hybrid as reference
from dlrover_tpu.models import granite_hybrid as model

REPO = cell_files.REPO
TOY = os.path.join(cell_files.HERE, "testdata", "cells")
CELL = "granite-4.0-h-micro.steady"


def _config(name, root=cell_files.HERE):
    with open(os.path.join(root, "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(cell_files.HERE, "run.py"),
         "--workload", "toy-granite.steady", "--seed", "3000000019",
         "--seconds", "2", "--trace", str(trace), "--cells-root", TOY,
         "--allow-cpu", "--deadline-s", "200"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=220,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"], line["why_incorrect"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["detail"]["reference"]["rms_rel"] < 3e-4
    return line


def test_toy_granite_cell_rehearsal_prints_a_correct_line():
    line = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p90"}


def test_traced_rehearsal_reports_no_device_metric():
    """The toy cell names what the cell names; its readers find no
    device plane and no peak off the chip, return nothing and do not
    raise, and the line says which they were."""
    line = _rehearse(1)
    assert line["metrics"]["step_trace_lower_s.setup"]["value"] > 0
    assert not [m for m in line["metrics"] if m.startswith(("ssd_", "mfu"))]
    assert {"ssd_fwd_roofline.train", "ssd_bwd_roofline.train",
            "mfu.train"} <= set(line["notes"]["read_nothing"])


# -- the published configuration's counts, by hand ------------------------


def test_published_configuration_counts():
    config = _config("granite-4.0-h-micro")
    shape = family.shape(config)
    # [z | xBC | dt]: 2048 x (4096 + 4352 + 64); out: 4096 x 2048;
    # the MLP: 3 x 2048 x 8192.
    assert family.mamba_matmul_params(config) == (
        17_432_576 + 8_388_608 + 50_331_648
    )
    # wq, wo 2048^2 each; wk, wv 2048 x 512 each; the same MLP.
    assert family.attention_matmul_params(config) == (
        2 * 4_194_304 + 2 * 1_048_576 + 50_331_648
    )
    assert (shape["mamba_layers"], shape["attention_layers"]) == (9, 1)
    assert shape["layers"] * shape["layer_matmul_params"] == (
        9 * 76_152_832 + 60_817_408
    )
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) == (32, 8, 64)
    assert (shape["ssm_heads"], shape["ssm_head_dim"], shape["ssm_state"],
            shape["ssm_groups"], shape["ssm_chunk"]) == (64, 64, 128, 1, 256)
    assert shape["seq_len"] == 4096 and shape["window"] is None
    # Every parameter, matrices or not: a Mamba layer 76,182,976 (the
    # mixer 25,847,232 + 25,984 outside its two products; the MLP; the
    # two norms 4,096), an attention layer 60,821,504, the quarter
    # table 25,088 x 2048 and the final norm.
    cfg = family.build(config)["cfg"]
    sizes = jax.tree.map(
        lambda a: int(np.prod(a.shape)),
        jax.eval_shape(functools.partial(model.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)),
    )
    per_layer = {
        name: sum(jax.tree.leaves(tree)) // n
        for (name, _, n), tree in zip(cfg.runs, (
            sizes["runs"][name] for name, _, _ in cfg.runs
        ))
    }
    assert per_layer == {
        "0_mamba": 76_182_976, "1_attention": 60_821_504, "2_mamba": 76_182_976,
    }
    assert [n for _, _, n in cfg.runs] == [5, 1, 4]
    assert sizes["wte"] == 51_380_224
    assert sum(jax.tree.leaves(sizes)) == 797_850_560
    # Every width as published; depth, pattern and vocabulary cut.
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert config["reduced_from"]["num_hidden_layers"] == 40
    assert config["reduced_from"]["vocab_size"] == 100_352 == 4 * config["vocab_size"]
    published = config["reduced_from"]["layer_types"]
    assert [i for i, k in enumerate(published) if k == "attention"] == [5, 15, 25, 35]
    assert config["layer_types"] == published[:10]
    assert model.GraniteHybridConfig(layer_types=tuple(published)).period == tuple(
        config["layer_types"]
    )
    assert (config["hidden_size"], config["shared_intermediate_size"],
            config["mamba_d_conv"], config["embedding_multiplier"],
            config["attention_multiplier"], config["residual_multiplier"],
            config["logits_scaling"], config["tie_word_embeddings"]) == (
        2048, 8192, 4, 12, 0.015625, 0.22, 8, True)
    assert all(
        key + "_why" in config["assumed"] or key.endswith("_why")
        or key in ("dtype", "dt_max")
        for key in config["assumed"]
    )


def test_ssd_work_by_hand():
    shape = family.shape(_config("granite-4.0-h-micro"))
    work = ssd_fwd.work(shape, 1)
    # 16 chunks of 256: 32,896 pairs (t, s <= t) a chunk; C B^T over a
    # state of 128 once (one group), its product with dt x over 64
    # heads of 64; 15 states left and 15 read, 2 x 256 x 4096 x 128 each.
    assert 256 * 257 // 2 == 32_896
    assert work["flops"] == (
        16 * 2 * 32_896 * (128 + 4096) + 15 * 2 * 268_435_456
    ) == 12_499_550_208
    # x and y 4096 x 4096 bf16; B and C 4096 x 128 bf16; dt 4096 x 64
    # f32; 15 states of 64 x 64 x 128 f32.
    assert work["bytes"] == (
        2 * 33_554_432 + 2 * 1_048_576 + 1_048_576 + 15 * 2_097_152
    ) == 101_711_872
    back = ssd_bwd.work(shape, 1)
    assert back["flops"] == 2 * work["flops"]
    assert back["bytes"] == (
        3 * 33_554_432 + 4 * 1_048_576 + 2 * 1_048_576 + 15 * 2_097_152
    )
    assert flops.kernel_work("ssd_fwd", _config("granite-4.0-h-micro"), 2) == (
        ssd_fwd.work(shape, 2)
    )
    assert ssd_fwd.work(shape, 2)["flops"] == 2 * work["flops"]


def test_required_operations_a_token_by_hand():
    shape = family.shape(_config("granite-4.0-h-micro"))
    matrices = 9 * 76_152_832 + 60_817_408 + 25_088 * 2048
    assert matrices == 797_573_120
    attention = 12 * 2048 * (4096 + 1) / 2  # one attention layer
    scans = 3 * 9 * 12_499_550_208 / 4096
    assert family.flops_per_token(shape) == (
        6 * matrices + attention + scans
    ) == 4_918_177_152
    # The yardstick asks the family first; the reader reads nothing
    # without a peak.
    config = _config("granite-4.0-h-micro")
    assert flops.train_flops_per_token(config) == 4_918_177_152
    window = {"tokens_per_s": 1000.0}
    peaks = {"bf16_flops_per_s": 197e12}
    cell = {"config": config}
    assert model_flops.read({"window": window, "peaks": None, "cell": cell}) is None
    assert model_flops.read(
        {"window": {"tokens_per_s": 16_000.0}, "peaks": peaks, "cell": cell,
         "device": {"count": 1}}
    ) == pytest.approx(100 * 4_918_177_152 * 16_000 / 197e12)


def test_manifest_lists_the_cell():
    membership.assert_cell_is_listed(membership.manifest(), CELL)


@pytest.mark.parametrize("name", [
    "ssd_ms_per_step.train", "ssd_fwd_roofline.train",
    "ssd_bwd_roofline.train", "mfu.train", "attn_ms_per_step.train",
])
def test_manifest_lists_the_cell_in_its_metrics(name):
    spec = membership.assert_cell_reports(membership.manifest(), CELL, name)
    assert spec["moves"] == "tokens_per_s"


def test_shape_stays_off_jax_and_off_the_model():
    """A launcher reads ``shape`` without JAX, and no cell of another
    family pays for the hybrid model, its kernel or its reference:
    only ``build`` imports them."""
    code = (
        "import json, sys\n"
        "from benchmark import flops\n"
        "from benchmark.families import llama, olmoe, gpt\n"
        "c = json.load(open('benchmark/configs/granite-4.0-h-micro.json'))\n"
        "assert flops.shape_of(c)['mamba_layers'] == 9\n"
        "bad = [m for m in sys.modules if m == 'jax' or 'granite' in m\n"
        "       and 'families' not in m or m.endswith('ops.ssd')]\n"
        "assert not bad, bad\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- the program's stack against the plain reference, toy width -----------


@pytest.fixture(scope="module")
def toy():
    """The toy configuration (two periods of mamba, mamba, attention,
    mamba; two B/C groups; four chunks of 16), its float32 program and
    seeded weights: gains, D, the convolution's bias, A_log and
    dt_bias are all off the values that would hide them."""
    config = _config("toy-granite", TOY)
    cfg = dataclasses.replace(
        family.build(config)["cfg"], dtype=jnp.float32,
        use_flash_attention=False, remat=False,
    )
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    noise = jax.tree.map(
        lambda x: 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
        params,
    )
    params = jax.tree.map(jnp.add, params, noise)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 256, (2, 65)).astype(np.int32)
    return config, cfg, params, jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _rel(got, want):
    """Largest difference over the largest magnitude of ``want``."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# What float32 on both sides leaves: the same sums in another order
# (chunks of 16 against a token at a time, running sums of dt A
# against products of decays, a chunked loss head). Read on this
# test: loss 9e-8, logits 6e-7, gradients at most 8e-6 of each leaf's
# largest element (dt_bias, through the running sums). The loss is
# held to 1e-6, which the bf16 program below misses five-fold; logits
# and gradients to 3e-5, four times the room.
LOSS_TOL = 1e-6
F32_TOL = 3e-5
# The same program in bf16 reads 4.6e-6 on this test (at seeded
# weights the loss is near ln(vocabulary) and rounding moves it
# little); the bound is the benchmark's own on the chip
# (kinds/common.REFERENCE_REL_TOL).
BF16_TOL = 3e-4


@pytest.mark.parametrize("remat", [False, "full"])
def test_hybrid_stack_agrees_with_the_reference(toy, remat):
    config, cfg, params, tok, tgt = toy
    cfg = dataclasses.replace(cfg, remat=remat)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            functools.partial(model.loss_fn_fused, cfg=cfg)
        ))(params, tok, tgt)
        logits = model.forward(params, tok, cfg)
        plain = model.loss_fn(params, tok, tgt, cfg)
    want, g_want = jax.value_and_grad(
        functools.partial(reference.loss, config=config)
    )(params, tok, tgt)
    assert float(got) == pytest.approx(float(want), rel=LOSS_TOL)
    assert float(plain) == pytest.approx(float(want), rel=LOSS_TOL)
    assert _rel(logits, reference.logits(params, tok, config)) < F32_TOL
    flat_got = dict(jax.tree_util.tree_leaves_with_path(g_got))
    assert len(flat_got) == 2 + 2 * 13 + 9
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_want):
        assert float(jnp.max(jnp.abs(leaf))) > 0.0, path
        assert _rel(flat_got[path], leaf) < F32_TOL, path


@pytest.mark.parametrize("left_out", [
    "D", "conv_b", "ssm_norm", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "embedding_multiplier",
])
def test_the_reference_sees_every_path(toy, left_out):
    """No bias, gain, skip or multiplier sits at a value that hides
    it: the reference with it at its neutral value gives other
    logits."""
    config, cfg, params, tok, tgt = toy
    want = reference.logits(params, tok, config)
    if left_out in config:
        neutral = {"attention_multiplier": 0.5}.get(left_out, 1.0)
        other = reference.logits(
            params, tok, dict(config, **{left_out: neutral})
        )
    else:
        fill = 0.0 if left_out == "conv_b" else (
            0.0 if left_out == "D" else 1.0
        )
        runs = {
            name: {k: jnp.full_like(v, fill) if k == left_out else v
                   for k, v in tree.items()}
            for name, tree in params["runs"].items()
        }
        other = reference.logits(dict(params, runs=runs), tok, config)
    assert _rel(other, want) > 1e-3


def test_bf16_program_fails_the_float32_tolerance(toy):
    """The tolerance is tight enough that computing in the precision
    below would fail it, and the bf16 one is stated."""
    config, cfg, params, tok, tgt = toy
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    # Each leaf in the dtype the bf16 program's own init gives it.
    like = jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg16), jax.random.PRNGKey(0)
    )
    params16 = jax.tree.map(lambda x, l: x.astype(l.dtype), params, like)
    got = float(jax.jit(functools.partial(model.loss_fn_fused, cfg=cfg16))(
        params16, tok, tgt
    ))
    # The reference reads the same bf16-valued weights in float32.
    want = float(reference.loss(params16, tok, tgt, config))
    assert got != pytest.approx(want, rel=LOSS_TOL)
    assert got == pytest.approx(want, rel=BF16_TOL)


@pytest.mark.parametrize("axes", [
    {"data": 2}, {"fsdp": 2}, {"data": 2, "fsdp": 2},
])
def test_same_loss_on_host_device_meshes(toy, axes):
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, under_mesh
    from dlrover_tpu.parallel.sharding import tree_shardings
    from dlrover_tpu.trainer.step import shard_batch

    config, cfg, params, tok, tgt = toy
    size = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:size])
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    tok4, tgt4 = jnp.tile(tok, (2, 1)), jnp.tile(jnp.flip(tgt, 0), (2, 1))
    want = float(jax.jit(loss)(params, tok4, tgt4))
    sharded = jax.tree.map(
        jax.device_put, params,
        tree_shardings(mesh, model.param_logical_axes(cfg)),
    )
    got, grads = jax.jit(jax.value_and_grad(under_mesh(loss, mesh)))(
        sharded, *shard_batch(mesh, np.asarray(tok4), np.asarray(tgt4))
    )
    assert float(got) == pytest.approx(want, rel=LOSS_TOL)
    want_grads = jax.jit(jax.grad(loss))(params, tok4, tgt4)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(a, b) < 10 * F32_TOL


def test_the_recurrence_is_the_recurrence():
    """The reference's scan against the two equations in a Python
    loop, one head, five tokens."""
    rng = np.random.default_rng(3)
    x, b, c = (rng.normal(size=s).astype(np.float32)
               for s in ((1, 5, 1, 3), (1, 5, 1, 4), (1, 5, 1, 4)))
    dt = rng.uniform(0.1, 1.0, (1, 5, 1)).astype(np.float32)
    a, d = np.float32([-0.7]), np.float32([1.3])
    state = np.zeros((3, 4), np.float32)
    want = []
    for t in range(5):
        state = np.exp(dt[0, t, 0] * a[0]) * state + dt[0, t, 0] * np.outer(
            x[0, t, 0], b[0, t, 0]
        )
        want.append(state @ c[0, t, 0] + d[0] * x[0, t, 0])
    got = reference.recurrence(*(jnp.asarray(v) for v in (x, dt, a, b, c, d)))
    np.testing.assert_allclose(
        np.asarray(got)[0, :, 0], np.stack(want), rtol=1e-4, atol=1e-6
    )
