"""Ask the chip's compiler, without the chip: what the compile tests
(``tests/test_tpu_compile_*.py``) and ``tools/step_hash.py`` share.

libtpu is installed in the sandbox and compiles for a TPU that is
described, not attached (``v5e:2x2``). Nothing runs, so nothing here
says a result is right or fast, but the compiler refuses exactly what
it would refuse on the chip: a kernel over its VMEM budget, a tile
below the sublane floor, a Mosaic call GSPMD cannot partition, a
program that does not fit 16 GB. Interpret-mode tests see none of
that.

The topology is described inside a fixture (or the tool's ``main``),
never while a module is imported: a process that loads libtpu keeps
its lock until it exits. The test files are split by family so that
no one of them is a run's wall under ``--dist loadfile``; several
workers then load libtpu at once, which the driver's command allows
(``ALLOW_MULTIPLE_LIBTPU_LOAD=1``) and a plain ``pytest`` in one
process never needs.

The step builders lower and stop there (``lower_train_step``,
``lower_step``); a test compiles what it is given, the tool hashes its
text.
"""

import contextlib
import dataclasses
import functools
import importlib
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.models import (
    deepseek_v2, gpt, granite_hybrid, kimi_linear, llama, mellum, ouro,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import prune_specs_to_mesh, tree_specs
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
from dlrover_tpu.trainer.step import (
    _match_opt_sharding,
    batch_spec,
    init_opt_state,
    make_train_step,
)

HBM_BYTES = 16e9  # one v5e chip

# The modules whose entry points ask ``use_interpret()`` where they
# are given no ``interpret`` argument.
_KERNEL_MODULES = (
    "flash_attention", "quantization", "grouped_matmul", "ssd",
    "causal_conv", "kda", "rows_sum", "rope",
)


@contextlib.contextmanager
def described_v5e():
    """The ``v5e:2x2`` topology, with the persistent compile cache
    off while it is in use: a compile for a described chip is written
    to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Else libtpu logs under /tmp.
    with mock.patch.dict(os.environ, {"TPU_LOG_DIR": "disabled"}):
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@contextlib.contextmanager
def kernels_for_the_chip():
    """Entry points without an ``interpret`` argument ask
    ``use_interpret()``, which sees this process's CPU backend; here
    the answer is the chip's. ``dlrover_tpu.ops.flash_attention`` the
    attribute is the re-exported function, so import the module by
    its name."""
    with contextlib.ExitStack() as stack:
        for name in _KERNEL_MODULES:
            stack.enter_context(mock.patch.object(
                importlib.import_module(f"dlrover_tpu.ops.{name}"),
                "use_interpret",
                lambda: False,
            ))
        yield


@pytest.fixture(scope="module")
def topo():
    with contextlib.ExitStack() as stack:
        try:
            desc = stack.enter_context(described_v5e())
        except Exception as e:  # noqa: BLE001 — any reason is a skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels():
    with kernels_for_the_chip():
        yield


def compile_(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def bf16(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)


# -- the cells' configurations, at the cells' shapes ----------------------


def gpt2_cfg():
    """GPT-2 124M as chip_smoke.py trains it."""
    return dataclasses.replace(
        gpt.GPTConfig.gpt2(), use_flash_attention=True
    )


def mistral_cfg():
    """Two layers at Mistral-7B's widths, T = 8192, window 4096."""
    return dataclasses.replace(
        llama.LlamaConfig.mistral_7b(), n_layer=2, block_size=8192,
        use_flash_attention=True,
    )


def olmoe_cfg():
    return dataclasses.replace(
        llama.LlamaConfig.olmoe_1b_7b(), n_layer=1,
        use_flash_attention=True,
    )


def granite_cfg():
    model = granite_hybrid
    return dataclasses.replace(
        model.GraniteHybridConfig(
            vocab_size=25088, layer_types=model.GraniteHybridConfig().period,
            remat="full",
        ),
        use_flash_attention=True,
    )


def ouro_cfg():
    return ouro.OuroConfig(
        n_layer=8, vocab_size=8192, jitter=0.1, use_flash_attention=True,
    )


def kimi_cfg():
    model = kimi_linear
    return model.KimiLinearConfig(
        vocab_size=20480,
        mixers=(model.KDA, model.KDA, model.KDA, model.MLA, model.KDA),
        ffns=(model.DENSE,) + (model.MOE,) * 4,
        held=8, remat="full", use_flash_attention=True,
    )


def mellum_cfg():
    return mellum.MellumConfig(
        vocab_size=24576, layer_types=mellum.MellumConfig().period,
        held=16, remat="full", use_flash_attention=True,
    )


def deepseek_cfg():
    return deepseek_v2.DeepseekV2Config(
        vocab_size=12800, n_layer=6, held=8, remat="full",
        use_flash_attention=True,
    )


# -- the steps ------------------------------------------------------------


def lower_train_step(model, cfg, devices, axis, global_batch, accum=None,
                     attn_fn=None):
    """``make_train_step``'s program, or with ``accum`` the trainer's
    own (``ElasticTrainer._build_step``: ``accum`` microbatches of
    ``global_batch`` rows through its ``lax.scan``), full remat, fused
    cross-entropy, adamw, flash attention (the family's own choice,
    or ``attn_fn``), lowered for ``devices`` laid out along ``axis``."""
    mesh = build_mesh(MeshConfig(**{axis: len(devices)}), devices=devices)
    optimizer = optax.adamw(6e-4)
    loss = functools.partial(model.loss_fn_fused, cfg=cfg, attn_fn=attn_fn)
    if accum is None:
        step = make_train_step(mesh, loss, optimizer)
        batch_shape, spec = (global_batch,), batch_spec(mesh)
    else:
        step = ElasticTrainer(
            mesh, loss, optimizer,
            global_batch_size=accum * global_batch,
            micro_batch_size=global_batch // len(devices),
        )._compiled
        batch_shape = (accum, global_batch)
        spec = P(None, *batch_spec(mesh))
    param_shapes = jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.PRNGKey(0)
    )
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        prune_specs_to_mesh(
            mesh, tree_specs(model.param_logical_axes(cfg), None)
        ),
        is_leaf=lambda x: isinstance(x, P),
    )
    opt_shapes = jax.eval_shape(
        functools.partial(init_opt_state, optimizer), param_shapes
    )
    opt_shardings = _match_opt_sharding(
        opt_shapes, param_shapes, param_shardings, mesh
    )

    def with_shardings(shapes, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings,
        )

    tokens = jax.ShapeDtypeStruct(
        batch_shape + (cfg.block_size,), jnp.int32,
        sharding=NamedSharding(mesh, spec),
    )
    return step.lower(
        with_shardings(param_shapes, param_shardings),
        with_shardings(opt_shapes, opt_shardings),
        tokens, tokens,
    )


# The ten cells' families at the cells' shapes. name: (model, its
# configuration, chips, the mesh axis they lie along, rows a
# microbatch, microbatches: None is ``make_train_step``'s program, a
# number ``ElasticTrainer``'s accumulate-then-update step as
# benchmark/trainer_loop.py runs a steady cell).
STEPS = {
    "gpt2": (gpt, gpt2_cfg, 1, "data", 18, None),
    "gpt2_fsdp4": (gpt, gpt2_cfg, 4, "fsdp", 32, None),
    "mistral": (llama, mistral_cfg, 1, "data", 1, 1),
    "olmoe": (llama, olmoe_cfg, 1, "data", 4, None),
    "olmoe_fsdp4": (llama, olmoe_cfg, 4, "fsdp", 8, None),
    "granite": (granite_hybrid, granite_cfg, 1, "data", 1, 1),
    "ouro": (ouro, ouro_cfg, 1, "data", 1, 1),
    "kimi": (kimi_linear, kimi_cfg, 1, "data", 1, 1),
    "mellum": (mellum, mellum_cfg, 1, "data", 1, 1),
    "deepseek": (deepseek_v2, deepseek_cfg, 1, "data", 1, 1),
}


def lower_step(name, topo, attn_fn=None):
    """The step ``STEPS`` names, lowered for the described ``topo``."""
    model, cfg, chips, axis, rows, accum = STEPS[name]
    return lower_train_step(
        model, cfg(), list(topo.devices)[:chips], axis, rows, accum, attn_fn
    )


def train_step(*args, **kwargs):
    return lower_train_step(*args, **kwargs).compile()


def elastic_trainer_step(model, cfg, topo):
    """``ElasticTrainer``'s accumulate-then-update step for ``model``
    at ``cfg``, 1 x ``block_size`` tokens, compiled for one described
    chip."""
    return train_step(model, cfg, topo.devices[:1], "data", 1, accum=1)


def gpt2_step(devices, axis, global_batch, accum=None):
    return train_step(gpt, gpt2_cfg(), devices, axis, global_batch, accum)


def olmoe_step(devices, axis, global_batch):
    return train_step(llama, olmoe_cfg(), devices, axis, global_batch)


# -- reading a compiled step ----------------------------------------------


def assert_fits_with_flash(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    )


def step_gb(compiled):
    """What the benchmark's ``step_hbm_gb.train`` reads."""
    mem = compiled.memory_analysis()
    return (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    ) / 1e9


def by_computation(text):
    """(computation's name, line) for every line of an HLO text."""
    current = ""
    for line in text.splitlines():
        if line.endswith("{") and line[:1] in "%E":
            current = line.split()[1 if line.startswith("ENTRY") else 0]
        yield current, line


def computations_calling(compiled, kernel):
    """Names of the HLO computations that hold a custom call of the
    Pallas kernel ``kernel`` (the forward layer scan's body, the
    backward scan's body, ...)."""
    return [
        current for current, line in by_computation(compiled.as_text())
        if "tpu_custom_call" in line and f"/{kernel}/" in line
    ]


def assert_flash_forward_runs_once(compiled, times=1, in_line=0):
    """remat=True keeps the flash forward's (o, lse): one forward
    call a layer scan (``times`` of them in the step), in the forward
    scan's body, and none beside the backward kernel in a backward
    scan's body; of ``in_line`` layers outside any scan
    (models/layers.py) one forward and one backward call each, in the
    computation that holds the scans."""
    fwd = computations_calling(compiled, "flash_attention_fwd")
    bwd = computations_calling(compiled, "flash_attention_bwd")
    assert len(fwd) == len(bwd) == times + in_line, (fwd, bwd)
    for calls in (fwd, bwd):
        bodies = [c for c in calls if calls.count(c) == 1]
        assert len(bodies) == times, calls
        assert len(set(calls)) == times + (in_line > 0), calls
    assert len(set(fwd) & set(bwd)) == (in_line > 0)


def whole_array_passes(text, elements):
    """(``copy`` instructions, fusions with no ``op_name``) whose
    result has ``elements`` or more, among the instructions the chip
    runs one by one: those of every computation that is not a
    fusion's body."""
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.-]+)", text))
    copies, unnamed = [], []
    for current, line in by_computation(text):
        found = re.search(r"= \w+\[([0-9,]+)\]\S* (copy|fusion)\(", line)
        if current.lstrip("%") in fused or found is None:
            continue
        if math.prod(map(int, found.group(1).split(","))) < elements:
            continue
        if found.group(2) == "copy":
            copies.append(line)
        elif "op_name=" not in line:
            unnamed.append(line)
    return copies, unnamed



def attn_relayouts(text, elements):
    """The instructions under the ``attn`` scope, among those the chip
    runs one by one, that move an array of ``elements`` or more
    without computing on it: a ``copy``, or one whose ``op_name`` ends
    in ``transpose``, ``broadcast_in_dim`` (k and v repeated to the
    query heads), ``concatenate`` or ``slice`` (a rotation's halves).
    A step whose attention operands keep one layout has none."""
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.-]+)", text))
    found = []
    for current, line in by_computation(text):
        head = re.search(r"= \(?\w+\[([0-9,]+)\]\S* ([\w-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if current.lstrip("%") in fused or head is None or name is None:
            continue
        if head.group(2) in ("get-tuple-element", "bitcast", "tuple"):
            continue  # no instruction of the chip's
        if "/attn/" not in name.group(1):
            continue
        if math.prod(map(int, head.group(1).split(","))) < elements:
            continue
        tail = name.group(1).rsplit("/", 1)[-1]
        if head.group(2) == "copy" or tail in (
            "transpose", "broadcast_in_dim", "concatenate", "slice"
        ):
            found.append(line)
    return found
