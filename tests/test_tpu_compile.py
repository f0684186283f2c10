"""Ask the chip's compiler, without the chip.

libtpu is installed in the sandbox and compiles for a TPU that is
described, not attached (``v5e:2x2``). Nothing runs, so nothing here
says a result is right or fast — but the compiler refuses exactly what
it would refuse on the chip: a kernel over its VMEM budget, a tile
below the sublane floor, a Mosaic call GSPMD cannot partition, a
program that does not fit 16 GB. Interpret-mode tests see none of
that.

All of it lives in this one file and describes the topology inside a
fixture: only one process may load libtpu, and under pytest-xdist only
the worker that is handed this file does.
"""

import dataclasses
import functools
import math
import re
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.models import (
    deepseek_v2, gpt, granite_hybrid, kimi_linear, llama, mellum, moe, ouro,
)
from dlrover_tpu.ops import causal_conv, grouped_matmul, rows_sum
from dlrover_tpu.ops import kda as kda_ops
from dlrover_tpu.ops import ssd as ssd_ops
from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_rect,
)
from dlrover_tpu.ops.quantization import (
    quantize_blockwise,
    quantize_blockwise_4bit,
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


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any reason is a skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one; keep it out.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Entry points without an ``interpret`` argument ask
    ``use_interpret()``, which sees this process's CPU backend; here
    the answer is the chip's. ``dlrover_tpu.ops.flash_attention`` the
    attribute is the re-exported function, so go through sys.modules."""
    for name in ("flash_attention", "quantization", "grouped_matmul",
                 "ssd", "causal_conv", "kda", "rows_sum"):
        monkeypatch.setattr(
            sys.modules[f"dlrover_tpu.ops.{name}"],
            "use_interpret",
            lambda: False,
        )


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _bf16(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)


def _flash_grad(window=None):
    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


# (B, T, H, D, window). The first two are the main path's shapes; from
# 8k up the backward needs more than the default scoped VMEM and must
# say so (ops/flash_attention._bwd_vmem_limit) — the parent commit
# fails every one of those.
FLASH_CASES = [
    (18, 1024, 12, 64, None),
    (2, 4096, 32, 128, None),
    (2, 4096, 32, 128, 1024),
    (1, 8192, 8, 128, None),
    (1, 8192, 8, 128, 1024),
    (1, 16384, 4, 64, None),
    (1, 32768, 2, 128, None),
]


@pytest.mark.parametrize("b,t,h,d,window", FLASH_CASES)
def test_flash_fwd_bwd_compiles(one_chip, b, t, h, d, window):
    x = _bf16(one_chip, b, t, h, d)
    text = _compile(_flash_grad(window), x, x, x).as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and backward


def test_flash_rect_compiles(one_chip):
    """Tq=512 queries against Tk=4096 keys (chunked prefill)."""
    q = _bf16(one_chip, 2, 512, 32, 128)
    kv = _bf16(one_chip, 2, 4096, 32, 128)

    def loss(q, k, v):
        out = flash_attention_rect(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "quantize", [quantize_blockwise, quantize_blockwise_4bit]
)
def test_blockwise_quantize_compiles(one_chip, compiled_kernels, quantize):
    x = jax.ShapeDtypeStruct((4096, 512), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda x: quantize(x)[:2], x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["flash", "prefix_lm"])
def test_kernels_split_themselves_over_a_mesh(topo, compiled_kernels, kernel):
    """Any caller on any mesh: traced under the mesh a Pallas kernel
    puts itself in a shard_map over batch rows (and heads), so XLA is
    never asked to partition a Mosaic call — the model's flash choice
    and GLM's prefix-LM attention alike. data=2 x
    tensor=2: the batch splits over one axis, the heads over the
    other."""
    from dlrover_tpu.ops.prefix_lm import prefix_lm_attention
    from dlrover_tpu.parallel.mesh import under_mesh

    mesh = build_mesh(
        MeshConfig(data=2, tensor=2), devices=list(topo.devices)
    )
    qkv = _bf16(
        NamedSharding(mesh, P("data", None, "tensor", None)),
        4, 2048, 8, 128,
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def prefix_lm(q, k, v):
        return prefix_lm_attention(q, k, v, prefix_len=512)

    fn = {"flash": flash, "prefix_lm": prefix_lm}[kernel]

    def loss(*args):
        return fn(*args).astype(jnp.float32).sum()

    grad = jax.grad(under_mesh(loss, mesh), argnums=(0, 1, 2))
    text = _compile(grad, qkv, qkv, qkv).as_text()
    assert "tpu_custom_call" in text
    # Each device runs its own rows and heads: the kernel sees a
    # (2, 2048, 4, 128) block, and nothing crosses the mesh.
    assert "all-gather" not in text and "all-reduce" not in text


def test_head_keeps_the_logits_on_their_chip(topo):
    """The loss head of ``mistral-7b-host4.fsdp4`` at its real size
    (4 x 8192 rows, E 4096, V 32000, bf16, the table's embed dim on
    fsdp=4), compiled by the chip's partitioner: no collective on a
    ``[rows, 32000]`` array. Left to XLA it all-reduced
    ``f32[4096,32000]`` eight times a pass, twice a step (73.5 ms
    each on the chip, PERF.md PR 27)."""
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy
    from dlrover_tpu.parallel.mesh import under_mesh

    mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
    x = _bf16(NamedSharding(mesh, P("fsdp", None)), 32768, 4096)
    table = _bf16(NamedSharding(mesh, P(None, "fsdp")), 32000, 4096)
    targets = jax.ShapeDtypeStruct(
        (32768,), jnp.int32, sharding=NamedSharding(mesh, P("fsdp"))
    )
    grad = jax.value_and_grad(
        under_mesh(fused_cross_entropy, mesh), argnums=(0, 1)
    )
    text = _compile(grad, x, table, targets).as_text()
    collectives = [
        line for line in text.splitlines()
        if " all-reduce(" in line or " all-gather(" in line
        or " reduce-scatter(" in line or " all-to-all(" in line
    ]
    assert collectives  # the table is gathered, its gradient summed
    assert not [c for c in collectives if ",32000]" in c], collectives


@pytest.mark.parametrize("form", [
    "gate_up", "down", "input_grad", "weight_grad",
])
def test_grouped_matmul_compiles_at_olmoe_widths(one_chip, form):
    """The expert layer's products at the benchmark cell's shape:
    131,072 (token, choice) rows in 64 groups, 2048 x 1024. One
    expert's whole matrix and a row tile sit in VMEM, over the default
    budget, so each kernel declares its ``vmem_limit_bytes``."""
    rows, e, w, experts = 131072, 2048, 1024, 64
    sizes = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip)
    wide, narrow = _bf16(one_chip, rows, e), _bf16(one_chip, rows, w)
    if form == "gate_up":
        fn = functools.partial(grouped_matmul.moe_gmm, interpret=False)
        args = (wide, _bf16(one_chip, experts, e, w), sizes)
    elif form == "down":
        fn = functools.partial(grouped_matmul.moe_gmm, interpret=False)
        args = (narrow, _bf16(one_chip, experts, w, e), sizes)
    elif form == "input_grad":
        fn = functools.partial(
            grouped_matmul.moe_gmm, transpose_rhs=True, interpret=False
        )
        args = (narrow, _bf16(one_chip, experts, e, w), sizes)
    else:
        fn = functools.partial(grouped_matmul.moe_tgmm, interpret=False)
        args = (wide, narrow, sizes)
    text = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in text
    assert ("moe_tgmm" if form == "weight_grad" else "moe_gmm") in text


def _gpt2_step(devices, axis, global_batch, accum=None):
    """The step for GPT-2 124M as chip_smoke.py trains it (full
    remat, fused cross-entropy, adamw, flash attention) lowered and
    compiled for ``devices`` laid out along ``axis``:
    ``make_train_step``'s, or with ``accum`` the trainer's own."""
    cfg = dataclasses.replace(
        gpt.GPTConfig.gpt2(), use_flash_attention=True
    )
    return _train_step(gpt, cfg, devices, axis, global_batch, accum)


def _train_step(model, cfg, devices, axis, global_batch, accum=None):
    """``make_train_step``'s program, or with ``accum`` the trainer's
    own (``ElasticTrainer._build_step``: ``accum`` microbatches of
    ``global_batch`` rows through its ``lax.scan``)."""
    mesh = build_mesh(MeshConfig(**{axis: len(devices)}), devices=devices)
    optimizer = optax.adamw(6e-4)
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
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
    ).compile()


def _assert_fits_with_flash(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    )


def _step_gb(compiled):
    """What the benchmark's ``step_hbm_gb.train`` reads."""
    mem = compiled.memory_analysis()
    return (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    ) / 1e9


def _by_computation(text):
    """(computation's name, line) for every line of an HLO text."""
    current = ""
    for line in text.splitlines():
        if line.endswith("{") and line[:1] in "%E":
            current = line.split()[1 if line.startswith("ENTRY") else 0]
        yield current, line


def _computations_calling(compiled, kernel):
    """Names of the HLO computations that hold a custom call of the
    Pallas kernel ``kernel`` (the forward layer scan's body, the
    backward scan's body, ...)."""
    return [
        current for current, line in _by_computation(compiled.as_text())
        if "tpu_custom_call" in line and f"/{kernel}/" in line
    ]


def _assert_flash_forward_runs_once(compiled, times=1, in_line=0):
    """remat=True keeps the flash forward's (o, lse): one forward
    call a layer scan (``times`` of them in the step), in the forward
    scan's body, and none beside the backward kernel in a backward
    scan's body; of ``in_line`` layers outside any scan
    (models/layers.py) one forward and one backward call each, in the
    computation that holds the scans."""
    fwd = _computations_calling(compiled, "flash_attention_fwd")
    bwd = _computations_calling(compiled, "flash_attention_bwd")
    assert len(fwd) == len(bwd) == times + in_line, (fwd, bwd)
    for calls in (fwd, bwd):
        bodies = [c for c in calls if calls.count(c) == 1]
        assert len(bodies) == times, calls
        assert len(set(calls)) == times + (in_line > 0), calls
    assert len(set(fwd) & set(bwd)) == (in_line > 0)


def test_gpt2_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program chip_smoke.py's trainer runs: batch 18 x 1024.
    The flash forward runs once a layer, and from its result on the
    kernels' row statistics are ``f32[18,12,1,1024]`` rows (0.9 MB):
    the backward kernel takes no ``[B, H, T, 1]`` column, which the
    chip pads to 113 MB and XLA spent two copies a layer on.

    The first three layers run in line and nine are scanned
    (models/layers.py): what the scan keeps is stacked
    ``[9, 18, 1024, ...]`` and sliced out again by the backward loop,
    and no array of the step is ``[12, 18, ...]`` or ``[3, 18, ...]``:
    an in-line layer's kept values are never stacked. The bound is
    the all-scanned stack's own reading, 6.4555 GB compiled here
    (6.7502 with the columns, PR 33's tree: 6.7507 on the chip), and
    the step reads 6.2219, under it; with the LAST three in line it
    read 7.7969, the in-line layers' recomputed values held across the
    backward loop for weight gradients whose user comes after it
    (PERF.md, PR 51)."""
    compiled = _gpt2_step(topo.devices[:1], "data", 18)
    _assert_fits_with_flash(compiled)
    _assert_flash_forward_runs_once(compiled, in_line=3)
    text = compiled.as_text()
    assert "bf16[9,18,1024,3072]" in text  # the scan's kept MLP product
    assert "[12,18," not in text and "[3,18," not in text
    kept_reads = [
        line for line in text.splitlines()
        if " dynamic-slice(" in line and "transpose(jvp(layers))" in line
        and "[1,18," in line.split(" dynamic-slice(")[0]
    ]
    # Every read of a kept value by a layer's index is the backward
    # loop's, of the scan's nine layers' stacks above.
    assert kept_reads and all(
        "/while/body/dynamic_slice" in line for line in kept_reads
    )
    bwd_calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "/flash_attention_bwd/" in line
    ]
    assert len(bwd_calls) == 4
    for bwd in bwd_calls:
        operands = bwd[
            bwd.index("custom-call("):bwd.index("custom_call_target")
        ]
        assert "%" in operands and "f32[18,12,1024,1]" not in bwd
    assert _step_gb(compiled) < 6.4555 + 0.05


@pytest.mark.parametrize("axis", ["data", "fsdp"])
def test_gpt2_train_step_compiles_on_four_chips(
    topo, compiled_kernels, axis
):
    """The program chip_smoke.py --chips 4 runs: global batch 32. The
    parent commit fails it ("Mosaic kernels cannot be automatically
    partitioned"); traced under the mesh (parallel.mesh.under_mesh)
    the flash call now puts itself in a shard_map over batch and
    heads (ops.flash_attention.per_device)."""
    compiled = _gpt2_step(list(topo.devices), axis, 32)
    _assert_fits_with_flash(compiled)
    # It is one program across the mesh, not four copies of one.
    assert "all-reduce" in compiled.as_text()
    # The kept (o, lse) are tagged inside the kernel's shard_map.
    _assert_flash_forward_runs_once(compiled, in_line=3)
    if axis == "fsdp":  # PR 29's tree: 2.8190 GB a chip
        assert _step_gb(compiled) < 2.8190 + 0.05


def test_trainer_step_accumulates_on_one_chip(topo, compiled_kernels):
    """The trainer's own step (``ElasticTrainer._build_step``) for
    GPT-2 124M at two microbatches of 18 x 1024, the program ROADMAP
    R2's cell will run: one program, the microbatch scan a loop in it
    with the flash kernels once a layer scan inside, within the
    chip's memory (7.4554 GB compiled with every layer scanned, the
    bound here: the float32 accumulator and a second staged
    microbatch over the plain step's 6.4555; 7.2899 with the first
    three layers in line)."""
    compiled = _gpt2_step(topo.devices[:1], "data", 18, accum=2)
    _assert_fits_with_flash(compiled)
    _assert_flash_forward_runs_once(compiled, in_line=3)
    text = compiled.as_text()
    assert "/accumulate/" in text and "/optimizer/" in text
    assert "all-reduce" not in text  # one chip: nothing to reduce
    assert _step_gb(compiled) < 7.4554 + 0.05


def test_trainer_step_on_data4_leaves_the_reduction_to_xla(
    topo, compiled_kernels
):
    """Pure data parallel on four chips, the mesh the deleted
    overlapped reduction was for: the trainer's step is one program in
    which XLA's own collectives form the gradients' mean over the
    shards, and the parameters, replicated, are gathered by nobody."""
    compiled = _gpt2_step(list(topo.devices), "data", 32, accum=2)
    _assert_fits_with_flash(compiled)
    text = compiled.as_text()
    assert " all-reduce(" in text or " reduce-scatter(" in text
    # A weight's shape: a leaf's own, or one layer's slice of a
    # stacked leaf as a layer scan's body sees it (what fsdp=4
    # gathers: bf16[1,768,3072], bf16[768,3072], bf16[50304,768]).
    weights = set()
    for leaf in jax.tree.leaves(jax.eval_shape(
        functools.partial(gpt.init_params, cfg=gpt.GPTConfig.gpt2()),
        jax.random.PRNGKey(0),
    )):
        weights |= {leaf.shape, leaf.shape[1:], (1,) + leaf.shape[1:]}
    names = {
        "[" + ",".join(map(str, shape)) + "]"
        for shape in weights if shape not in ((), (1,))
    }
    gathered = [
        line for line in text.splitlines()
        if " all-gather(" in line
        and any(n in line.split(" all-gather(")[0] for n in names)
    ]
    assert not gathered, gathered


def test_mistral_block_keeps_flash_outputs_on_four_chips(
    topo, compiled_kernels
):
    """The Llama block at Mistral-7B's widths (grouped queries, window
    4096, T = 8192) on ``fsdp=4``, two layers of the eight of the
    benchmark's ``mistral-7b-host4.fsdp4``, which has no room for an
    ``o`` kept beside the out-projection's output (0.067 GB a layer a
    chip): kept in its place, the step takes what PR 29's tree took
    (7.2069 GB a chip compiled here)."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.mistral_7b(), n_layer=2, block_size=8192,
        use_flash_attention=True,
    )
    compiled = _train_step(llama, cfg, list(topo.devices), "fsdp", 4)
    _assert_fits_with_flash(compiled)
    _assert_flash_forward_runs_once(compiled, times=0, in_line=2)
    assert _step_gb(compiled) < 7.2069 + 0.05


def test_mistral_train_step_has_no_layer_scan_on_one_chip(
    topo, compiled_kernels
):
    """The program of the benchmark's ``mistral-7b.steady``: two
    layers at published widths, 1 x 8192 tokens, window 4096, full
    remat, ``ElasticTrainer``'s step. Both layers run in line
    (models/layers.py): no loop and no ``dynamic-slice`` stands under
    the ``layers`` scope, nothing is stacked ``[2, 1, 8192, ...]``,
    the flash kernels are called once a layer. 10.8164 GB compiled
    here, for the 14.4058 of the two-layer scan
    (``peak_memory_in_bytes`` 10.33: ``temp_size_in_bytes``, which the
    reading sums, counts a scan's stacks above the step's peak)."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.mistral_7b(), n_layer=2, block_size=8192,
        use_flash_attention=True,
    )
    compiled = _elastic_trainer_step(llama, cfg, topo)
    _assert_fits_with_flash(compiled)
    _assert_flash_forward_runs_once(compiled, times=0, in_line=2)
    # What the ``layers`` scope holds, from the scope's name on (the
    # microbatch loop stands before it in every op_name).
    under_layers = [
        line[line.index("layers)"):]
        for line in compiled.as_text().splitlines() if "layers)" in line
    ]
    assert under_layers
    assert not [
        line for line in under_layers
        if "/while/" in line or "dynamic_slice" in line
    ]
    assert "[2,1,8192," not in compiled.as_text()
    assert _step_gb(compiled) < 14.4058 * 1.02


def _olmoe_step(devices, axis, global_batch):
    cfg = dataclasses.replace(
        llama.LlamaConfig.olmoe_1b_7b(), n_layer=1,
        use_flash_attention=True,
    )
    return _train_step(llama, cfg, devices, axis, global_batch)


def test_olmoe_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``olmoe-1b-7b.steady``: one
    OLMoE layer at published widths, 4 x 4096 tokens: sorted routing,
    the grouped-product kernels forward and backward under full remat
    inside the layer scan, flash attention at head size 128. The step
    holds the grouped products a gated expert layer needs and no
    more, three forward, three input gradients, three weight
    gradients (nine ``moe_gmm`` before the layer named what its
    backward takes). 10.2454 GB compiled here, 10.3990 with the
    layer's forward run twice: at one layer the kept values are live
    in the backward either way."""
    compiled = _olmoe_step(topo.devices[:1], "data", 4)
    _assert_fits_with_flash(compiled)
    assert len(_computations_calling(compiled, "moe_gmm")) == 6
    assert len(_computations_calling(compiled, "moe_tgmm")) == 3
    # No kept [131072, .] value is rounded in a pass of its own.
    assert not [
        line for line in compiled.as_text().splitlines()
        if " reduce-precision(" in line and "= bf16[131072," in line
    ]
    assert _step_gb(compiled) < 10.2454 + 0.05


def test_olmoe_train_step_compiles_on_four_chips(topo, compiled_kernels):
    """Tokens over ``fsdp=4``: each chip sorts its own tokens inside
    the kernels' shard_map, the expert weights are gathered whole and
    their gradients reduced over the mesh."""
    compiled = _olmoe_step(list(topo.devices), "fsdp", 8)
    _assert_fits_with_flash(compiled)
    text = compiled.as_text()
    assert "moe_gmm" in text and "all-gather" in text


# -- the chunked state-space scan and the hybrid stack that runs it ------


def _ssd_operands(sharding, bsz, rows_sharding=None):
    """ops/ssd.py's operands at Granite 4.0-H's widths: 64 heads of
    64, state 128, one B/C group, 4096 tokens, bf16 with float32
    steps and per-head scalars."""
    rows = rows_sharding or sharding
    f32 = lambda shape, s: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=s)
    return (
        _bf16(rows, bsz, 4096, 4096), f32((bsz, 4096, 64), rows),
        f32((64,), sharding), _bf16(rows, bsz, 4096, 1, 128),
        _bf16(rows, bsz, 4096, 1, 128), f32((64,), sharding),
    )


def _ssd_grad(*args):
    return jax.grad(
        lambda *a: ssd_ops.ssd(*a, chunk=256).astype(jnp.float32).sum(),
        argnums=range(6),
    )(*args)


def test_ssd_fwd_bwd_compiles_at_granite_widths(one_chip, compiled_kernels):
    """Blocks of 8 heads of 64 (lane offsets of 64 inside a 512-lane
    block), a head a column of a lane-sparse block, every head's state
    in VMEM scratch along the sequential chunk axis, the declared
    ``vmem_limit_bytes``: Mosaic takes both kernels."""
    text = _compile(_ssd_grad, *_ssd_operands(one_chip, 1)).as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert text.count("tpu_custom_call") >= 2


def test_ssd_splits_itself_over_a_mesh(topo, compiled_kernels):
    """Under fsdp=4 each chip scans its own batch row; the per-head
    parameters' gradients are summed over the mesh."""
    from dlrover_tpu.parallel.mesh import under_mesh

    mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
    args = _ssd_operands(
        NamedSharding(mesh, P()), 4, NamedSharding(mesh, P("fsdp"))
    )
    text = _compile(under_mesh(_ssd_grad, mesh), *args).as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "bf16[1,4096,4096]" in text  # a chip's own row
    assert "all-reduce" in text and "all-gather" not in text


def _conv_operands(sharding, bsz, rows_sharding=None):
    """A Granite mixer's projection ``[z | xBC | dt]`` (4096 + 4352 +
    64 columns, 4096 tokens) with the convolution's weights, bf16."""
    return (
        _bf16(rows_sharding or sharding, bsz, 4096, 8512),
        _bf16(sharding, 4, 4352), _bf16(sharding, 4352),
    )


def _conv_grad(proj, w, bias):
    """The two calls of ``models/granite_hybrid.mamba_mixer``: x's
    columns of the projection, then B|C's."""
    def loss(proj, w, bias):
        x = causal_conv.conv_silu(
            proj, w[:, :4096], bias[:4096], start=4096
        )
        bc = causal_conv.conv_silu(
            proj, w[:, 4096:], bias[4096:], start=8192
        )
        return x.astype(jnp.float32).sum() + bc.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(proj, w, bias)


def _conv_calls(text):
    return [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "conv_silu_" in line
    ]


def test_conv_silu_compiles_at_granite_widths(one_chip, compiled_kernels):
    """Row rotations along the sublanes, a tile with its neighbours in
    float32 scratch, dynamic row offsets in the chunk loops: Mosaic
    takes both kernels, and both read the projection where it lies
    (the custom calls' operand is the [1, 4096, 8512] array, no copy
    of its columns)."""
    calls = _conv_calls(
        _compile(_conv_grad, *_conv_operands(one_chip, 1)).as_text()
    )
    # The forward of a gradient alone is dead code: two backward calls.
    assert len(calls) == 2 and all("conv_silu_bwd" in c for c in calls)
    assert all(
        "operand_layout_constraints={bf16[1,4096,8512]" in c for c in calls
    )


def test_conv_silu_splits_itself_over_a_mesh(topo, compiled_kernels):
    """Under fsdp=4 each chip convolves its own batch row; the
    weights' gradients are summed over the mesh."""
    from dlrover_tpu.parallel.mesh import under_mesh

    mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
    args = _conv_operands(
        NamedSharding(mesh, P()), 4, NamedSharding(mesh, P("fsdp"))
    )
    text = _compile(under_mesh(_conv_grad, mesh), *args).as_text()
    assert len(_conv_calls(text)) == 2
    assert "bf16[1,4096,8512]" in text  # a chip's own row
    assert "all-reduce" in text and "all-gather" not in text


def _elastic_trainer_step(model, cfg, topo):
    """``ElasticTrainer``'s accumulate-then-update step for ``model``
    at ``cfg``, 1 x ``block_size`` tokens, lowered and compiled for one
    described chip, as benchmark/trainer_loop.py runs a steady cell."""
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    mesh = build_mesh(MeshConfig(data=1), devices=topo.devices[:1])
    optimizer = optax.adamw(6e-4)
    trainer = ElasticTrainer(
        mesh, functools.partial(model.loss_fn_fused, cfg=cfg), optimizer,
        global_batch_size=1, micro_batch_size=1,
    )
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
        (1, 1, cfg.block_size), jnp.int32,
        sharding=NamedSharding(mesh, trainer._mb_spec),
    )
    return trainer._compiled.lower(
        with_shardings(param_shapes, param_shardings),
        with_shardings(opt_shapes, opt_shardings), tokens, tokens,
    ).compile()


def test_granite_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``granite-4.0-h-micro.steady``:
    one period of the published pattern (5 Mamba-2, attention, 4
    Mamba-2) at published widths with a quarter of the tied table,
    1 x 4096 tokens, ``ElasticTrainer``'s accumulate-then-update step
    (the float32 gradient accumulator is a quarter of the arguments'
    weight). It fits; ``ssd_fwd`` is in the two forward scan bodies
    and not beside ``ssd_bwd`` (the scan's output and chunk states
    are kept); the flash forward runs once. 15.589 GB compiled here,
    15.589 on the chip (PERF.md, PR 34)."""
    model = granite_hybrid
    cfg = dataclasses.replace(
        model.GraniteHybridConfig(
            vocab_size=25088, layer_types=model.GraniteHybridConfig().period,
            remat="full",
        ),
        use_flash_attention=True,
    )
    compiled = _elastic_trainer_step(model, cfg, topo)
    _assert_fits_with_flash(compiled)
    # One attention layer, outside the layer scans: one call each.
    assert len(_computations_calling(compiled, "flash_attention_fwd")) == 1
    assert len(_computations_calling(compiled, "flash_attention_bwd")) == 1
    fwd = _computations_calling(compiled, "ssd_fwd")
    bwd = _computations_calling(compiled, "ssd_bwd")
    assert len(fwd) == 2 and len(bwd) == 2 and not set(fwd) & set(bwd), (
        fwd, bwd
    )
    # The mixer's convolution is recomputed (its kernel stands in the
    # backward bodies too) and differentiated by its own kernel there.
    conv_fwd = _computations_calling(compiled, "conv_silu_fwd")
    conv_bwd = _computations_calling(compiled, "conv_silu_bwd")
    assert set(conv_fwd) == set(fwd) | set(bwd), (conv_fwd, fwd, bwd)
    assert set(conv_bwd) == set(bwd), (conv_bwd, bwd)
    mem = compiled.memory_analysis()
    # 15.285 GB since PR 52 (15.589 before: the float32 copies of xBC
    # the plain convolution's backward held are gone).
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
    ) / 1e9 < 15.285 + 0.05


def test_ouro_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``ouro-2.6b.steady``: 8 of 48
    layers at published widths run 4 times on the same weights, a
    sixth of both tables, 1 x 4096 tokens, full remat. It compiles; the
    passes are ``ut_steps`` layer scans in a row, so the flash forward
    stands in ``ut_steps`` forward bodies and the backward in as many
    backward bodies, the forward not run again beside the backward; no
    array is stacked ``[ut_steps, n_layer, ...]``; the loss head's
    three products over the 16,384 stacked rows. What it reads here
    and on the chip: PERF.md section 6, PRs 44 and 45."""
    cfg = ouro.OuroConfig(
        n_layer=8, vocab_size=8192, jitter=0.1, use_flash_attention=True,
    )
    compiled = _elastic_trainer_step(ouro, cfg, topo)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_flash_forward_runs_once(compiled, times=cfg.ut_steps)
    assert f"[{cfg.ut_steps},{cfg.n_layer}," not in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 17.194 GB here (17.586 with the layers' scan inside a scan over
    # the passes), over the 16.909 GB a program gets on the chip, where
    # the step runs: ``memory_analysis()`` still over-reads a step whose
    # buffer assignment totals 11.694 GB (14.208 nested; PERF.md 7(j)).
    # A reading that moves says the layer scans keep more or less.
    assert 16.7e9 < total < 17.4e9, total


def _kda_operands(sharding, bsz, t, heads=32, rows_sharding=None,
                  dtype=jnp.bfloat16):
    """ops/kda.py's operands at Kimi Linear's widths: heads of 128,
    bf16 with float32 log decays and beta."""
    rows = rows_sharding or sharding
    shaped = lambda dt, *shape: jax.ShapeDtypeStruct(shape, dt, sharding=rows)
    wide = shaped(dtype, bsz, t, heads, 128)
    return (
        wide, wide, wide, shaped(jnp.float32, bsz, t, heads, 128),
        shaped(jnp.float32, bsz, t, heads),
    )


def _kda_grad(*args):
    return jax.grad(
        lambda *a: kda_ops.kda(*a).astype(jnp.float32).sum(),
        argnums=range(5),
    )(*args)


@pytest.mark.parametrize("bsz,t,heads,dtype", [
    (1, 8192, 32, jnp.bfloat16), (128, 64, 32, jnp.bfloat16),
    (1, 512, 4, jnp.float32),
])
def test_kda_fwd_bwd_compiles_at_kimi_widths(
    one_chip, compiled_kernels, bsz, t, heads, dtype
):
    """Blocks of 8 heads of 128 read where the operands lie, a head
    a dynamic slice of whole lanes in a rolled loop, beta a head a
    row, every head's state in VMEM scratch along the sequential
    chunk axis: Mosaic takes both kernels, at the cell's one sequence
    of 128 chunks, at the ``no_carry`` control's 128 sequences of one
    chunk and, in float32 under ``default_matmul_precision("highest")``,
    at tools/tpu_kernel_smoke.py's shape (the exact sums' bf16 pieces
    are pinned to one pass: Mosaic refuses "highest" of bf16 operands,
    which the chip's smoke met first)."""
    operands = _kda_operands(one_chip, bsz, t, heads, dtype=dtype)
    with jax.default_matmul_precision(
        "highest" if dtype == jnp.float32 else "default"
    ):
        text = _compile(_kda_grad, *operands).as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    assert "kda_fwd" in text and "kda_bwd" in text


def test_kda_splits_itself_over_a_mesh(topo, compiled_kernels):
    """Under fsdp=4 each chip runs the rule on its own batch row."""
    from dlrover_tpu.parallel.mesh import under_mesh

    mesh = build_mesh(MeshConfig(fsdp=4), devices=list(topo.devices))
    args = _kda_operands(
        NamedSharding(mesh, P()), 4, 512, heads=8,
        rows_sharding=NamedSharding(mesh, P("fsdp")),
    )
    text = _compile(under_mesh(_kda_grad, mesh), *args).as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    assert "bf16[1,512,1024]" in text  # a chip's own row
    assert "all-gather" not in text


def _whole_array_passes(text, elements):
    """(``copy`` instructions, fusions with no ``op_name``) whose
    result has ``elements`` or more, among the instructions the chip
    runs one by one: those of every computation that is not a
    fusion's body."""
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.-]+)", text))
    copies, unnamed = [], []
    for current, line in _by_computation(text):
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


@pytest.mark.parametrize(
    "n,top_k,held,cap,d",
    [(8192, 8, 8, 8192, 2304), (8192, 8, 16, 32768, 2304),
     (8192, 6, 8, 16384, 2048)],
    ids=["kimi", "mellum", "deepseek"],
)
@pytest.mark.parametrize("weighted", [True, False], ids=["combine", "bwd"])
def test_moe_rows_sum_compiles_at_the_held_widths(
    one_chip, compiled_kernels, n, top_k, held, cap, d, weighted
):
    """The held path's rows summed back by token at the three cells'
    shapes, from the plan the layer forms (``_held_order``,
    ``_block_plan``): the forward's form, bf16 rows with float32
    weights, and the backward's, the rows alone. One custom call,
    tiles of 256 tokens by chunks of 128 rows, and nothing
    buffer-sized beside it: no float32 copy of the rows, no sort of
    them."""
    def fn(local, rows, weights):
        whole = moe._held_order(local, held)
        plan = moe._block_plan(whole, 0, n, top_k, cap)
        weight = moe._row_weights(weights, plan) if weighted else None
        return moe._tokens_of_rows(rows, weight, plan, n)

    one = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    text = _compile(
        fn, one((n, top_k), jnp.int32), _bf16(one_chip, cap, d),
        one((n, top_k), jnp.float32),
    ).as_text()
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_rows_sum', text
    )) == 1
    assert rows_sum.layout(n, cap, held)["tile"] == 256
    assert cap == n or f"f32[{cap},{d}]" not in text
    assert not re.search(rf"= [^\n]*\[{cap}\][^\n]* sort\(", text)


def test_mellum_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``mellum2-12b-a2.5b.steady``: one
    period (three window-1024 layers, one full with YaRN) at published
    widths, 32/4 heads of 128 on a hidden size of 2304, 16 of 64
    experts held, a quarter of both tables, 1 x 8192 tokens, full
    remat, as ONE program. It fits; the flash kernels are compiled for
    both masks (a banded and a plain causal call of each), and the
    forward runs once a layer: four calls, not eight. Each expert
    layer's buffer is two blocks of 32,768 rows (``rows_cap`` at 16 of
    64 held, 8 a token: four held choices a token), the second behind
    the held path's ``lax.cond``, so the scan over the blocks is a
    loop in the program and no longer folds away as the one block of
    65,536 rows did: the grouped kernels stand in its body, and
    ``memory_analysis()`` reads 9.63 GB for that program's 9.14 (the
    buffer's arrays halve; the loop's carries, the three matrices'
    gradient sums among them, and the block's own copies beside them
    are counted at once: PERF.md section 6, PR 58). Since PR 60 the
    rows are summed back by token by ``moe_rows_sum`` and it reads
    9.68, which is the compiler's heap, 2.66 GiB for the parent's
    2.78, plus the holes in it, 826 MiB for 657:
    ``temp_size_in_bytes`` counts a heap's fragmentation a second
    time, so it rises where a program's live bytes fall faster than
    its heap (PERF.md section 6, PR 60, has the compiler's own
    lines)."""
    cfg = mellum.MellumConfig(
        vocab_size=24576, layer_types=mellum.MellumConfig().period,
        held=16, remat="full", use_flash_attention=True,
    )
    compiled = _elastic_trainer_step(mellum, cfg, topo)
    _assert_fits_with_flash(compiled)
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf'custom_call_target="tpu_custom_call"[^\n]*{name}', text
    ))
    assert calls("flash_attention_fwd") == 4, calls("flash_attention_fwd")
    assert calls("flash_attention_bwd") == 4, calls("flash_attention_bwd")
    assert "moe_gmm" in text and "moe_tgmm" in text
    # The buffer: [32768, 2304] rows through the products, never the
    # layer's 65,536 pairs.
    assert "bf16[32768,2304]" in text and "bf16[65536,2304]" not in text
    # The rows' sum is the kernel's, forward and (the rows' gather's
    # backward) in each layer's backward.
    assert calls("moe_rows_sum") == 8, calls("moe_rows_sum")
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print("mellum step bytes", total, mem)
    assert total / 1e9 < 9.7, total


def test_deepseek_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``deepseek-v2-lite.steady``:
    published layers 0 to 5 (the dense layer, then five expert layers)
    at published widths, latent attention on every one with the shared
    key part rotated, 8 of 64 experts held beside two shared ones, an
    eighth of both tables, 1 x 8192 tokens, full remat, as ONE program.
    It fits; the flash kernels take the two head sizes (192 and 128)
    six times each way, the forward once a layer and not twice; each
    expert layer's buffer is 16,384 rows (``rows_cap`` at 8 of 64
    held, 6 a token: two held choices a token), three blocks of which
    the last two are behind the held path's ``lax.cond``.
    ``memory_analysis()`` reads 9.21 GB since PR 60 (9.86 before, the
    rows' sum in plain ``jax.numpy`` with its float32 copies of the
    buffer): arguments 6.36 (635,466,752 parameters at 10 bytes),
    temporaries 2.85."""
    cfg = deepseek_v2.DeepseekV2Config(
        vocab_size=12800, n_layer=6, held=8, remat="full",
        use_flash_attention=True,
    )
    compiled = _elastic_trainer_step(deepseek_v2, cfg, topo)
    _assert_fits_with_flash(compiled)
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf'custom_call_target="tpu_custom_call"[^\n]*{name}', text
    ))
    assert calls("flash_attention_fwd") == 6, calls("flash_attention_fwd")
    assert calls("flash_attention_bwd") == 6, calls("flash_attention_bwd")
    assert "moe_gmm" in text and "moe_tgmm" in text
    # The buffer: [16384, 2048] rows through the products, never the
    # layer's 49,152 pairs.
    assert "bf16[16384,2048]" in text and "bf16[49152,2048]" not in text
    assert calls("moe_rows_sum") == 10, calls("moe_rows_sum")
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print("deepseek step bytes", total, mem)
    assert total / 1e9 < 9.5, total


def _kimi_cell_cfg():
    model = kimi_linear
    return model.KimiLinearConfig(
        vocab_size=20480,
        mixers=(model.KDA, model.KDA, model.KDA, model.MLA, model.KDA),
        ffns=(model.DENSE,) + (model.MOE,) * 4,
        held=8, remat="full", use_flash_attention=True,
    )


def test_kimi_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``kimi-linear-48b-a3b.steady``:
    published layers 1 to 5 (dense-KDA, KDA, KDA, MLA, KDA with
    experts) at published widths, 8 of 256 experts held, an eighth of
    both tables, 1 x 8192 tokens, full remat. It fits; the flash
    kernels take the latent layer's two head sizes (192 and 128) and
    the forward runs once; each expert layer's grouped products stand
    in the one block of the held path's scan over its blocks of
    ``rows_cap`` sorted rows, in the one step program."""
    compiled = _elastic_trainer_step(kimi_linear, _kimi_cell_cfg(), topo)
    _assert_fits_with_flash(compiled)
    assert len(_computations_calling(compiled, "flash_attention_fwd")) == 1
    assert len(_computations_calling(compiled, "flash_attention_bwd")) == 1
    text = compiled.as_text()
    assert "moe_gmm" in text and "moe_tgmm" in text
    assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
    # The rule's kernels, one call each a KDA layer: the rematerialised
    # layer takes the kept output and chunk states and does not run
    # ``kda_fwd`` again.
    calls = lambda name: len(re.findall(
        rf'custom_call_target="tpu_custom_call"[^\n]*{name}', text
    ))
    assert calls("kda_fwd") == 4 and calls("kda_bwd") == 4, (
        calls("kda_fwd"), calls("kda_bwd")
    )
    # The held path's rows summed by token: forward and, as the
    # backward of the rows' gather, once more, in each expert layer.
    assert calls("moe_rows_sum") == 8, calls("moe_rows_sum")
    # No relayout at the rule's edge: from its convolutions to ``w_o``
    # a KDA mixer stays [B, T, H*d], what ``conv_silu`` writes and the
    # rule's kernels read, and a head's sums are products with the
    # heads' membership. No 4-D layout is a bitcast of that tiling, so
    # every [B, T, H, d] view was a copy of the whole array: 56 ``copy``
    # of a [T, inner] array's elements or more before PR 56 and 31
    # fusions with no ``op_name`` that fed them. The six copies left
    # are the latent layer's, by name; the four fusions a
    # rematerialised KDA layer's ``y`` in bf16, named for nothing
    # because their root is the out-projection's own bitcast.
    copies, unnamed = _whole_array_passes(text, 8192 * 4096)
    assert len(copies) == 6 and all("/attn/mla/" in c for c in copies), (
        copies
    )
    assert len(unnamed) <= 4 and all(
        "= bf16[8192,4096]{1,0" in f for f in unnamed
    ), unnamed
    assert not re.search(r"\[1024,8,32,128\]|f32\[1,8192,32,128\]", text)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print("kimi step bytes", total, mem)
    assert total / 1e9 < 16.9, total
