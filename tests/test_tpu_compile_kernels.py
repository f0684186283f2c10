"""The package's kernels, each alone at the cells' real shapes, through
the chip's compiler without the chip (tests/tpu_steps.py says how):
flash attention, the blockwise quantizers, the loss head, the
state-space scan and its convolution, the delta rule; and each of them
splitting itself over a mesh. The expert layer's kernels are in
tests/test_tpu_compile_expert_kernels.py."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.ops import causal_conv
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
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    bf16,
    compile_,
    compiled_kernels,
    one_chip,
    topo,
)


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
    x = bf16(one_chip, b, t, h, d)
    text = compile_(_flash_grad(window), x, x, x).as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and backward


def test_flash_rect_compiles(one_chip):
    """Tq=512 queries against Tk=4096 keys (chunked prefill)."""
    q = bf16(one_chip, 2, 512, 32, 128)
    kv = bf16(one_chip, 2, 4096, 32, 128)

    def loss(q, k, v):
        out = flash_attention_rect(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = compile_(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "quantize", [quantize_blockwise, quantize_blockwise_4bit]
)
def test_blockwise_quantize_compiles(one_chip, compiled_kernels, quantize):
    x = jax.ShapeDtypeStruct((4096, 512), jnp.float32, sharding=one_chip)
    compiled = compile_(lambda x: quantize(x)[:2], x)
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
    qkv = bf16(
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
    text = compile_(grad, qkv, qkv, qkv).as_text()
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
    x = bf16(NamedSharding(mesh, P("fsdp", None)), 32768, 4096)
    table = bf16(NamedSharding(mesh, P(None, "fsdp")), 32000, 4096)
    targets = jax.ShapeDtypeStruct(
        (32768,), jnp.int32, sharding=NamedSharding(mesh, P("fsdp"))
    )
    grad = jax.value_and_grad(
        under_mesh(fused_cross_entropy, mesh), argnums=(0, 1)
    )
    text = compile_(grad, x, table, targets).as_text()
    collectives = [
        line for line in text.splitlines()
        if " all-reduce(" in line or " all-gather(" in line
        or " reduce-scatter(" in line or " all-to-all(" in line
    ]
    assert collectives  # the table is gathered, its gradient summed
    assert not [c for c in collectives if ",32000]" in c], collectives


def _ssd_operands(sharding, bsz, rows_sharding=None):
    """ops/ssd.py's operands at Granite 4.0-H's widths: 64 heads of
    64, state 128, one B/C group, 4096 tokens, bf16 with float32
    steps and per-head scalars."""
    rows = rows_sharding or sharding
    f32 = lambda shape, s: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=s)
    return (
        bf16(rows, bsz, 4096, 4096), f32((bsz, 4096, 64), rows),
        f32((64,), sharding), bf16(rows, bsz, 4096, 1, 128),
        bf16(rows, bsz, 4096, 1, 128), f32((64,), sharding),
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
    text = compile_(_ssd_grad, *_ssd_operands(one_chip, 1)).as_text()
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
    text = compile_(under_mesh(_ssd_grad, mesh), *args).as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "bf16[1,4096,4096]" in text  # a chip's own row
    assert "all-reduce" in text and "all-gather" not in text


def _conv_operands(sharding, bsz, rows_sharding=None):
    """A Granite mixer's projection ``[z | xBC | dt]`` (4096 + 4352 +
    64 columns, 4096 tokens) with the convolution's weights, bf16."""
    return (
        bf16(rows_sharding or sharding, bsz, 4096, 8512),
        bf16(sharding, 4, 4352), bf16(sharding, 4352),
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
        compile_(_conv_grad, *_conv_operands(one_chip, 1)).as_text()
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
    text = compile_(under_mesh(_conv_grad, mesh), *args).as_text()
    assert len(_conv_calls(text)) == 2
    assert "bf16[1,4096,8512]" in text  # a chip's own row
    assert "all-reduce" in text and "all-gather" not in text


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
        text = compile_(_kda_grad, *operands).as_text()
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
    text = compile_(under_mesh(_kda_grad, mesh), *args).as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    assert "bf16[1,512,1024]" in text  # a chip's own row
    assert "all-gather" not in text
