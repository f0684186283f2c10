"""The dense families' steps on four chips (GPT-2 along ``data`` and
along ``fsdp``, the trainer's step on ``data=4``, Mistral's block on
``fsdp=4``), compiled for a described v5e:2x2 (tests/tpu_steps.py
says how)."""

import functools

import jax
import pytest

from dlrover_tpu.models import gpt, llama
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    assert_fits_with_flash,
    assert_flash_forward_runs_once,
    attn_relayouts,
    compiled_kernels,
    gpt2_step,
    mistral_cfg,
    step_gb,
    topo,
    train_step,
)


@pytest.mark.parametrize("axis", ["data", "fsdp"])
def test_gpt2_train_step_compiles_on_four_chips(
    topo, compiled_kernels, axis
):
    """The program chip_smoke.py --chips 4 runs: global batch 32. The
    parent commit fails it ("Mosaic kernels cannot be automatically
    partitioned"); traced under the mesh (parallel.mesh.under_mesh)
    the flash call now puts itself in a shard_map over batch and
    heads (ops.flash_attention.per_device)."""
    compiled = gpt2_step(list(topo.devices), axis, 32)
    assert_fits_with_flash(compiled)
    # It is one program across the mesh, not four copies of one.
    assert "all-reduce" in compiled.as_text()
    # The kept (o, lse) are tagged inside the kernel's shard_map.
    assert_flash_forward_runs_once(compiled, in_line=3)
    if axis == "fsdp":  # PR 29's tree: 2.8190 GB a chip
        assert step_gb(compiled) < 2.8190 + 0.05


def test_trainer_step_on_data4_leaves_the_reduction_to_xla(
    topo, compiled_kernels
):
    """Pure data parallel on four chips, the mesh the deleted
    overlapped reduction was for: the trainer's step is one program in
    which XLA's own collectives form the gradients' mean over the
    shards, and the parameters, replicated, are gathered by nobody."""
    compiled = gpt2_step(list(topo.devices), "data", 32, accum=2)
    assert_fits_with_flash(compiled)
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
    compiled = train_step(
        llama, mistral_cfg(), list(topo.devices), "fsdp", 4
    )
    assert_fits_with_flash(compiled)
    assert_flash_forward_runs_once(compiled, times=0, in_line=2)
    assert step_gb(compiled) < 7.2069 + 0.05
    # Attention's operands keep one layout (PR 62): from the
    # projections to ``wo`` q, k, v, o and their gradients stay
    # [B, T, H*D], the flash kernels read a head as a column block and
    # a key-value head by the block's index, the rotation and the
    # group sums are kernels of their own. Under ``/attn/`` no
    # ``copy``, transposition, repeat, or half of a rotation of a
    # k-sized array or larger is left: 34 before, none now.
    assert not attn_relayouts(compiled.as_text(), 8192 * 1024)
    # The wide operands go through the kernels' ``shard_map`` a chip's
    # batch rows each: the calls stand in the step, none is gathered.
    assert "rope_wide" in compiled.as_text()
