"""OLMoE's step, the sorted expert path, on one chip and on four,
compiled for a described v5e:2x2 (tests/tpu_steps.py says how). The
held expert path's cells: tests/test_tpu_compile_held.py."""

from tests.tpu_steps import (  # noqa: F401 — the fixtures
    assert_fits_with_flash,
    attn_relayouts,
    compiled_kernels,
    computations_calling,
    olmoe_step,
    step_gb,
    topo,
)


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
    compiled = olmoe_step(topo.devices[:1], "data", 4)
    assert_fits_with_flash(compiled)
    assert len(computations_calling(compiled, "moe_gmm")) == 6
    assert len(computations_calling(compiled, "moe_tgmm")) == 3
    # No kept [131072, .] value is rounded in a pass of its own.
    assert not [
        line for line in compiled.as_text().splitlines()
        if " reduce-precision(" in line and "= bf16[131072," in line
    ]
    assert step_gb(compiled) < 10.2454 + 0.05
    # Attention's operands keep one layout (PR 62): from the
    # projections to ``wo`` q, k, v, o and their gradients stay
    # [B, T, H*D], the flash kernels read a head as a column block and
    # a key-value head by the block's index, the rotation and the
    # group sums are kernels of their own. Under ``/attn/`` no
    # ``copy``, transposition, repeat, or half of a rotation of a
    # k-sized array or larger is left: 12 before, none now.
    assert not attn_relayouts(compiled.as_text(), 4 * 4096 * 2048)


def test_olmoe_train_step_compiles_on_four_chips(topo, compiled_kernels):
    """Tokens over ``fsdp=4``: each chip sorts its own tokens inside
    the kernels' shard_map, the expert weights are gathered whole and
    their gradients reduced over the mesh."""
    compiled = olmoe_step(list(topo.devices), "fsdp", 8)
    assert_fits_with_flash(compiled)
    text = compiled.as_text()
    assert "moe_gmm" in text and "all-gather" in text
