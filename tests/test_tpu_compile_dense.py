"""The dense families' steps on one chip (GPT-2's, the trainer's
accumulating step, the Mistral cell's), compiled for a described
v5e:2x2 (tests/tpu_steps.py says how). On four chips:
tests/test_tpu_compile_dense_mesh.py."""

from dlrover_tpu.models import llama
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    assert_fits_with_flash,
    assert_flash_forward_runs_once,
    attn_relayouts,
    compiled_kernels,
    elastic_trainer_step,
    gpt2_step,
    mistral_cfg,
    step_gb,
    topo,
)


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
    compiled = gpt2_step(topo.devices[:1], "data", 18)
    assert_fits_with_flash(compiled)
    assert_flash_forward_runs_once(compiled, in_line=3)
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
    assert step_gb(compiled) < 6.4555 + 0.05


def test_trainer_step_accumulates_on_one_chip(topo, compiled_kernels):
    """The trainer's own step (``ElasticTrainer._build_step``) for
    GPT-2 124M at two microbatches of 18 x 1024, the program ROADMAP
    R2's cell will run: one program, the microbatch scan a loop in it
    with the flash kernels once a layer scan inside, within the
    chip's memory (7.4554 GB compiled with every layer scanned, the
    bound here: the float32 accumulator and a second staged
    microbatch over the plain step's 6.4555; 7.2899 with the first
    three layers in line)."""
    compiled = gpt2_step(topo.devices[:1], "data", 18, accum=2)
    assert_fits_with_flash(compiled)
    assert_flash_forward_runs_once(compiled, in_line=3)
    text = compiled.as_text()
    assert "/accumulate/" in text and "/optimizer/" in text
    assert "all-reduce" not in text  # one chip: nothing to reduce
    assert step_gb(compiled) < 7.4554 + 0.05


def test_mistral_train_step_has_no_layer_scan_on_one_chip(
    topo, compiled_kernels
):
    """The program of the benchmark's ``mistral-7b.steady``: two
    layers at published widths, 1 x 8192 tokens, window 4096, full
    remat, ``ElasticTrainer``'s step. Both layers run in line
    (models/layers.py): no loop and no ``dynamic-slice`` stands under
    the ``layers`` scope, nothing is stacked ``[2, 1, 8192, ...]``,
    the flash kernels are called once a layer. 10.4862 GB compiled
    here since PR 62 (10.8164 before), for the 14.4058 of the two-layer scan
    (``peak_memory_in_bytes`` 10.33: ``temp_size_in_bytes``, which the
    reading sums, counts a scan's stacks above the step's peak)."""
    compiled = elastic_trainer_step(llama, mistral_cfg(), topo)
    assert_fits_with_flash(compiled)
    assert_flash_forward_runs_once(compiled, times=0, in_line=2)
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
    assert step_gb(compiled) < 14.4058 * 1.02
    # Attention's operands keep one layout (PR 62): from the
    # projections to ``wo`` q, k, v, o and their gradients stay
    # [B, T, H*D], the flash kernels read a head as a column block and
    # a key-value head by the block's index, the rotation and the
    # group sums are kernels of their own. Under ``/attn/`` no
    # ``copy``, transposition, repeat, or half of a rotation of a
    # k-sized array or larger is left: 32 before, none now.
    assert not attn_relayouts(compiled.as_text(), 8192 * 1024)
    assert "rope_wide" in compiled.as_text()
    assert "flash_group_sum" in compiled.as_text()
