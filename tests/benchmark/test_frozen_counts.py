"""The yardstick's numerators, frozen at PR 63's parent (commit
d716ad5) before that PR folded the per-family FLOP readers
(``hybrid_flops``, ``looped_flops``, ``kimi_flops``, ``mellum_flops``,
``deepseek_flops``) into ``flops.train_flops_per_token`` and the six
``kernel_work`` variants (``flash_fwd_qkv``, ``flash_fwd_pattern``,
``moe_gmm_held`` and the backward's) into one module a kernel: for all
nine configurations the folded code returns what the module its cell
read before returned, to the last digit. A later PR that moves one of
these has moved a numerator of ``mfu.train`` or of a roofline, and
says so in ``PERF.md``."""

import pytest

from benchmark import cell as cell_files
from benchmark import flops

# cell -> operations a token of a whole step. (OLMoE had no such
# metric before: its line lacked the share for want of its name in a
# list, not of a count. The count its shape already gave through the
# fallback is what ``mfu.train`` reads for it.)
FLOPS_PER_TOKEN = {
    "gpt2-124m.steady": 798087168.0,
    "mistral-7b.steady": 3705692160.0,
    "gpt2-124m.resume": 798087168.0,
    "mistral-7b-host4.fsdp4": 12463472640.0,
    "olmoe-1b-7b.steady": 1071919104.0,
    "granite-4.0-h-micro.steady": 4918177152.0,
    "ouro-2.6b.steady": 11878662144.0,
    "kimi-linear-48b-a3b.steady": 2318727168.0,
    "mellum2-12b-a2.5b.steady": 1493074944.0,
    "deepseek-v2-lite.steady": 2528864256.0,
}

# cell -> kernel -> (sequences a call, operations, bytes) of one call,
# under the module the cell's family read at the parent: ``_qkv`` for
# Kimi and DeepSeek, ``_pattern`` for Mellum, ``_held`` for the three
# that hold a share of the experts, the plain module otherwise.
KERNEL_WORK = {
    "gpt2-124m.steady": {
        "flash_fwd": (18, 29019340800.0, 114130944.0),
        "flash_bwd": (18, 58038681600.0, 228261888.0)},
    "mistral-7b.steady": {
        "flash_fwd": (1, 412350414848.0, 269484032.0),
        "flash_bwd": (1, 824700829696.0, 538968064.0)},
    "gpt2-124m.resume": {
        "flash_fwd": (18, 29019340800.0, 114130944.0),
        "flash_bwd": (18, 58038681600.0, 228261888.0)},
    "mistral-7b-host4.fsdp4": {
        "flash_fwd": (1, 412350414848.0, 269484032.0),
        "flash_bwd": (1, 824700829696.0, 538968064.0)},
    "olmoe-1b-7b.steady": {
        "flash_fwd": (4, 274945015808.0, 269484032.0),
        "flash_bwd": (4, 549890031616.0, 538968064.0),
        "moe_gmm": (4, 549755813888.0, 1073741824.0),
        "moe_tgmm": (4, 549755813888.0, 1073741824.0)},
    "granite-4.0-h-micro.steady": {
        "flash_fwd": (1, 68736253952.0, 67633152.0),
        "flash_bwd": (1, 137472507904.0, 135266304.0),
        "ssd_fwd": (1, 12499550208.0, 101711872.0),
        "ssd_bwd": (1, 24999100416.0, 138412032.0)},
    "ouro-2.6b.steady": {
        "flash_fwd": (1, 68736253952.0, 67371008.0),
        "flash_bwd": (1, 137472507904.0, 134742016.0)},
    "kimi-linear-48b-a3b.steady": {
        "flash_fwd": (1, 687278653440.0, 336592896.0),
        "flash_bwd": (1, 1374557306880.0, 673185792.0),
        "moe_gmm": (1, 9663676416.0, 51380224.0),
        "moe_tgmm": (1, 9663676416.0, 51380224.0),
        "kda_fwd": (1, 36507222016.0, 672137216.0)},
    "mellum2-12b-a2.5b.steady": {
        "flash_fwd": (1, 234098786304.0, 269484032.0),
        "flash_bwd": (1, 468197572608.0, 538968064.0),
        "moe_gmm": (1, 67645734912.0, 170917888.0),
        "moe_tgmm": (1, 67645734912.0, 170917888.0)},
    "deepseek-v2-lite.steady": {
        "flash_fwd": (1, 343639326720.0, 168296448.0),
        "flash_bwd": (1, 687278653440.0, 336592896.0),
        "moe_gmm": (1, 35433480192.0, 88604672.0),
        "moe_tgmm": (1, 35433480192.0, 88604672.0)},
}


@pytest.mark.parametrize("cell", sorted(FLOPS_PER_TOKEN))
def test_a_token_s_operations_are_the_parents_to_the_last_digit(cell):
    config = cell_files.load_cell(cell)["config"]
    assert flops.train_flops_per_token(config) == FLOPS_PER_TOKEN[cell]


@pytest.mark.parametrize("cell,kernel", [
    (cell, kernel) for cell in sorted(KERNEL_WORK)
    for kernel in sorted(KERNEL_WORK[cell])
])
def test_a_kernel_call_s_work_is_the_parents_to_the_last_digit(cell, kernel):
    loaded = cell_files.load_cell(cell)
    rows, want_flops, want_bytes = KERNEL_WORK[cell][kernel]
    assert rows == loaded["workload"]["micro_batch_per_chip"]
    assert flops.kernel_work(kernel, loaded["config"], rows) == {
        "flops": want_flops, "bytes": want_bytes,
    }


@pytest.mark.parametrize("cell", sorted(FLOPS_PER_TOKEN))
def test_every_roofline_a_frozen_cell_reports_is_frozen(cell):
    """Of the ten cells there were at PR 63; a later cell brings its
    own counts and its own test, and is no business of this table."""
    loaded = cell_files.load_cell(cell)
    for spec in cell_files.cell_metric_specs(loaded):
        if spec.get("args", {}).get("what") == "roofline":
            assert spec["args"]["kernel"] in KERNEL_WORK[cell], spec["name"]
