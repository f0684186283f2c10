"""The hybrid and the looped families' steps (Granite 4.0-H, Ouro,
Kimi Linear), compiled for a described v5e:2x2 (tests/tpu_steps.py
says how)."""

import re

from dlrover_tpu.models import granite_hybrid, kimi_linear, ouro
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    assert_fits_with_flash,
    assert_flash_forward_runs_once,
    attn_relayouts,
    compiled_kernels,
    computations_calling,
    elastic_trainer_step,
    granite_cfg,
    kimi_cfg,
    ouro_cfg,
    topo,
    whole_array_passes,
)


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
    compiled = elastic_trainer_step(granite_hybrid, granite_cfg(), topo)
    assert_fits_with_flash(compiled)
    # One attention layer, outside the layer scans: one call each.
    assert len(computations_calling(compiled, "flash_attention_fwd")) == 1
    assert len(computations_calling(compiled, "flash_attention_bwd")) == 1
    fwd = computations_calling(compiled, "ssd_fwd")
    bwd = computations_calling(compiled, "ssd_bwd")
    assert len(fwd) == 2 and len(bwd) == 2 and not set(fwd) & set(bwd), (
        fwd, bwd
    )
    # The mixer's convolution is recomputed (its kernel stands in the
    # backward bodies too) and differentiated by its own kernel there.
    conv_fwd = computations_calling(compiled, "conv_silu_fwd")
    conv_bwd = computations_calling(compiled, "conv_silu_bwd")
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
    cfg = ouro_cfg()
    compiled = elastic_trainer_step(ouro, cfg, topo)
    assert "tpu_custom_call" in compiled.as_text()
    assert_flash_forward_runs_once(compiled, times=cfg.ut_steps)
    assert f"[{cfg.ut_steps},{cfg.n_layer}," not in compiled.as_text()
    # Attention's operands keep one layout (PR 62): from the
    # projections to ``wo`` q, k, v, o and their gradients stay
    # [B, T, H*D], the flash kernels read a head as a column block and
    # a key-value head by the block's index, the rotation and the
    # group sums are kernels of their own. Under ``/attn/`` no
    # ``copy``, transposition, repeat, or half of a rotation of a
    # k-sized array or larger is left: 48 before, none now.
    assert not attn_relayouts(compiled.as_text(), 4096 * 2048)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 17.194 GB here (17.586 with the layers' scan inside a scan over
    # the passes), over the 16.909 GB a program gets on the chip, where
    # the step runs: ``memory_analysis()`` still over-reads a step whose
    # buffer assignment totals 11.694 GB (14.208 nested; PERF.md 7(j)).
    # A reading that moves says the layer scans keep more or less.
    assert 16.7e9 < total < 17.4e9, total


def test_kimi_train_step_compiles_on_one_chip(topo, compiled_kernels):
    """The program of the benchmark's ``kimi-linear-48b-a3b.steady``:
    published layers 1 to 5 (dense-KDA, KDA, KDA, MLA, KDA with
    experts) at published widths, 8 of 256 experts held, an eighth of
    both tables, 1 x 8192 tokens, full remat. It fits; the flash
    kernels take the latent layer's two head sizes (192 and 128) and
    the forward runs once; each expert layer's grouped products stand
    in the one block of the held path's scan over its blocks of
    ``rows_cap`` sorted rows, in the one step program."""
    compiled = elastic_trainer_step(kimi_linear, kimi_cfg(), topo)
    assert_fits_with_flash(compiled)
    assert len(computations_calling(compiled, "flash_attention_fwd")) == 1
    assert len(computations_calling(compiled, "flash_attention_bwd")) == 1
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
    copies, unnamed = whole_array_passes(text, 8192 * 4096)
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
