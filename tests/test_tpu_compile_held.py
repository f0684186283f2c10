"""The steps of two cells that hold a share of the experts (Mellum,
DeepSeek-V2), compiled for a described v5e:2x2 (tests/tpu_steps.py
says how). The third, Kimi Linear's, is with the hybrid stacks."""

import re

from dlrover_tpu.models import deepseek_v2, mellum
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    assert_fits_with_flash,
    attn_relayouts,
    compiled_kernels,
    deepseek_cfg,
    elastic_trainer_step,
    mellum_cfg,
    topo,
)


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
    compiled = elastic_trainer_step(mellum, mellum_cfg(), topo)
    assert_fits_with_flash(compiled)
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
    # Attention's operands keep one layout (PR 62): from the
    # projections to ``wo`` q, k, v, o and their gradients stay
    # [B, T, H*D], the flash kernels read a head as a column block and
    # a key-value head by the block's index, the rotation and the
    # group sums are kernels of their own. Under ``/attn/`` no
    # ``copy``, transposition, repeat, or half of a rotation of a
    # k-sized array or larger is left: 64 before, none now.
    assert not attn_relayouts(text, 8192 * 512)
    # A layer: q and k rotated forward, dq and dk back (kept rotated,
    # not rotated again), dk and dv summed over a group's eight heads.
    assert calls("rope_wide") == 16, calls("rope_wide")
    assert calls("flash_group_sum") == 4, calls("flash_group_sum")
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print("mellum step bytes", total, mem)
    # 8.515 since PR 62: k and v are never written 32 heads wide.
    assert total / 1e9 < 8.6, total


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
    compiled = elastic_trainer_step(deepseek_v2, deepseek_cfg(), topo)
    assert_fits_with_flash(compiled)
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
