"""The flash-attention backward as the yardstick reads it: the
operation counts of ``kernel_work/flash_bwd.py`` against hand-worked
numbers, and the two per-layer metrics that find the kernel by the
name the program gives it."""

import importlib

import pytest

from benchmark import cell as cell_files
from benchmark import flops
from benchmark import trace_reduce as tr
from tests.benchmark.test_trace_reduce import _config, _ev

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("config,rows,flop,byte", [
    # 18 x 1024, 12 heads of 64, 512.5 keys: twice the forward's
    # 4 b h d t keys; eight bf16 tensors of 18 x 1024 x 768 and two
    # f32 row vectors of 18 x 12 x 1024.
    ("gpt2-124m", 18, 58_038_681_600, 226_492_416 + 1_769_472),
    # 1 x 8192, 32 heads of 128, window 4096: 3072.25 keys.
    ("mistral-7b", 1, 824_700_829_696, 536_870_912 + 2_097_152),
])
def test_flash_backward_call_against_hand_counts(config, rows, flop, byte):
    work = flops.kernel_work("flash_bwd", _config(config), rows)
    assert work == {"flops": flop, "bytes": byte}
    forward = flops.kernel_work("flash_fwd", _config(config), rows)
    assert work["flops"] == 2 * forward["flops"]
    # What flops.attention_flops_per_token holds a step to: forward
    # once and backward once a layer, 12 x E x keys a token.
    shape = flops.shape_of(_config(config))
    assert (work["flops"] + forward["flops"]) * shape["layers"] == (
        pytest.approx(flops.attention_flops_per_token(_config(config))
                      * rows * shape["seq_len"])
    )
    assert flops.roofline_seconds(work, PEAKS)["bound"] == "compute"


def _layer_metric(name, ctx):
    (spec,) = [s for s in cell_files.layer_metric_specs() if s["name"] == name]
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def test_the_flash_backward_metrics_follow_the_kernels_name():
    """A step of 12 layers: the backward kernel once a layer, 1.7 ms a
    call, under the name the program gives it and whatever number XLA
    appends; the forward twice a layer (remat) stays the forward's."""
    events = []
    for step in range(4):
        b = step * 100e6
        events.append(_ev(tr.MODULES_LINE, "jit_train_step", b, 90e6))
        for layer in range(12):
            at = b + layer * 5e6
            events += [
                _ev(tr.OPS_LINE, "flash_attention_fwd.16", at, 0.7e6,
                    category="custom-call:tpu_custom_call"),
                _ev(tr.OPS_LINE, "flash_attention_fwd.17", at + 1e6, 0.7e6,
                    category="custom-call:tpu_custom_call"),
                _ev(tr.OPS_LINE, "flash_attention_bwd.3", at + 2e6, 1.7e6,
                    category="custom-call:tpu_custom_call"),
            ]
    cell = cell_files.load_cell("gpt2-124m.steady")
    ctx = {"trace": tr.reduce(events), "cell": cell, "peaks": PEAKS}
    assert ctx["trace"]["steps"] == 3
    assert _layer_metric("flash_bwd_ms_per_step.train", ctx) == pytest.approx(
        12 * 1.7
    )
    least = 58_038_681_600 / 197e12
    assert _layer_metric("flash_bwd_roofline.train", ctx) == pytest.approx(
        100 * least / 1.7e-3
    )
    assert ctx["notes"]["flash_bwd_bound"] == "compute"
    # The forward's metric reads the forward's events only, as before.
    fwd = flops.kernel_work("flash_fwd", cell["config"], 18)["flops"] / 197e12
    assert _layer_metric("flash_fwd_roofline.train", ctx) == pytest.approx(
        100 * fwd / 0.7e-3
    )
    assert _layer_metric("pallas_ms_per_step.train", ctx) == pytest.approx(
        12 * (0.7 + 0.7 + 1.7)
    )
    # The parent commit's trace has no such name: nothing is read.
    old = [dict(e, name="attn.9") if e["name"].startswith("flash_attention_bwd")
           else e for e in events]
    ctx["trace"] = tr.reduce(old)
    assert _layer_metric("flash_bwd_ms_per_step.train", ctx) is None
    assert _layer_metric("flash_bwd_roofline.train", ctx) is None
