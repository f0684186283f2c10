"""The expert layer's kernels alone at the cells' real shapes, through
the chip's compiler without the chip (tests/tpu_steps.py says how):
the grouped products at OLMoE's widths, the held path's rows summed
back by token at the three held cells'."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.ops import grouped_matmul, rows_sum
from tests.tpu_steps import (  # noqa: F401 — the fixtures
    bf16,
    compile_,
    compiled_kernels,
    one_chip,
    topo,
)


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
    wide, narrow = bf16(one_chip, rows, e), bf16(one_chip, rows, w)
    if form == "gate_up":
        fn = functools.partial(grouped_matmul.moe_gmm, interpret=False)
        args = (wide, bf16(one_chip, experts, e, w), sizes)
    elif form == "down":
        fn = functools.partial(grouped_matmul.moe_gmm, interpret=False)
        args = (narrow, bf16(one_chip, experts, w, e), sizes)
    elif form == "input_grad":
        fn = functools.partial(
            grouped_matmul.moe_gmm, transpose_rhs=True, interpret=False
        )
        args = (narrow, bf16(one_chip, experts, e, w), sizes)
    else:
        fn = functools.partial(grouped_matmul.moe_tgmm, interpret=False)
        args = (wide, narrow, sizes)
    text = compile_(fn, *args).as_text()
    assert "tpu_custom_call" in text
    assert ("moe_tgmm" if form == "weight_grad" else "moe_gmm") in text


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
    text = compile_(
        fn, one((n, top_k), jnp.int32), bf16(one_chip, cap, d),
        one((n, top_k), jnp.float32),
    ).as_text()
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_rows_sum', text
    )) == 1
    assert rows_sum.layout(n, cap, held)["tile"] == 256
    assert cap == n or f"f32[{cap},{d}]" not in text
    assert not re.search(rf"= [^\n]*\[{cap}\][^\n]* sort\(", text)
