"""Kernel ``flash_fwd``: what one call of the forward flash-attention
kernel (ops/flash_attention.py, ``flash_attention_fwd``) has to do on
one device, from what the family's ``shape`` says of its attention:

- ``v_head_dim`` (a latent-attention layer: queries and keys
  ``head_dim`` wide, 192, values 128): QK^T at the one size and PV at
  the other. Absent, values are ``head_dim`` wide and the count is the
  one size's.
- ``sliding_layers`` / ``full_layers`` (a stack whose attention layers
  differ by position): the **mean over the calls of one period**,
  ``sliding_layers`` calls under the band ``sliding_window`` and
  ``full_layers`` plain causal ones. ``readers/trace_events.py``
  multiplies one call's work by the calls it finds under the kernel's
  name, and both kinds run under one name, so the mean times the calls
  found is the period's sum. It takes the roofline of the work it is
  given, the least time of the mean's operations and the mean's bytes:
  that is the mean of the two kinds' least times where both are bound
  by the same limit, and both are compute-bound at Mellum's sizes (a
  sliding call 0.65 ms of operations against 0.33 ms of bytes on a
  v5e, a full call 2.79 against 0.33;
  ``tests/benchmark/test_mellum_cell_cpu.py`` holds the cell to that).
  Absent, every call is under ``window``.
"""

from benchmark import flops

KINDS = ("sliding", "full")


def one_call(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences under ``shape["window"]``: the
    operations the masked QK^T (at the query/key size) and PV (at the
    value size) need, and the bytes that must cross HBM (q and k read
    at the one size, v read and o written at the other, once, in bf16;
    the f32 log-sum-exp written once). ``heads`` of k and v are
    counted: at head size 64 and in the latent layers the program
    hands the kernel k and v repeated to the query heads; at head size
    128 (since PR 62) the kernel reads a group's one key-value head
    through the block's index, re-read for each query head of the
    group, and the count stands. The columns that pad 192 to a lane
    multiple are a layout and not counted."""
    b, t, h = batch_rows, shape["seq_len"], shape["heads"]
    d_qk = shape["head_dim"]
    d_v = shape.get("v_head_dim", d_qk)
    keys = flops.mean_keys(t, shape["window"])
    return {
        "flops": 2.0 * b * h * (d_qk + d_v) * t * keys,
        "bytes": 2.0 * b * t * h * (d_qk + d_v) * 2 + b * h * t * 4.0,
    }


def by_kind(shape: dict, batch_rows: int, one=one_call) -> list:
    """[(calls in a period, {"flops", "bytes"} of one)] for the sliding
    and the full layers of a patterned stack."""
    return [
        (
            shape[f"{kind}_layers"],
            one(dict(shape, window=shape[f"{kind}_window"]), batch_rows),
        )
        for kind in KINDS
    ]


def work(shape: dict, batch_rows: int, one=one_call) -> dict:
    if "sliding_layers" not in shape:
        return one(shape, batch_rows)
    kinds = by_kind(shape, batch_rows, one)
    calls = sum(n for n, _ in kinds)
    return {
        key: sum(n * w[key] for n, w in kinds) / calls
        for key in ("flops", "bytes")
    }
