"""Kernel ``flash_fwd`` in a stack whose attention layers differ by
position (family ``mellum``): what one call of the forward
flash-attention kernel (ops/flash_attention.py,
``flash_attention_fwd``) has to do on one device, as the **mean over
the calls of one period**: ``sliding_layers`` calls under the band
``sliding_window`` and ``full_layers`` plain causal calls.

``readers/trace_events.py`` multiplies one call's work by the calls it
finds under the kernel's name, and both kinds run under one name, so
the mean over the period times the calls found is the period's sum.
It takes the roofline of the work it is given: the least time of the
mean's operations and the mean's bytes. That is the mean of the two
kinds' least times where both are bound by the same limit, and both
are compute-bound at the cell's sizes (a sliding call 0.65 ms of
operations against 0.33 ms of bytes on a v5e, a full call 2.79 against
0.33); ``tests/benchmark/test_mellum_cell_cpu.py`` holds the cell to
that. Each kind's count is ``kernel_work/flash_fwd.py``'s at that
kind's window."""

from benchmark.kernel_work import flash_fwd

def by_kind(shape: dict, batch_rows: int, one_kind=flash_fwd) -> list:
    """[(calls in a period, {"flops", "bytes"} of one)] for the sliding
    and the full layers."""
    return [
        (
            shape[f"{kind}_layers"],
            one_kind.work(
                dict(shape, window=shape[f"{kind}_window"]), batch_rows
            ),
        )
        for kind in ("sliding", "full")
    ]


def work(shape: dict, batch_rows: int, one_kind=flash_fwd) -> dict:
    kinds = by_kind(shape, batch_rows, one_kind)
    calls = sum(n for n, _ in kinds)
    return {
        key: sum(n * w[key] for n, w in kinds) / calls
        for key in ("flops", "bytes")
    }
