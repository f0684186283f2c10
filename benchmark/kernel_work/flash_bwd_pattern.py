"""Kernel ``flash_bwd`` in a stack whose attention layers differ by
position (family ``mellum``): one call of the backward
flash-attention kernel as the mean over the calls of one period, as
``kernel_work/flash_fwd_pattern.py`` counts the forward's and for its
reasons (both kinds compute-bound: a sliding call 1.31 ms of
operations against 0.66 ms of bytes on a v5e, a full call 5.58 against
0.66). Each kind's count is ``kernel_work/flash_bwd.py``'s at that
kind's window."""

from benchmark.kernel_work import flash_bwd, flash_fwd_pattern


def by_kind(shape: dict, batch_rows: int) -> list:
    return flash_fwd_pattern.by_kind(shape, batch_rows, flash_bwd)


def work(shape: dict, batch_rows: int) -> dict:
    return flash_fwd_pattern.work(shape, batch_rows, flash_bwd)
