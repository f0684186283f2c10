"""Kernel ``moe_tgmm`` of a layer that holds a share of the experts
(models/moe.py's held path): what one weight-gradient product has to
do on one device at the load the share expects."""

from benchmark.kernel_work import moe_gmm_held


def work(shape: dict, batch_rows: int) -> dict:
    """The held pairs' ``rows x embd`` transposed times their ``rows x
    expert_width``, group by group: the operations of the product it is
    the gradient of and the same bytes the other way round (both
    activations read, each held expert's ``embd x expert_width``
    gradient written once, bf16), as ``kernel_work/moe_tgmm.py`` says
    of the whole layer's. The rows of the buffer past the held pairs
    are not counted here either (``kernel_work/moe_gmm_held.py``)."""
    return moe_gmm_held.work(shape, batch_rows)
