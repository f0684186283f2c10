"""Kernel ``moe_tgmm``: what one weight-gradient product of the expert
MLP (ops/grouped_matmul.py, ``moe_tgmm``) has to do on one device."""

from benchmark.kernel_work import moe_gmm


def work(shape: dict, batch_rows: int) -> dict:
    """``rows x embd`` transposed times ``rows x expert_width``, group
    by group: the operations of the product it is the gradient of, and
    the same bytes the other way round (both activations read, every
    expert's ``embd x expert_width`` gradient written once, bf16); of
    a layer that holds a share of the experts, the held pairs' rows and
    the held experts' gradients, the rows of the buffer past them not
    counted (``kernel_work/moe_gmm.py``)."""
    return moe_gmm.work(shape, batch_rows)
