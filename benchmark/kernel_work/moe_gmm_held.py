"""Kernel ``moe_gmm`` of a layer that holds a share of the experts
(models/moe.py's held path): what one grouped matrix product has to do
on one device at the load the share expects."""


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences send this chip ``rows`` = sequences x
    tokens x experts a token x held / router experts (token, choice)
    pairs when the load is even (1 x 8,192 x 8 x 16 / 64 = 16,384),
    each multiplied by its own expert's ``embd x expert_width``
    matrix: those operations, and the bytes that must cross HBM once
    (the ``rows x embd`` and ``rows x expert_width`` activations and
    the held experts' matrices, in bf16). The same count holds for the
    gate, up and down products and their input gradients, as
    ``kernel_work/moe_gmm.py`` says of the whole layer's. The held
    path's products walk its whole buffer (``rows_cap``), which at a
    quarter share is every pair of the layer, four times these rows:
    the rows past the held pairs are padding, which no count here
    includes, so the share this reads against is a quarter at most
    (PERF.md section 6, PR 57)."""
    rows = (
        batch_rows * shape["seq_len"] * shape["experts_per_token"]
        * shape["experts_held"] / shape["router_experts"]
    )
    e, w = shape["embd"], shape["expert_width"]
    return {
        "flops": 2.0 * rows * e * w,
        "bytes": 2.0 * (rows * e + rows * w + shape["experts_held"] * e * w),
    }
