"""Kernel ``moe_gmm``: what one grouped matrix product of the expert
MLP (models/moe.py ``grouped_matmul``) has to do on one device."""


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences give ``rows`` = sequences x tokens x
    experts a token (token, choice) pairs, each multiplied by its own
    expert's ``embd x expert_width`` matrix. The operations, and the
    bytes that must cross HBM: the ``rows x embd`` and ``rows x
    expert_width`` activations and every expert's matrix once each, in
    bf16. The same count holds for the gate and up products (rows x
    embd in, rows x width out), the down product (the other way
    round), their input gradients, and the weight gradient (both
    activations in, the experts' matrices out).

    A layer that holds a share of the experts (models/moe.py's held
    path: the shape gives ``experts_held`` of ``router_experts``) is
    sent ``held / router experts`` of those pairs when the load is even
    (Mellum 1 x 8,192 x 8 x 16 / 64 = 16,384) and reads the held
    experts' matrices alone. The held path's products walk its buffer
    ``rows_cap`` rows at a time, sized by the share (since PR 58:
    tokens x the held choices all but one token in forty stay within,
    twice the even load at Mellum's share, four times at Kimi's) with
    the blocks past the counted rows skipped: the rows of a block past
    the held pairs are padding, which no count here includes, so the
    share this reads against is the even load's over the buffer's at
    most (PERF.md section 6, PRs 57 and 58)."""
    rows = batch_rows * shape["seq_len"] * shape["experts_per_token"]
    experts = shape.get("experts_held")
    if experts is None:
        experts = shape["experts"]
    else:
        rows = rows * experts / shape["router_experts"]
    e, w = shape["embd"], shape["expert_width"]
    return {
        "flops": 2.0 * rows * e * w,
        "bytes": 2.0 * (rows * e + rows * w + experts * e * w),
    }
