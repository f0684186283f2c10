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
    activations in, the experts' matrices out)."""
    rows = batch_rows * shape["seq_len"] * shape["experts_per_token"]
    e, w = shape["embd"], shape["expert_width"]
    return {
        "flops": 2.0 * rows * e * w,
        "bytes": 2.0 * (rows * e + rows * w + shape["experts"] * e * w),
    }
