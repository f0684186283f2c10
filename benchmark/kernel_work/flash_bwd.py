"""Kernel ``flash_bwd``: what one call of the backward flash-attention
kernel (ops/flash_attention.py, ``flash_attention_bwd``) has to do on
one device."""

from benchmark import flops


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences: twice the forward's operations, which
    is what ``flops.attention_flops_per_token`` holds the backward to
    (dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q; the QK^T the
    kernel computes again is recomputation and not counted), and the
    bytes that must cross HBM once: q, k, v, o and do read and dq, dk,
    dv written in bf16, the two f32 row vectors (log-sum-exp, the row
    sums of o * do) read. k and v come repeated to the query heads, as
    for the forward."""
    b, t = batch_rows, shape["seq_len"]
    h, d = shape["heads"], shape["head_dim"]
    keys = flops.mean_keys(t, shape["window"])
    return {
        "flops": 8.0 * b * h * d * t * keys,
        "bytes": 8.0 * b * t * h * d * 2 + 2.0 * b * h * t * 4.0,
    }
