"""Kernel ``flash_fwd``: what one call of the forward flash-attention
kernel (ops/flash_attention.py, ``flash_attention_fwd``) has to do on
one device."""

from benchmark import flops


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences: the operations the masked QK^T and PV
    need, and the bytes that must cross HBM (q, k, v read and o written
    once in bf16, the f32 log-sum-exp written once). The program
    repeats k and v to the query heads before the kernel
    (models/llama.py ``_block``), so the kernel reads ``heads`` of
    each."""
    b, t = batch_rows, shape["seq_len"]
    h, d = shape["heads"], shape["head_dim"]
    keys = flops.mean_keys(t, shape["window"])
    return {
        "flops": 4.0 * b * h * d * t * keys,
        "bytes": 4.0 * b * t * h * d * 2 + b * h * t * 4.0,
    }
