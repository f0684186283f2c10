"""Kernel ``flash_bwd``: what one call of the backward flash-attention
kernel (ops/flash_attention.py, ``flash_attention_bwd``) has to do on
one device. Two head sizes (``v_head_dim``) and a patterned stack
(``sliding_layers`` / ``full_layers``) are read from the family's
``shape`` as ``kernel_work/flash_fwd.py`` reads them, and for its
reasons (both of Mellum's kinds compute-bound: a sliding call 1.31 ms
of operations against 0.66 ms of bytes on a v5e, a full call 5.58
against 0.66)."""

from benchmark.kernel_work import flash_fwd


def one_call(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences under ``shape["window"]``: twice the
    forward's operations, which is what
    ``flops.attention_flops_per_token`` holds the backward to (dP = dO
    V^T and dV = P^T dO at the value size, dQ = dS K and dK = dS^T Q at
    the query/key size; the QK^T the kernel computes again is
    recomputation and not counted), and the bytes that must cross HBM
    once: q, k, dq and dk at the one size, v, o, do and dv at the
    other, in bf16, and the two f32 row vectors (log-sum-exp, the row
    sums of o * do) read. ``heads`` of k, v, dk and dv are counted, as
    for the forward: the group sums of dk and dv that follow at head
    size 128 are another kernel's."""
    b, t, h = batch_rows, shape["seq_len"], shape["heads"]
    d_qk = shape["head_dim"]
    d_v = shape.get("v_head_dim", d_qk)
    return {
        "flops": 2.0 * flash_fwd.one_call(shape, batch_rows)["flops"],
        "bytes": (
            4.0 * b * t * h * (d_qk + d_v) * 2 + 2.0 * b * h * t * 4.0
        ),
    }


def by_kind(shape: dict, batch_rows: int) -> list:
    return flash_fwd.by_kind(shape, batch_rows, one_call)


def work(shape: dict, batch_rows: int) -> dict:
    return flash_fwd.work(shape, batch_rows, one_call)
