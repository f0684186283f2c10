"""Kernel ``flash_bwd`` at two head sizes: what one call of the
backward flash-attention kernel (ops/flash_attention.py,
``flash_attention_bwd``) has to do on one device when queries and keys
are ``head_dim`` wide and values ``v_head_dim``. With ``v_head_dim``
absent or equal this count is ``kernel_work/flash_bwd.py``'s."""

from benchmark.kernel_work import flash_fwd_qkv


def work(shape: dict, batch_rows: int) -> dict:
    """Twice the forward's operations (dP = dO V^T and dV = P^T dO at
    the value size, dQ = dS K and dK = dS^T Q at the query/key size;
    the QK^T the kernel computes again is recomputation and not
    counted), and the bytes that must cross HBM once: q, k, dq and dk
    at the one size, v, o, do and dv at the other, in bf16, and the
    two f32 row vectors read."""
    b, t, h = batch_rows, shape["seq_len"], shape["heads"]
    d_qk = shape["head_dim"]
    d_v = shape.get("v_head_dim", d_qk)
    return {
        "flops": 2.0 * flash_fwd_qkv.work(shape, batch_rows)["flops"],
        "bytes": (
            4.0 * b * t * h * (d_qk + d_v) * 2 + 2.0 * b * h * t * 4.0
        ),
    }
