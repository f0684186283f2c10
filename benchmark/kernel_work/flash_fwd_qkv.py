"""Kernel ``flash_fwd`` at two head sizes: what one call of the forward
flash-attention kernel (ops/flash_attention.py,
``flash_attention_fwd``) has to do on one device when queries and keys
are ``head_dim`` wide and values ``v_head_dim`` (a latent-attention
layer: 192 and 128). ``kernel_work/flash_fwd.py`` knows one size and
is an accepted file; with ``v_head_dim`` absent or equal this count is
that one's."""

from benchmark import flops


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences: the masked QK^T at the query/key size
    and PV at the value size, and the bytes that must cross HBM (q and
    k read at the one size, v read and o written at the other, in
    bf16; the f32 log-sum-exp written once). The columns that pad 192
    to a lane multiple are a layout and not counted."""
    b, t, h = batch_rows, shape["seq_len"], shape["heads"]
    d_qk = shape["head_dim"]
    d_v = shape.get("v_head_dim", d_qk)
    keys = flops.mean_keys(t, shape["window"])
    return {
        "flops": 2.0 * b * h * (d_qk + d_v) * t * keys,
        "bytes": 2.0 * b * t * h * (d_qk + d_v) * 2 + b * h * t * 4.0,
    }
