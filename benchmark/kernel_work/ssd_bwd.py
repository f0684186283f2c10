"""Kernel ``ssd_bwd``: what one backward call of the chunked
state-space scan (ops/ssd.py, ``ssd_bwd``) has to do on one device,
whatever implements it."""

from benchmark.kernel_work import ssd_fwd


def work(shape: dict, batch_rows: int) -> dict:
    """Every matrix product of the forward has two in the backward,
    one for each operand's gradient, and nothing is recomputed: twice
    the forward's operations. The bytes that must cross HBM once:
    ``x``, ``dy`` read and ``dx`` written in bf16; ``B``, ``C`` read
    and their gradients written in bf16; ``dt`` read and its gradient
    written in float32; the float32 chunk states read."""
    b, t = batch_rows, shape["seq_len"]
    heads, p = shape["ssm_heads"], shape["ssm_head_dim"]
    groups, n = shape["ssm_groups"], shape["ssm_state"]
    chunks = t // shape["ssm_chunk"]
    bytes_ = b * (
        3 * (t * heads * p * 2.0)            # x, dy in; dx out
        + 4 * (t * groups * n * 2.0)         # B, C in; dB, dC out
        + 2 * (t * heads * 4.0)              # dt in; d dt out
        + (chunks - 1) * heads * p * n * 4.0  # chunk states
    )
    return {"flops": 2.0 * ssd_fwd.work(shape, batch_rows)["flops"],
            "bytes": bytes_}
