"""Kernel ``ssd_fwd``: what one forward call of the chunked
state-space scan (ops/ssd.py, ``ssd_fwd``: one Mamba-2 layer's scan
over whole sequences) has to do on one device, whatever implements
it."""


def work(shape: dict, batch_rows: int) -> dict:
    """``batch_rows`` sequences of ``seq_len`` tokens in chunks of
    ``ssm_chunk``. The matrix operations the chunked form requires,
    nothing recomputed: in every chunk the causal half of ``C B^T``
    (once a B/C group, not once a head) and of its product with
    ``dt x`` (a head); for every chunk but the last the state it
    leaves (``(dt x)^T B``, head x state), and for every chunk but the
    first what the state it enters with adds (``C S^T``). The bytes
    that must cross HBM once: ``x``, ``B``, ``C`` read and ``y``
    written in bf16, ``dt`` read in float32, and what the backward
    needs of the forward, the float32 state each chunk but the first
    enters with."""
    b, t, length = batch_rows, shape["seq_len"], shape["ssm_chunk"]
    heads, p = shape["ssm_heads"], shape["ssm_head_dim"]
    groups, n = shape["ssm_groups"], shape["ssm_state"]
    chunks = t // length
    half = length * (length + 1) / 2.0  # pairs (t, s <= t) in a chunk
    flops = b * (
        chunks * 2.0 * half * (groups * n + heads * p)
        + (chunks - 1) * 2 * (2.0 * length * heads * p * n)
    )
    bytes_ = b * (
        2 * (t * heads * p * 2.0)            # x in, y out
        + 2 * (t * groups * n * 2.0)         # B, C
        + t * heads * 4.0                    # dt
        + (chunks - 1) * heads * p * n * 4.0  # chunk states
    )
    return {"flops": flops, "bytes": bytes_}
