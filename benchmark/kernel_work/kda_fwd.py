"""Operations and bytes the chunked delta rule's forward requires
(``ops/kda.py``: kernels ``kda_fwd`` / ``kda_bwd`` since PR 54, the
plain ``jax.numpy`` form where the head sizes are no multiple of 128;
the count is of the mathematics, whatever implements it, for the Kimi
family's ``flops_per_token`` and for the kernels' rooflines, which no
metric reads yet), one call on one device, nothing recomputed.

A chunk of C tokens of one head of size d (keys and values alike):
the two pair matrices' lower triangles (k k^T and q k^T with their
decays, C^2 d each), the unit triangular solve for W and U0 by forward
substitution (C^2 2d), the three products against the d x d state
(W S0, (Q exp G) S0, (K decays)^T U: 2 C d^2 each) and B U's lower
triangle (C^2 d): 5 C^2 d + 6 C d^2. The decays themselves (C d
exponentials and the sub-blocks' pairs) run on the vector unit and are
not counted.

Bytes: q, k, v in bf16, the log decays in float32 and beta read once,
o written in bf16, each chunk's starting state written in float32."""


def work(shape: dict, batch_rows: int) -> dict:
    t, heads = shape["seq_len"], shape["kda_heads"]
    d, c = shape["kda_head_dim"], shape["kda_chunk"]
    tokens = batch_rows * t * heads
    return {
        "flops": float(tokens * (5 * c * d + 6 * d * d)),
        "bytes": float(
            tokens * (3 * d * 2 + d * 4 + 4 + d * 2)
            + batch_rows * (t // c) * heads * d * d * 4
        ),
    }
