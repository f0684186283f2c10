"""Reader ``deepseek_flops``: model FLOP/s utilisation of a stack of
latent-attention layers with a leading dense MLP and expert layers
behind it (family ``deepseek_v2``), in percent: the operations the
passes of a **whole step** require for a token, nothing recomputed and
no row of padding counted, times tokens per second, over chips times
the peak in ``peaks.json``.

A token requires 6 x the matrix parameters it passes in each layer
held (the latent mixer's four projections; the dense MLP, or the
expert layer's router, its shared experts and the routed experts at
the load this share expects, ``experts a token x held / router
experts``: 6 x 8 / 64 = three quarters of one) and in the loss head's
rows; and for each layer the causal half of QK^T at the query/key head
size and of PV at the value head size, forward and backward
(``flops.mean_keys``: 4,096.5 of 8,192). The rotation is elementwise
and counts nothing. A configuration whose ``shape`` has no rotated
latent part reads nothing."""

from benchmark import flops


def flops_per_token(shape: dict) -> float:
    matrices = (
        shape["mla_layers"] * shape["mla_matmul_params"]
        + shape["dense_layers"] * shape["dense_matmul_params"]
        + shape["moe_layers"] * shape["moe_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    attention = (
        6.0 * shape["mla_layers"] * shape["heads"]
        * (shape["head_dim"] + shape["v_head_dim"])
        * flops.mean_keys(shape["seq_len"], shape["window"])
    )
    return 6.0 * matrices + attention


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    shape = flops.shape_of(ctx["cell"]["config"])
    if "mla_rope_dim" not in shape:
        return None
    return 100.0 * flops_per_token(shape) * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
