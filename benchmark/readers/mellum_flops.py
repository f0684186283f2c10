"""Reader ``mellum_flops``: model FLOP/s utilisation of a stack whose
attention layers differ by position (family ``mellum``), in percent:
the operations the passes of a **whole step** require for a token,
nothing recomputed and no row of padding counted, times tokens per
second, over chips times the peak in ``peaks.json``.

A token requires 6 x the matrix parameters it passes in each layer
held (the attention projections, the router's every output, and the
held experts at the load this share expects, ``experts a token x held
/ router experts``: 8 x 16 / 64 = two experts a token) and in the loss
head's rows; and for each layer the causal QK^T and PV, forward and
backward, over its own kind's mean number of keys
(``flops.mean_keys``: 960 of 8,192 under the window of 1,024, 4,096.5
without). A configuration whose ``shape`` does not count its layers by
these kinds reads nothing."""

from benchmark import flops


def flops_per_token(shape: dict) -> float:
    matrices = (
        shape["layers"] * shape["layer_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    keys = sum(
        shape[f"{kind}_layers"]
        * flops.mean_keys(shape["seq_len"], shape[f"{kind}_window"])
        for kind in ("sliding", "full")
    )
    return 6.0 * matrices + 12.0 * shape["heads"] * shape["head_dim"] * keys


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    shape = flops.shape_of(ctx["cell"]["config"])
    if "sliding_layers" not in shape:
        return None
    return 100.0 * flops_per_token(shape) * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
