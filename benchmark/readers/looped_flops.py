"""Reader ``looped_flops``: model FLOP/s utilisation of a stack that a
step runs several times on the same weights (family ``ouro``), in
percent: the operations the passes require for a token, nothing
recomputed, times tokens per second, over chips times the peak in
``peaks.json``.

A token is multiplied by every matrix of every layer held once a
pass, and by the head's rows once a pass (the loss reads every pass's
logits): 6 x ``ut_steps`` x (``layers`` x ``layer_matmul_params`` +
``vocab_rows`` x ``embd``); attention's causal half of QK^T and PV,
forward and backward, is ``flops.py``'s count a layer, once a pass. The
exit gate's ``embd`` multiplications a pass are left out. A
configuration whose ``shape`` gives no ``ut_steps`` reads nothing."""

from benchmark import flops


def flops_per_token(shape: dict) -> float:
    matrices = (
        shape["layers"] * shape["layer_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    attention = (
        12.0 * shape["layers"] * shape["heads"] * shape["head_dim"]
        * flops.mean_keys(shape["seq_len"], shape["window"])
    )
    return shape["ut_steps"] * (6.0 * matrices + attention)


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    shape = flops.shape_of(ctx["cell"]["config"])
    if "ut_steps" not in shape:
        return None
    return 100.0 * flops_per_token(shape) * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
