"""Reader ``hybrid_flops``: model FLOP/s utilisation of a stack whose
layers are of two kinds (family ``granite_hybrid``), in percent: the
operations the passes require for a token, nothing recomputed, times
tokens per second, over chips times the peak in ``peaks.json``.

A token requires 6 x the matrix parameters of each layer held (by its
own kind) and of the loss head's rows; for each attention layer the
causal half of QK^T and PV, forward and backward (``flops.py``'s
count); for each Mamba-2 layer three times the chunked scan's forward
operations (``kernel_work/ssd_fwd.py``: the backward's are twice the
forward's). A configuration whose ``shape`` does not count its layers
by kind reads nothing."""

from benchmark import flops
from benchmark.kernel_work import ssd_fwd


def flops_per_token(shape: dict) -> float:
    matrices = (
        shape["mamba_layers"] * shape["mamba_matmul_params"]
        + shape["attention_layers"] * shape["attention_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    attention = (
        12.0 * shape["attention_layers"] * shape["heads"] * shape["head_dim"]
        * flops.mean_keys(shape["seq_len"], shape["window"])
    )
    scan = 3.0 * shape["mamba_layers"] * (
        ssd_fwd.work(shape, 1)["flops"] / shape["seq_len"]
    )
    return 6.0 * matrices + attention + scan


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    shape = flops.shape_of(ctx["cell"]["config"])
    if "mamba_layers" not in shape:
        return None
    return 100.0 * flops_per_token(shape) * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
