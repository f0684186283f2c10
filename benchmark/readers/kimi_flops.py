"""Reader ``kimi_flops``: model FLOP/s utilisation of a stack whose
layers are of three kinds (family ``kimi_linear``), in percent: the
operations the passes require for a token, nothing recomputed, times
tokens per second, over chips times the peak in ``peaks.json``.

A token requires 6 x the matrix parameters of each layer held, by its
own kind (the KDA or latent mixer; the dense MLP, or the expert
layer's router, shared expert and the routed experts at the load this
share expects, ``experts a token x held / router experts``: 8 x 8 /
256 = a quarter of one expert a token) and of the loss head's rows;
for each latent layer the causal half of QK^T at the query/key head
size and of PV at the value head size, forward and backward; for each
KDA layer three times the chunked rule's forward operations
(``kernel_work/kda_fwd.py``: the backward's are twice the forward's).
A configuration whose ``shape`` does not count its layers by these
kinds reads nothing."""

from benchmark import flops
from benchmark.kernel_work import kda_fwd


def flops_per_token(shape: dict) -> float:
    matrices = (
        shape["kda_layers"] * shape["kda_matmul_params"]
        + shape["mla_layers"] * shape["mla_matmul_params"]
        + shape["dense_layers"] * shape["dense_matmul_params"]
        + shape["moe_layers"] * shape["moe_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    attention = (
        6.0 * shape["mla_layers"] * shape["heads"]
        * (shape["head_dim"] + shape["v_head_dim"])
        * flops.mean_keys(shape["seq_len"], shape["window"])
    )
    rule = 3.0 * shape["kda_layers"] * (
        kda_fwd.work(shape, 1)["flops"] / shape["seq_len"]
    )
    return 6.0 * matrices + attention + rule


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    shape = flops.shape_of(ctx["cell"]["config"])
    if "kda_layers" not in shape:
        return None
    return 100.0 * flops_per_token(shape) * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
