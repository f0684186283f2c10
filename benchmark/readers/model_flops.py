"""Reader ``model_flops``: model FLOP/s utilisation of the whole step,
in percent: the operations the passes require for a token
(``flops.train_flops_per_token``: the family's own count or the dense
stack's, nothing recomputed, the causal half and the window taken off)
times tokens per second, over chips times the peak in ``peaks.json``."""

from benchmark import flops


def read(ctx: dict):
    rate = (ctx.get("window") or {}).get("tokens_per_s")
    if not rate or not ctx.get("peaks"):
        return None  # no rate, or a rehearsal off the chip: no peak
    need = flops.train_flops_per_token(ctx["cell"]["config"])
    return 100.0 * need * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"]
    )
