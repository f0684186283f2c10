"""Family ``olmoe``: a configuration file with the published OLMoE keys
-> models/llama.py's init, loss and logical axes with the expert MLP
(models/moe.py), normalised queries and keys and unrenormalised top-k
weights, the plain reference that goes with it, and the sizes the
yardstick's counts need. Nothing is imported at the top: a launcher
that reads ``shape`` must stay off JAX."""

from __future__ import annotations

import functools

from benchmark.families.llama import _seq_len  # no JAX there either


def shape(config: dict) -> dict:
    """The sizes ``flops.py`` and ``kernel_work/`` count from, under
    the names every family gives them, and the three the grouped
    products' count needs."""
    e = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d = e // heads
    width = config["intermediate_size"]  # of ONE expert (OlmoeMLP)
    experts = config["num_experts"]
    per_token = config["num_experts_per_tok"]
    return {
        "layers": config["num_hidden_layers"],
        "embd": e,
        "heads": heads,
        "kv_heads": kv,
        "head_dim": d,
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        # What a token is multiplied by: wq, wo E^2 each; wk, wv
        # E x (kv x d) each; the router E x experts; gate, up and
        # down of the experts it is sent to, not of all of them.
        "layer_matmul_params": (
            2 * e * e + 2 * e * kv * d + e * experts
            + per_token * 3 * e * width
        ),
        "experts": experts,
        "experts_per_token": per_token,
        "expert_width": width,
    }


def build(config: dict) -> dict:
    from benchmark.reference import olmoe as reference
    from dlrover_tpu.models import llama

    assumed = config.get("assumed", {})
    cfg = llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        n_embd=config["hidden_size"],
        intermediate=config["intermediate_size"],
        rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"],
        n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_renorm_top_k=config["norm_topk_prob"],
        moe_aux_loss_weight=assumed["router_aux_loss_coef"],
        moe_z_loss_weight=assumed["router_z_loss_coef"],
        qk_norm=True,
        remat=assumed.get("remat", True),
    )
    return {
        "cfg": cfg,
        "init": functools.partial(llama.init_params, cfg=cfg),
        "loss": functools.partial(llama.loss_fn_fused, cfg=cfg),
        "axes": llama.param_logical_axes(cfg),
        "seq_len": cfg.block_size,
        "vocab": config["vocab_size"],
        "reference_loss": functools.partial(reference.loss, config=config),
    }
