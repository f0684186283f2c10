"""Family ``deepseek_v2``: a configuration file with the published
DeepSeek-V2 keys -> models/deepseek_v2.py's init, loss and logical
axes (latent attention on every layer with the shared key part rotated
under ``rope_scaling``, ``first_k_dense_replace`` leading dense MLPs,
then expert layers with a greedy softmax router and shared experts, of
which this chip holds ``n_routed_experts`` from
``assumed.first_expert`` on), the plain reference that goes with it,
and the sizes the yardstick's counts need. Nothing is imported at the
top: the model and the reference are loaded by ``build`` alone, so a
cell of another family never pays for them, and a launcher that reads
``shape`` stays off JAX."""

from __future__ import annotations

import functools


def _seq_len(config: dict) -> int:
    """The training context (``assumed``): ``max_position_embeddings``
    is the serving limit, 163,840."""
    return config["assumed"]["sequence_length"]


def layer_kinds(config: dict) -> list:
    """The feed-forward of published layers 0 to ``num_hidden_layers``
    - 1: every mixer is latent attention."""
    if config["moe_layer_freq"] != 1:
        raise ValueError("family deepseek_v2: an expert layer on every layer")
    dense = config["first_k_dense_replace"]
    return [
        "dense" if i < dense else "moe"
        for i in range(config["num_hidden_layers"])
    ]


def mla_matmul_params(config: dict) -> int:
    e, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, d_v = config["kv_lora_rank"], config["v_head_dim"]
    return (
        e * heads * (nope + rope) + e * (rank + rope)
        + rank * heads * (nope + d_v) + heads * d_v * e
    )


def expert_matmul_params(config: dict) -> float:
    """What a token is multiplied by in an expert layer of this share,
    at the load it expects: the router's every output, the shared
    experts, and ``num_experts_per_tok x held / router experts``
    routed experts (6 x 8 / 64 = three quarters of one)."""
    e, width = config["hidden_size"], config["moe_intermediate_size"]
    routed = (
        config["num_experts_per_tok"] * config["n_routed_experts"]
        / config["assumed"]["router_num_experts"]
    )
    return (
        e * config["assumed"]["router_num_experts"]
        + (config["n_shared_experts"] + routed) * 3 * e * width
    )


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/`` (``layer_matmul_params`` the mean over the layers
    held, so that ``layers`` times it is their sum; ``heads`` and
    ``head_dim`` the latent layers' query/key size), and those the
    latent layers' and the expert layer's counts need."""
    e = config["hidden_size"]
    kinds = layer_kinds(config)
    mla = mla_matmul_params(config)
    ffn = {
        "dense": 3 * e * config["intermediate_size"],
        "moe": expert_matmul_params(config),
    }
    return {
        "layers": len(kinds),
        "embd": e,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        "layer_matmul_params": mla + sum(ffn[f] for f in kinds) / len(kinds),
        "layer_kinds": [f"mla+{f}" for f in kinds],
        "mla_layers": len(kinds),
        "dense_layers": kinds.count("dense"),
        "moe_layers": kinds.count("moe"),
        "mla_matmul_params": mla,
        "dense_matmul_params": ffn["dense"],
        "moe_matmul_params": ffn["moe"],
        "v_head_dim": config["v_head_dim"],
        "mla_rope_dim": config["qk_rope_head_dim"],
        "experts_held": config["n_routed_experts"],
        "router_experts": config["assumed"]["router_num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
    }


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed and no row of padding counted
    (``flops.train_flops_per_token`` asks here first): 6 x the matrix
    parameters it passes in each layer held (the latent mixer's four
    projections; the dense MLP, or the expert layer's router, its
    shared experts and the routed experts at the load this share
    expects, ``expert_matmul_params``) and in the loss head's rows; and
    for each layer the causal half of QK^T at the query/key head size
    and of PV at the value head size, forward and backward
    (``flops.mean_keys``: 4,096.5 of 8,192). The rotation is
    elementwise and counts nothing."""
    from benchmark import flops

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


def build(config: dict) -> dict:
    from benchmark.reference import deepseek_v2 as reference
    from dlrover_tpu.models import deepseek_v2 as model

    assumed = config["assumed"]
    scaling = config["rope_scaling"]
    if config["tie_word_embeddings"] or config["q_lora_rank"] is not None:
        raise ValueError("family deepseek_v2: untied head, plain query")
    if config["attention_bias"] or scaling["type"] != "yarn":
        raise ValueError("family deepseek_v2: no bias, YaRN's rotation")
    if config["scoring_func"] != "softmax" or not config["seq_aux"]:
        raise ValueError(
            "family deepseek_v2: the published softmax router and its "
            "sequence-wise balance loss"
        )
    if (config["topk_method"], config["n_group"], config["topk_group"]) != (
        "greedy", 1, 1
    ):
        raise ValueError("family deepseek_v2: a greedy choice in one group")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("family deepseek_v2: a key and value a head")
    kinds = layer_kinds(config)
    cfg = model.DeepseekV2Config(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        n_layer=len(kinds),
        first_dense=kinds.count("dense"),
        n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"],
        qk_nope=config["qk_nope_head_dim"],
        qk_rope=config["qk_rope_head_dim"],
        v_head=config["v_head_dim"],
        intermediate=config["intermediate_size"],
        n_experts=assumed["router_num_experts"],
        top_k=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=(
            config["n_shared_experts"] * config["moe_intermediate_size"]
        ),
        routed_scale=float(config["routed_scaling_factor"]),
        renorm_top_k=config["norm_topk_prob"],
        first_expert=assumed["first_expert"],
        held=config["n_routed_experts"],
        aux_loss_weight=assumed["aux_loss_alpha"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original=scaling["original_max_position_embeddings"],
        beta_fast=float(scaling["beta_fast"]),
        beta_slow=float(scaling["beta_slow"]),
        mscale=scaling["mscale"],
        mscale_all_dim=scaling["mscale_all_dim"],
        rms_eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"],
        jitter=assumed["init_jitter"],
        remat=assumed["remat"],
    )
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/deepseek_v2_cells: the cell with one path
        # of the program broken on purpose, which ``correct`` has to
        # refuse.
        from benchmark.controls import deepseek_v2 as controls

        loss = controls.broken(config["control"], loss)

    def reference_loss(params, tokens, targets):
        # The reference turns adjacent pairs, as published; the
        # program's rotated columns are the split-halves order of the
        # same weights (models/deepseek_v2.py, ``rope_columns``).
        return reference.loss(
            model.published_layout(params, cfg), tokens, targets,
            config=config,
        )

    return {
        "cfg": cfg,
        "init": functools.partial(model.init_params, cfg=cfg),
        "loss": loss,
        "axes": model.param_logical_axes(cfg),
        "seq_len": cfg.block_size,
        "vocab": config["vocab_size"],
        "reference_loss": reference_loss,
    }
