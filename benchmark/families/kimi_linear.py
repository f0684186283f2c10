"""Family ``kimi_linear``: a configuration file with the published
Kimi Linear keys -> models/kimi_linear.py's init, loss and logical
axes (Kimi Delta Attention layers and latent-attention layers by the
two published lists, a leading dense MLP, then expert layers with a
sigmoid router, a choice bias and a shared expert, of which this chip
holds ``num_experts`` from ``assumed.first_expert`` on), the plain
reference that goes with it, and the sizes the yardstick's counts
need. Nothing is imported at the top: the model, the rule and the
reference are loaded by ``build`` alone, so a cell of another family
never pays for them, and a launcher that reads ``shape`` stays off
JAX."""

from __future__ import annotations

import functools


def _seq_len(config: dict) -> int:
    """The training context (``assumed``): ``model_max_length`` is the
    serving limit, a million tokens."""
    return config["assumed"]["sequence_length"]


def layer_kinds(config: dict) -> list:
    """[(mixer, ffn)] of published layers 1 to ``num_hidden_layers``."""
    linear = config["linear_attn_config"]
    kinds = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if i in linear["kda_layers"]:
            mixer = "kda"
        elif i in linear["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {i} is in neither published list")
        kinds.append(
            (mixer, "dense" if i <= config["first_k_dense_replace"] else "moe")
        )
    return kinds


def kda_matmul_params(config: dict) -> int:
    """What a token is multiplied by in a KDA mixer: the three
    projections, the decay's and the gate's two-step projections,
    beta's, and the projection out."""
    e = config["hidden_size"]
    linear = config["linear_attn_config"]
    inner = linear["num_heads"] * linear["head_dim"]
    rank = config["assumed"]["gate_rank"]
    return (
        3 * e * inner + 2 * (e * rank + rank * inner)
        + e * linear["num_heads"] + inner * e
    )


def mla_matmul_params(config: dict) -> int:
    e, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, d_v = config["kv_lora_rank"], config["v_head_dim"]
    return (
        e * heads * (nope + rope) + e * (rank + rope)
        + rank * heads * (nope + d_v) + heads * d_v * e
    )


def expert_matmul_params(config: dict) -> float:
    """What a token is multiplied by in an expert layer of this
    share, at the load it expects: the router's every output, the
    shared expert, and ``num_experts_per_token x held / router
    experts`` routed experts (8 x 8 / 256 = a quarter of one)."""
    e, width = config["hidden_size"], config["moe_intermediate_size"]
    routed = (
        config["num_experts_per_token"] * config["num_experts"]
        / config["assumed"]["router_num_experts"]
    )
    return (
        e * config["assumed"]["router_num_experts"]
        + (config["num_shared_experts"] + routed) * 3 * e * width
    )


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/`` (``layer_matmul_params`` the mean over the layers
    held, so that ``layers`` times it is their sum; ``heads`` and
    ``head_dim`` the latent layer's query/key size), and those the
    rule's and the stack's counts need."""
    e = config["hidden_size"]
    kinds = layer_kinds(config)
    linear = config["linear_attn_config"]
    mixer = {"kda": kda_matmul_params(config), "mla": mla_matmul_params(config)}
    ffn = {
        "dense": 3 * e * config["intermediate_size"],
        "moe": expert_matmul_params(config),
    }
    total = sum(mixer[m] + ffn[f] for m, f in kinds)
    return {
        "layers": len(kinds),
        "embd": e,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        "layer_matmul_params": total / len(kinds),
        "kda_layers": sum(m == "kda" for m, _ in kinds),
        "mla_layers": sum(m == "mla" for m, _ in kinds),
        "dense_layers": sum(f == "dense" for _, f in kinds),
        "moe_layers": sum(f == "moe" for _, f in kinds),
        "kda_matmul_params": mixer["kda"],
        "mla_matmul_params": mixer["mla"],
        "dense_matmul_params": ffn["dense"],
        "moe_matmul_params": ffn["moe"],
        "kda_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "kda_chunk": config["assumed"]["kda_chunk"],
        "v_head_dim": config["v_head_dim"],
        "experts_held": config["num_experts"],
        "router_experts": config["assumed"]["router_num_experts"],
        "experts_per_token": config["num_experts_per_token"],
        "expert_width": config["moe_intermediate_size"],
    }


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed (``flops.train_flops_per_token`` asks here first): 6 x
    the matrix parameters of each layer held, by its own kind (the KDA
    or latent mixer; the dense MLP, or the expert layer's router,
    shared expert and the routed experts at the load this share
    expects, ``expert_matmul_params``) and of the loss head's rows; for
    each latent layer the causal half of QK^T at the query/key head
    size and of PV at the value head size, forward and backward; for
    each KDA layer three times the chunked rule's forward operations
    (``kernel_work/kda_fwd.py``: the backward's are twice the
    forward's)."""
    from benchmark import flops
    from benchmark.kernel_work import kda_fwd

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


def build(config: dict) -> dict:
    from benchmark.reference import kimi_linear as reference
    from dlrover_tpu.models import kimi_linear as model

    assumed = config["assumed"]
    linear = config["linear_attn_config"]
    if config["tie_word_embeddings"] or config["q_lora_rank"] is not None:
        raise ValueError("family kimi_linear: untied head, plain query")
    if not config["mla_use_nope"] or config["num_expert_group"] != 1:
        raise ValueError("family kimi_linear: no rotation, one expert group")
    if config["moe_router_activation_func"] != "sigmoid":
        raise ValueError("family kimi_linear: the published sigmoid router")
    if linear["num_heads"] != config["num_attention_heads"]:
        raise ValueError("family kimi_linear: one head count for both mixers")
    kinds = layer_kinds(config)
    cfg = model.KimiLinearConfig(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        mixers=tuple(m for m, _ in kinds),
        ffns=tuple(f for _, f in kinds),
        n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"],
        kda_head_dim=linear["head_dim"],
        conv=linear["short_conv_kernel_size"],
        gate_rank=assumed["gate_rank"],
        kv_rank=config["kv_lora_rank"],
        qk_nope=config["qk_nope_head_dim"],
        qk_rope=config["qk_rope_head_dim"],
        v_head=config["v_head_dim"],
        intermediate=config["intermediate_size"],
        n_experts=assumed["router_num_experts"],
        top_k=config["num_experts_per_token"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=(
            config["num_shared_experts"] * config["moe_intermediate_size"]
        ),
        routed_scale=config["routed_scaling_factor"],
        renorm_top_k=config["moe_renormalize"],
        scoring=config["moe_router_activation_func"],
        first_expert=assumed["first_expert"],
        held=config["num_experts"],
        rms_eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"],
        a_min=assumed["A_min"],
        a_max=assumed["A_max"],
        dt_min=assumed["dt_min"],
        dt_max=assumed["dt_max"],
        jitter=assumed["init_jitter"],
        bias_std=assumed["router_bias_std"],
        remat=assumed.get("remat", True),
    )
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/kimi_cells: the cell with one path of the
        # program broken on purpose, which ``correct`` has to refuse.
        from benchmark.controls import kimi_linear as controls

        loss = controls.broken(config["control"], loss)
    return {
        "cfg": cfg,
        "init": functools.partial(model.init_params, cfg=cfg),
        "loss": loss,
        "axes": model.param_logical_axes(cfg),
        "seq_len": cfg.block_size,
        "vocab": config["vocab_size"],
        "reference_loss": functools.partial(reference.loss, config=config),
    }
