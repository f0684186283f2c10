"""Family ``mellum``: a configuration file with the published Mellum 2
keys -> models/mellum.py's init, loss and logical axes (sliding-window
and full attention layers by the published ``layer_types``, each kind
with the rotation ``rope_parameters`` gives it, heads of ``head_dim``
columns, and on every layer an expert layer with a softmax router of
which this chip holds ``num_experts`` from ``assumed.first_expert``
on), the plain reference that goes with it, and the sizes the
yardstick's counts need. Nothing is imported at the top: the model and
the reference are loaded by ``build`` alone, so a cell of another
family never pays for them, and a launcher that reads ``shape`` stays
off JAX."""

from __future__ import annotations

import functools

SLIDING, FULL = "sliding_attention", "full_attention"


def _seq_len(config: dict) -> int:
    """The training context (``assumed``): ``max_position_embeddings``
    is the serving limit, 131,072."""
    return config["assumed"]["sequence_length"]


def layer_kinds(config: dict) -> list:
    """The kinds of the layers held: the first ``num_hidden_layers``
    entries of the published ``layer_types``, which the file keeps
    whole."""
    kinds = list(config["layer_types"][: config["num_hidden_layers"]])
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {
        SLIDING, FULL
    }:
        raise ValueError(f"family mellum: layer_types gives {kinds!r}")
    return kinds


def attention_matmul_params(config: dict) -> int:
    """What a token is multiplied by in an attention layer: the query
    and output projections at ``heads x head_dim`` columns and the key
    and value projections at ``kv heads x head_dim``."""
    e, d = config["hidden_size"], config["head_dim"]
    return 2 * e * d * (
        config["num_attention_heads"] + config["num_key_value_heads"]
    )


def expert_matmul_params(config: dict) -> float:
    """What a token is multiplied by in an expert layer of this share,
    at the load it expects: the router's every output and
    ``num_experts_per_tok x held / router experts`` experts (8 x 16 /
    64 = two a token)."""
    e = config["hidden_size"]
    routed = (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["assumed"]["router_num_experts"]
    )
    return (
        e * config["assumed"]["router_num_experts"]
        + routed * 3 * e * config["moe_intermediate_size"]
    )


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/`` (``window`` None: no one window describes the
    stack, and the counts of this family's own read the layers by kind
    and ``sliding_window``), and those the expert layer's counts
    need."""
    kinds = layer_kinds(config)
    return {
        "layers": len(kinds),
        "embd": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        "layer_matmul_params": (
            attention_matmul_params(config) + expert_matmul_params(config)
        ),
        "sliding_layers": kinds.count(SLIDING),
        "full_layers": kinds.count(FULL),
        "sliding_window": config["sliding_window"],
        "full_window": None,
        "experts_held": config["num_experts"],
        "router_experts": config["assumed"]["router_num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
    }


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed and no row of padding counted
    (``flops.train_flops_per_token`` asks here first): 6 x the matrix
    parameters it passes in each layer held (the attention projections,
    the router's every output, and the held experts at the load this
    share expects, ``expert_matmul_params``) and in the loss head's
    rows; and for each layer the causal QK^T and PV, forward and
    backward, over its own kind's mean number of keys
    (``flops.mean_keys``: 960 of 8,192 under the window of 1,024,
    4,096.5 without)."""
    from benchmark import flops

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


def _rope(model, entry: dict):
    if entry["rope_type"] == "default":
        return model.Rope(theta=entry["rope_theta"])
    return model.Rope(
        rope_type=entry["rope_type"], theta=entry["rope_theta"],
        factor=entry["factor"],
        original_max_position=entry["original_max_position_embeddings"],
        beta_fast=entry["beta_fast"], beta_slow=entry["beta_slow"],
        attention_factor=entry["attention_factor"],
    )


def build(config: dict) -> dict:
    from benchmark.reference import mellum as reference
    from dlrover_tpu.models import mellum as model

    assumed = config["assumed"]
    if config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError("family mellum: untied head, no bias")
    if not config["use_sliding_window"] or config["max_window_layers"]:
        raise ValueError("family mellum: the window from the first layer on")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("family mellum: an expert layer on every layer")
    if assumed["qk_norm"] != "none" or assumed["mtp"] != "left out":
        raise ValueError("family mellum: no q/k norm, no prediction head")
    ropes = config["rope_parameters"]
    cfg = model.MellumConfig(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        layer_types=tuple(layer_kinds(config)),
        n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rope_sliding=_rope(model, ropes[SLIDING]),
        rope_full=_rope(model, ropes[FULL]),
        n_experts=assumed["router_num_experts"],
        top_k=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        renorm_top_k=config["norm_topk_prob"],
        first_expert=assumed["first_expert"],
        held=config["num_experts"],
        aux_loss_weight=assumed["router_aux_loss_coef"],
        rms_eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"],
        jitter=assumed["init_jitter"],
        remat=assumed["remat"],
    )
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/mellum_cells: the cell with one path of
        # the program broken on purpose, which ``correct`` has to refuse.
        from benchmark.controls import mellum as controls

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
