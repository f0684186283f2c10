"""Family ``granite_hybrid``: a configuration file with the published
Granite 4.0-H keys -> models/granite_hybrid.py's init, loss and
logical axes (Mamba-2 layers among attention layers by
``layer_types``, the chunked scan of ops/ssd.py), the plain reference
that goes with it, and the sizes the yardstick's counts need. Nothing
is imported at the top: the model, the kernel and the reference are
loaded by ``build`` alone, so a cell of another family never pays for
them, and a launcher that reads ``shape`` stays off JAX."""

from __future__ import annotations

import functools

from benchmark.families.llama import _seq_len  # no JAX there either


def mamba_matmul_params(config: dict) -> int:
    """What a token is multiplied by in a Mamba-2 layer: the
    projection into the mixer ([z | xBC | dt]), the projection out,
    and the MLP's three matrices."""
    e = config["hidden_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return (
        e * (inner + conv_dim + config["mamba_n_heads"]) + inner * e
        + 3 * e * config["shared_intermediate_size"]
    )


def attention_matmul_params(config: dict) -> int:
    """wq, wo E^2 each; wk, wv E x (kv x d) each; gate, up, down."""
    e = config["hidden_size"]
    kv = config["num_key_value_heads"] * (e // config["num_attention_heads"])
    return 2 * e * e + 2 * e * kv + 3 * e * config["shared_intermediate_size"]


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/`` (``layer_matmul_params`` the mean over the layers
    held, so that ``layers`` times it is their sum), and those the
    scan's and the hybrid stack's counts need."""
    e = config["hidden_size"]
    heads = config["num_attention_heads"]
    types = config["layer_types"]
    n_mamba, n_attn = types.count("mamba"), types.count("attention")
    total = (
        n_mamba * mamba_matmul_params(config)
        + n_attn * attention_matmul_params(config)
    )
    return {
        "layers": len(types),
        "embd": e,
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": e // heads,
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        "layer_matmul_params": total / len(types),
        "mamba_layers": n_mamba,
        "attention_layers": n_attn,
        "mamba_matmul_params": mamba_matmul_params(config),
        "attention_matmul_params": attention_matmul_params(config),
        "ssm_heads": config["mamba_n_heads"],
        "ssm_head_dim": config["mamba_d_head"],
        "ssm_state": config["mamba_d_state"],
        "ssm_groups": config["mamba_n_groups"],
        "ssm_chunk": config["mamba_chunk_size"],
    }


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed (``flops.train_flops_per_token`` asks here first): 6 x
    the matrix parameters of each layer held (by its own kind) and of
    the loss head's rows; for each attention layer the causal half of
    QK^T and PV, forward and backward (``flops.py``'s count); for each
    Mamba-2 layer three times the chunked scan's forward operations
    (``kernel_work/ssd_fwd.py``: the backward's are twice the
    forward's)."""
    from benchmark import flops
    from benchmark.kernel_work import ssd_fwd

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


def build(config: dict) -> dict:
    from benchmark.reference import granite_hybrid as reference
    from dlrover_tpu.models import granite_hybrid as model

    assumed = config.get("assumed", {})
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not have num_hidden_layers entries")
    if not config["tie_word_embeddings"] or config["num_local_experts"]:
        raise ValueError("family granite_hybrid: tied table, no routed experts")
    if config["position_embedding_type"] != "nope":
        raise ValueError("family granite_hybrid: no positional embedding")
    cfg = model.GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        layer_types=tuple(config["layer_types"]),
        n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        intermediate=config["shared_intermediate_size"],
        rms_eps=config["rms_norm_eps"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        ssm_conv=config["mamba_d_conv"],
        ssm_chunk=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        init_std=assumed["initializer_range"],
        dt_min=assumed["dt_min"],
        dt_max=assumed["dt_max"],
        a_scale=assumed.get("A_scale", 1.0),
        jitter=assumed["init_jitter"],
        remat=assumed.get("remat", True),
    )
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/: the cell with one path of the program
        # broken on purpose, which ``correct`` has to refuse.
        from benchmark.controls import granite_hybrid as controls

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
