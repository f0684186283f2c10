"""Family ``llama``: a configuration file with the published Llama /
Mistral keys -> models/llama.py's init, loss and logical axes, the
plain reference that goes with it, and the sizes the yardstick's
counts need. Nothing is imported at the top: the resume cell's parent
reads ``shape`` and must stay off JAX."""

from __future__ import annotations

import functools


def _seq_len(config: dict) -> int:
    return config.get("assumed", {}).get(
        "sequence_length", config["max_position_embeddings"]
    )


def shape(config: dict) -> dict:
    """The sizes ``flops.py`` and ``kernel_work/`` count from, under
    the names every family gives them."""
    e = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d = e // heads
    i = config["intermediate_size"]
    return {
        "layers": config["num_hidden_layers"],
        "embd": e,
        "heads": heads,
        "kv_heads": kv,
        "head_dim": d,
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": config.get("sliding_window"),
        # wq, wo E^2 each; wk, wv E x (kv x d) each; gate, up, down
        "layer_matmul_params": 2 * e * e + 2 * e * kv * d + 3 * e * i,
    }


def build(config: dict) -> dict:
    from benchmark.reference import llama as reference
    from dlrover_tpu.models import llama

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
        sliding_window=config.get("sliding_window"),
        remat=config.get("assumed", {}).get("remat", True),
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
