"""Family ``gpt``: a configuration file -> models/gpt.py's init, loss
and logical axes, the plain reference that goes with it, and the sizes
the yardstick's counts need. Nothing is imported at the top: the
resume cell's parent reads ``shape`` and must stay off JAX."""

from __future__ import annotations

import functools


def shape(config: dict) -> dict:
    """The sizes ``flops.py`` and ``kernel_work/`` count from, under
    the names every family gives them."""
    e = config["n_embd"]
    heads = config["n_head"]
    return {
        "layers": config["n_layer"],
        "embd": e,
        "heads": heads,
        "kv_heads": heads,
        "head_dim": e // heads,
        # The loss head multiplies every row of the (padded) table.
        "vocab_rows": config.get("assumed", {}).get(
            "padded_vocab_size", config["vocab_size"]
        ),
        "seq_len": config["n_positions"],
        "window": None,
        # wqkv 3E^2 + wo E^2 + wi 4E^2 + wo2 4E^2
        "layer_matmul_params": 12 * e * e,
    }


def build(config: dict) -> dict:
    from benchmark.reference import gpt as reference
    from dlrover_tpu.models import gpt

    assumed = config.get("assumed", {})
    cfg = gpt.GPTConfig(
        vocab_size=assumed.get("padded_vocab_size", config["vocab_size"]),
        block_size=config["n_positions"],
        n_layer=config["n_layer"],
        n_head=config["n_head"],
        n_embd=config["n_embd"],
        remat=assumed.get("remat", True),
    )
    return {
        "cfg": cfg,
        "init": functools.partial(gpt.init_params, cfg=cfg),
        "loss": functools.partial(gpt.loss_fn_fused, cfg=cfg),
        "axes": gpt.param_logical_axes(cfg),
        "seq_len": cfg.block_size,
        "vocab": config["vocab_size"],
        "reference_loss": functools.partial(reference.loss, config=config),
    }
