"""Family ``ouro``: a configuration file with the published Ouro keys
-> models/ouro.py's init, loss and logical axes (one stack of layers
run ``total_ut_steps`` times a step on shared weights, a learned exit
gate, a loss the gate weighs over the passes), the plain reference that
goes with it, and the sizes the yardstick's counts need. Nothing is
imported at the top but another family's ``_seq_len``: a launcher
that reads ``shape`` stays off JAX."""

from __future__ import annotations

import functools

from benchmark.families.llama import _seq_len  # no JAX there either


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/``, ``layers`` the layers HELD (``flops.py``'s own
    count then multiplies a token by each once, as it does for every
    family), and ``ut_steps``, the times a step runs them
    (``flops_per_token`` below counts with it)."""
    e = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d = config["head_dim"]
    return {
        "layers": config["num_hidden_layers"],
        "embd": e,
        "heads": heads,
        "kv_heads": kv,
        "head_dim": d,
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        # wq, wo E x (heads x d) each; wk, wv E x (kv x d); gate, up, down
        "layer_matmul_params": (
            2 * e * heads * d + 2 * e * kv * d
            + 3 * e * config["intermediate_size"]
        ),
        "ut_steps": config["total_ut_steps"],
    }


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed (``flops.train_flops_per_token`` asks here first): a
    token is multiplied by every matrix of every layer held once a
    pass, and by the head's rows once a pass (the loss reads every
    pass's logits): 6 x ``ut_steps`` x (``layers`` x
    ``layer_matmul_params`` + ``vocab_rows`` x ``embd``); attention's
    causal half of QK^T and PV, forward and backward, is ``flops.py``'s
    count a layer, once a pass. The exit gate's ``embd``
    multiplications a pass are left out."""
    from benchmark import flops

    matrices = (
        shape["layers"] * shape["layer_matmul_params"]
        + shape["vocab_rows"] * shape["embd"]
    )
    attention = (
        12.0 * shape["layers"] * shape["heads"] * shape["head_dim"]
        * flops.mean_keys(shape["seq_len"], shape["window"])
    )
    return shape["ut_steps"] * (6.0 * matrices + attention)


def build(config: dict) -> dict:
    from benchmark.reference import ouro as reference
    from dlrover_tpu.models import ouro as model

    assumed = config.get("assumed", {})
    e, heads = config["hidden_size"], config["num_attention_heads"]
    if config["head_dim"] * heads != e:
        raise ValueError("family ouro: heads x head_dim is the hidden size")
    if config["tie_word_embeddings"] or config.get("sliding_window"):
        raise ValueError("family ouro: untied tables, full attention")
    if set(config.get("layer_types", ["full_attention"])) != {"full_attention"}:
        raise ValueError("family ouro: every layer is full attention")
    cfg = model.OuroConfig(
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        n_layer=config["num_hidden_layers"],
        n_head=heads,
        n_kv_head=config["num_key_value_heads"],
        n_embd=e,
        intermediate=config["intermediate_size"],
        rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"],
        ut_steps=config["total_ut_steps"],
        exit_entropy_coef=assumed["exit_entropy_coef"],
        init_std=assumed["initializer_range"],
        jitter=assumed["init_jitter"],
        remat=assumed.get("remat", "full"),
    )
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/: the cell with one path of the program
        # broken on purpose, which ``correct`` has to refuse.
        from benchmark.controls import ouro as controls

        loss = controls.broken(config["control"], cfg)
    return {
        "cfg": cfg,
        "init": functools.partial(model.init_params, cfg=cfg),
        "loss": loss,
        "axes": model.param_logical_axes(cfg),
        "seq_len": cfg.block_size,
        "vocab": config["vocab_size"],
        "reference_loss": functools.partial(reference.loss, config=config),
    }
