"""Family ``phi4_flash``: a configuration file with the published
Phi-4-mini-flash keys -> models/phi4_flash.py's init, loss and logical
axes (Mamba-1 mixers and differential attention under a window in the
self-decoder, one full attention layer, gated memory units and
cross-attention onto that layer's keys and values behind it; the
layers held are ``num_hidden_layers`` published layers from
``assumed.first_layer`` on), the plain reference that goes with it,
and the sizes the yardstick's counts need. Nothing is imported at the
top: the model, the scan and the reference are loaded by ``build``
alone, so a cell of another family never pays for them, and a launcher
that reads ``shape`` stays off JAX."""

from __future__ import annotations

import functools

STATE_SPACE = ("mamba", "mamba_memory")
# What a pair of (channel, state) costs a token in the scan's forward:
# dt x A, its exponential, (dt x) x B, the decay times the state, the
# sum, the state times C, the sum over the states.
SCAN_OPS_A_PAIR = 7


def _seq_len(config: dict) -> int:
    """The training context (``assumed``): ``max_position_embeddings``
    is the serving limit, 262,144."""
    return config["assumed"]["sequence_length"]


def published_layers(config: dict) -> int:
    """The depth the rule is applied to: the published one where the
    file holds a slice."""
    return config.get("reduced_from", {}).get(
        "num_hidden_layers", config["num_hidden_layers"]
    )


def layer_kinds(config: dict) -> list:
    """The kinds of the layers held, by the published rule: of ``n``
    layers, ``l`` even has a state-space mixer and ``l`` odd
    attention; below ``n / 2`` Mamba and window attention, at ``n /
    2`` the Mamba layer whose scan output is the memory, at ``n / 2 +
    1`` full attention (the shared keys and values), beyond them
    gated memory units and cross-attention."""
    n, per = published_layers(config), config["mb_per_layer"]
    if n % 4 or per != 2:
        raise ValueError(f"family phi4_flash: {n} layers, mb_per_layer {per}")
    first = config["assumed"]["first_layer"]
    held = range(first, first + config["num_hidden_layers"])
    if held.stop > n:
        raise ValueError(f"family phi4_flash: no layer {held.stop - 1} of {n}")

    def kind(l):
        if l < n // 2:
            return "mamba" if l % per == 0 else "attn_window"
        if l == n // 2:
            return "mamba_memory"
        if l == n // 2 + 1:
            return "attn_full"
        return "gmu" if l % per == 0 else "attn_cross"

    return [kind(l) for l in held]


def matmul_params(config: dict, kind: str) -> int:
    """What a token is multiplied by in a layer of ``kind``: the
    mixer's matrices and the MLP's three (vectors, the depthwise
    convolution and ``A`` multiply element-wise and are not here)."""
    e, assumed = config["hidden_size"], config["assumed"]
    inner = assumed["expand"] * e
    mlp = 3 * e * config["intermediate_size"]
    if kind in STATE_SPACE:
        rank, n = assumed["dt_rank"], assumed["d_state"]
        return e * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * e + mlp
    if kind == "gmu":
        return 2 * e * inner + mlp
    d = e // config["num_attention_heads"]
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    if kind == "attn_cross":
        return e * q + q * e + mlp
    return e * (q + 2 * kv) + q * e + mlp


def shape(config: dict) -> dict:
    """The nine sizes every family gives ``flops.py`` and
    ``kernel_work/`` and those this family's counts need. ``heads``,
    ``kv_heads``, ``head_dim`` and ``v_head_dim`` are one flash call's:
    differential attention calls the kernel twice a layer
    (``flash_calls_per_layer``), each time with a query head a pair
    (20), keys ``head_dim`` wide and the pair's two values side by
    side (128); the window layers are ``sliding_layers`` and the full
    and cross layers, which make the same call, ``full_layers``."""
    e, assumed = config["hidden_size"], config["assumed"]
    kinds = layer_kinds(config)
    by_kind = {kind: matmul_params(config, kind) for kind in set(kinds)}
    d = e // config["num_attention_heads"]
    return {
        "layers": len(kinds),
        "embd": e,
        "heads": config["num_attention_heads"] // 2,
        "kv_heads": config["num_key_value_heads"] // 2,
        "head_dim": d,
        "v_head_dim": 2 * d,
        "vocab_rows": config["vocab_size"],
        "seq_len": _seq_len(config),
        "window": None,
        "layer_matmul_params": sum(by_kind[k] for k in kinds) / len(kinds),
        "kinds": kinds,
        "matmul_params_by_kind": by_kind,
        "sliding_layers": kinds.count("attn_window"),
        "full_layers": kinds.count("attn_full") + kinds.count("attn_cross"),
        "sliding_window": config["sliding_window"],
        "full_window": None,
        "flash_calls_per_layer": 2,
        "mamba_layers": sum(k in STATE_SPACE for k in kinds),
        "gmu_layers": kinds.count("gmu"),
        "scan_channels": assumed["expand"] * e,
        "scan_states": assumed["d_state"],
        "scan_chunk": assumed["scan_chunk"],
    }


def scan_flops_per_token(shape: dict) -> float:
    """One Mamba layer's selective scan, forward: ``SCAN_OPS_A_PAIR``
    for each (channel, state) pair and ``dt x`` and ``D x`` a
    channel."""
    return (
        SCAN_OPS_A_PAIR * shape["scan_channels"] * shape["scan_states"]
        + 3.0 * shape["scan_channels"]
    )


def flops_per_token(shape: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed (``flops.train_flops_per_token`` asks here first): 6 x
    the matrices of each layer held (by its own kind) and of the loss
    head's rows; for each attention layer the causal half of both
    maps' QK^T (64 wide) and PV (128 wide) under its own window,
    forward and backward (three times the forward of
    ``flash_calls_per_layer`` calls); for each Mamba layer three times
    the scan's forward operations (the backward forms the cotangent's
    scan and the products with the states: twice the forward's)."""
    from benchmark import flops

    matrices = (
        sum(shape["matmul_params_by_kind"][k] for k in shape["kinds"])
        + shape["vocab_rows"] * shape["embd"]
    )
    keys = sum(
        shape[f"{kind}_layers"]
        * flops.mean_keys(shape["seq_len"], shape[f"{kind}_window"])
        for kind in ("sliding", "full")
    )
    attention = (
        3.0 * shape["flash_calls_per_layer"] * 2.0 * shape["heads"]
        * (shape["head_dim"] + shape["v_head_dim"]) * keys
    )
    scan = 3.0 * shape["mamba_layers"] * scan_flops_per_token(shape)
    return 6.0 * matrices + attention + scan


def build(config: dict) -> dict:
    from benchmark.reference import phi4_flash as reference
    from dlrover_tpu.models import phi4_flash as model

    assumed = config["assumed"]
    if not config["tie_word_embeddings"] or config["mlp_bias"]:
        raise ValueError("family phi4_flash: tied table, no MLP bias")
    if config["lm_head_bias"] or config["hidden_act"] != "silu":
        raise ValueError("family phi4_flash: no head bias, silu")
    if not (assumed["attention_bias"] and assumed["conv_bias"]):
        raise ValueError("family phi4_flash: attention and convolution bias")
    if assumed["dt_rank"] != -(-config["hidden_size"] // 16):
        raise ValueError("family phi4_flash: dt_rank is ceil(hidden / 16)")
    cfg = model.Phi4FlashConfig.stack(
        published_layers(config), assumed["first_layer"],
        config["num_hidden_layers"],
        vocab_size=config["vocab_size"],
        block_size=_seq_len(config),
        n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        intermediate=config["intermediate_size"],
        sliding_window=config["sliding_window"],
        ln_eps=config["layer_norm_eps"],
        d_state=assumed["d_state"],
        d_conv=assumed["d_conv"],
        expand=assumed["expand"],
        scan_chunk=assumed["scan_chunk"],
        init_std=assumed["initializer_range"],
        dt_min=assumed["dt_min"],
        dt_max=assumed["dt_max"],
        a_scale=assumed.get("A_scale", 1.0),
        lambda_std=assumed["lambda_std"],
        subln_gain=assumed.get("subln_gain", 1.0),
        jitter=assumed["init_jitter"],
        remat=assumed["remat"],
    )
    if list(cfg.kinds) != layer_kinds(config):
        raise ValueError("family phi4_flash: the model's rule is another")
    loss = functools.partial(model.loss_fn_fused, cfg=cfg)
    if config.get("control"):
        # benchmark/controls/phi4_flash_cells: the cell with one path
        # of the program broken on purpose, which ``correct`` has to
        # refuse.
        from benchmark.controls import phi4_flash as controls

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
