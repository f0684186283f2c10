"""Latent-attention expert model (the DeepSeek-V2 shape, arXiv:2405.04434;
huggingface ``deepseek_v2``): every layer's mixer is multi-head latent
attention whose shared key part is rotated by position (decoupled
RoPE, under YaRN where the configuration scales its context); the
feed-forward is a dense SwiGLU MLP in the leading layers and an
expert layer with shared experts in every other.

    h = wte[tokens]
    every layer:  h = h + mla(rms_1(h));  h = h + ffn(rms_2(h))
    logits = rms_f(h) @ lm_head^T                      (untied head)

* ``mla`` (models/mla.py holds the mixer, which models/kimi_linear.py
  shares): ``q = u w_q`` in heads of ``[q_n | q_r]`` (``qk_nope |
  qk_rope`` columns; no compressed query: ``q_lora_rank`` null); the
  latent ``[c | k_r] = u w_kva``, ``k_r`` one vector a token and no
  head's; ``[k_n | v] = rms(c) w_kvb`` a head (``k_r`` is not normed);
  ``q_r`` and ``k_r`` are turned by the token's position, ``k_r`` once
  on ``[B, T, qk_rope]`` before the heads share it; a head's key is
  ``[k_n | k_r]``; causal softmax attention with values of ``v_head``
  columns (ops/flash_attention.py takes the two head sizes) at scale
  ``(qk_nope + qk_rope)^-0.5 x m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1`` (YaRN's correction of the logits' size, which this
  family puts into the scale and not into cos and sin: those carry
  ``m(mscale) / m(mscale_all_dim)``, 1 as published); ``w_o``.
* the rotation's frequencies are ``mellum.rope_table``'s (plain, or
  YaRN's blend). **The layout**: the published code turns the channel
  pairs ``(2i, 2i + 1)`` of the 64 rotated columns. Here
  ``llama.apply_rope`` turns ``(i, i + 32)``, the split-halves form
  that needs no shuffle of neighbouring lanes, and the weights are
  this layout's: column ``j`` of a rotated part here is published
  column :func:`rope_columns` ``[j]`` (the even ones, then the odd).
  A dot product of a rotated query and key part is the same in both;
  ``benchmark/families/deepseek_v2.py`` hands the plain reference,
  which turns adjacent pairs, these weights through the inverse
  (:func:`published_layout`), and ``tests/test_deepseek_v2.py`` holds
  the two equal. A loader of published weights applies
  ``rope_columns`` to the rotated columns of ``w_q`` (a head) and of
  ``w_kva``.
* the expert layer is models/moe.py's held path: a softmax router
  over all ``n_experts``, the ``top_k`` largest chosen greedily (one
  group) and weighing as they are (no renormalisation, factor 1), this
  chip's ``held`` experts from ``first_expert`` on, and the shared
  experts as one SwiGLU of their summed width.
* the load-balancing loss (``seq_aux``) is formed beside the layer,
  sequence by sequence: ``alpha x mean over sequences of sum_e f_e
  P_e``, ``f_e`` the sequence's pairs sent to expert e times
  ``n_experts / (T top_k)`` and ``P_e`` its mean probability: per
  sequence ``moe.router_losses`` over ``top_k``. A layer's term is
  added as it is, not averaged over the layers. **Departure**: the
  published code adds this term's gradient and leaves its value out
  of the loss it reports; here it is part of the loss (the gradients
  are the same).
* the dense MLP is ``llama.swiglu``, the norms ``llama._rms_norm`` and
  the loss ``fused_cross_entropy``: shared with the other families.

The layers are calls in a row of one traced program, each with a
parameter subtree of its own (``layers/<index>_mla_<ffn>``), as
models/kimi_linear.py's are: a chip holds a share of a short stack
(six layers in the benchmark's cell) of two kinds. Event
``hybrid.pattern`` says so.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.models import llama, mellum, mla, moe

Params = Dict[str, Any]
DENSE, MOE = "dense", "moe"


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's correction of the attention logits' size for a context
    scaled by ``factor`` (1 where it is not scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_columns(width: int) -> np.ndarray:
    """Published column of each column of a rotated part here: the
    even ones, then the odd (``[0, 2, ..., 62, 1, 3, ..., 63]``)."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The defaults are DeepSeek-V2-Lite's published values."""

    vocab_size: int = 102400
    block_size: int = 8192  # the training context; 163,840 is the serving limit
    n_layer: int = 27
    first_dense: int = 1  # leading layers with the dense MLP
    n_embd: int = 2048
    n_head: int = 16
    kv_rank: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    intermediate: int = 10944
    n_experts: int = 64
    top_k: int = 6
    expert_hidden: int = 1408
    shared_hidden: int = 2 * 1408  # the two shared experts side by side
    routed_scale: float = 1.0
    renorm_top_k: bool = False
    first_expert: int = 0
    held: int = 0  # 0: all n_experts
    aux_loss_weight: float = 0.001  # alpha
    rope_theta: float = 10000.0
    # YaRN's (``rope_scaling``); factor 1: the plain rotation.
    rope_factor: float = 40.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    rms_eps: float = 1e-6
    # Initial values: normal(0, init_std) matrices, the projections
    # back into the residual stream over sqrt(2 x layers); the norm
    # gains are drawn around 1 (``jitter``), not set to it: a gain of
    # exactly 1 would hide its own omission from a check against a
    # reference.
    init_std: float = 0.02
    jitter: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: Any = True  # accelerate/remat.py's named policies
    use_flash_attention: Optional[bool] = None

    def __post_init__(self):
        if not 0 <= self.first_dense <= self.n_layer or not self.n_layer:
            raise ValueError(
                f"{self.first_dense} dense layers of {self.n_layer}"
            )
        if self.qk_rope % 2:
            raise ValueError(f"a rotated part of {self.qk_rope} columns")

    @property
    def ffns(self) -> Tuple[str, ...]:
        return (DENSE,) * self.first_dense + (MOE,) * (
            self.n_layer - self.first_dense
        )

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return tuple(f"{i}_mla_{f}" for i, f in enumerate(self.ffns))

    @property
    def d_qk(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def softmax_mscale(self) -> float:
        return yarn_mscale(self.rope_factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        return self.d_qk ** -0.5 * self.softmax_mscale ** 2

    @property
    def rope(self) -> mellum.Rope:
        """The rotated part's table, for ``mellum.rope_table``; cos
        and sin carry ``m(mscale) / m(mscale_all_dim)``."""
        if self.rope_factor == 1:
            return mellum.Rope(theta=self.rope_theta)
        return mellum.Rope(
            rope_type="yarn", theta=self.rope_theta,
            factor=self.rope_factor,
            original_max_position=self.rope_original,
            beta_fast=self.beta_fast, beta_slow=self.beta_slow,
            attention_factor=yarn_mscale(self.rope_factor, self.mscale)
            / self.softmax_mscale,
        )

    @property
    def moe_cfg(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            n_embd=self.n_embd, n_experts=self.n_experts,
            expert_hidden=self.expert_hidden, top_k=self.top_k,
            aux_loss_weight=0.0, z_loss_weight=0.0, dtype=self.dtype,
            gated=True, renorm_top_k=self.renorm_top_k,
            scoring="softmax", routed_scale=self.routed_scale,
            shared_hidden=self.shared_hidden,
            first_expert=self.first_expert,
            held=self.held or self.n_experts,
        )

    @staticmethod
    def tiny() -> "DeepseekV2Config":
        """Test size: a dense layer and two expert layers; 2 of 16
        experts held (an eighth), 4 a token; a rotated part of 16
        columns whose YaRN ramp spans three frequencies."""
        return DeepseekV2Config(
            vocab_size=256, block_size=64, n_layer=3, first_dense=1,
            n_embd=64, n_head=4, kv_rank=24, qk_nope=16, qk_rope=16,
            v_head=16, intermediate=128, n_experts=16, top_k=4,
            expert_hidden=32, shared_hidden=64, first_expert=4, held=2,
            rope_theta=100.0, rope_factor=4.0, rope_original=32,
            beta_fast=4.0, beta_slow=1.0, dtype=jnp.float32, remat=False,
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: DeepseekV2Config, ffn: str) -> Dict:
    """Leaf path -> (shape, logical axes) of one layer."""
    E, H = cfg.n_embd, cfg.n_head
    shapes = {
        "rms1": ((E,), (None,)),
        "rms2": ((E,), (None,)),
        "wq": ((E, H * cfg.d_qk), ("embed", "heads")),
        "w_kva": ((E, cfg.kv_rank + cfg.qk_rope), ("embed", None)),
        "kv_norm": ((cfg.kv_rank,), (None,)),
        "w_kvb": ((cfg.kv_rank, H * (cfg.qk_nope + cfg.v_head)),
                  (None, "heads")),
        "w_o": ((H * cfg.v_head, E), ("heads", "embed")),
    }
    if ffn == DENSE:
        I = cfg.intermediate
        shapes.update(
            w_gate=((E, I), ("embed", "mlp")),
            w_up=((E, I), ("embed", "mlp")),
            w_down=((I, E), ("mlp", "embed")),
        )
        return shapes
    n, X, S = cfg.moe_cfg.experts_here, cfg.expert_hidden, cfg.shared_hidden
    axes = moe.moe_logical_axes(True, False, True)
    for leaf, shape in (
        ("router", (E, cfg.n_experts)), ("wi", (n, E, X)), ("wg", (n, E, X)),
        ("wo", (n, X, E)),
    ):
        shapes[f"moe/{leaf}"] = (shape, axes[leaf])
    for leaf, shape in (
        ("w_gate", (E, S)), ("w_up", (E, S)), ("w_down", (S, E))
    ):
        shapes[f"moe/shared/{leaf}"] = (shape, axes["shared"][leaf])
    return shapes


def _init_leaf(key, path: str, shape, cfg: DeepseekV2Config):
    name = path.split("/")[-1]
    f32 = jnp.float32
    if name in ("rms1", "rms2", "kv_norm"):
        return 1.0 + cfg.jitter * jax.random.normal(key, shape, f32)
    std = cfg.init_std
    if name in ("w_o", "w_down", "wo"):
        std = std / np.sqrt(2 * cfg.n_layer)
    value = jax.random.normal(key, shape, f32) * std
    # The router stays float32: tiny, and a top-k choice flips on the
    # last bits.
    return value if name == "router" else value.astype(cfg.dtype)


def init_params(key: jax.Array, cfg: DeepseekV2Config) -> Params:
    k_table, k_head, k_final, k_layers = jax.random.split(key, 4)
    layers = {}
    for name, ffn, k_layer in zip(
        cfg.layer_names, cfg.ffns, jax.random.split(k_layers, cfg.n_layer)
    ):
        shapes = _layer_shapes(cfg, ffn)
        layers[name] = mla.nested({
            path: _init_leaf(k, path, shape, cfg)
            for (path, (shape, _)), k in zip(
                sorted(shapes.items()),
                jax.random.split(k_layer, len(shapes)),
            )
        })

    def table(k):
        rows = jax.random.normal(k, (cfg.vocab_size, cfg.n_embd), jnp.float32)
        return (rows * cfg.init_std).astype(cfg.dtype)

    return {
        "wte": table(k_table),
        "layers": layers,
        "rmsf": 1.0 + cfg.jitter * jax.random.normal(
            k_final, (cfg.n_embd,), jnp.float32
        ),
        "lm_head": table(k_head),
    }


def param_logical_axes(cfg: DeepseekV2Config) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table: ``embed`` on fsdp, ``heads`` / ``mlp`` / ``vocab`` on
    tensor, ``expert`` on expert)."""
    return {
        "wte": ("vocab", "embed"),
        "layers": {
            name: mla.nested({
                path: axes
                for path, (_, axes) in _layer_shapes(cfg, ffn).items()
            })
            for name, ffn in zip(cfg.layer_names, cfg.ffns)
        },
        "rmsf": (None,),
        "lm_head": ("vocab", "embed"),
    }


def published_layout(params: Params, cfg: DeepseekV2Config) -> Params:
    """``params`` with the rotated columns of every layer's ``wq`` (a
    head) and ``w_kva`` in the published order, adjacent pairs
    ``(2i, 2i + 1)``: the inverse of :func:`rope_columns`."""
    back = np.argsort(rope_columns(cfg.qk_rope))
    head = np.concatenate([np.arange(cfg.qk_nope), cfg.qk_nope + back])
    q_cols = (np.arange(cfg.n_head)[:, None] * cfg.d_qk + head).reshape(-1)
    kva_cols = np.concatenate([np.arange(cfg.kv_rank), cfg.kv_rank + back])
    return dict(params, layers={
        name: dict(lp, wq=lp["wq"][:, q_cols], w_kva=lp["w_kva"][:, kva_cols])
        for name, lp in params["layers"].items()
    })


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def balance_loss(h, router, cfg: DeepseekV2Config):
    """One layer's load-balancing loss on the normed ``h`` [B, T, E],
    weighted: each sequence's ``sum_e f_e P_e`` (``moe.router_losses``
    on its tokens alone, over ``top_k``), averaged over the sequences.
    The held path (models/moe.py) returns none. The router's product,
    softmax and choice are formed here a second time, small beside a
    layer, under a scope of their own, ``moe_balance``, so that
    ``moe_route`` reads the held path's routing alone."""

    def of_sequence(tokens):
        logits = moe.router_logits(tokens, router)
        probs = jax.nn.softmax(logits, axis=-1)
        _, experts = moe.top_k_route(probs, cfg.top_k, False)
        counts = moe.expert_counts(experts, cfg.n_experts)
        return moe.router_losses(logits, probs, counts)["aux_loss"]

    with jax.named_scope("moe_balance"):
        per_sequence = jax.vmap(of_sequence)(h) / cfg.top_k
    return cfg.aux_loss_weight * jnp.mean(per_sequence)


def _layer(x, lp, attn_fn, *, cfg: DeepseekV2Config, ffn: str, cos, sin):
    """One layer; returns (x, the layer's weighted balance loss)."""
    with jax.named_scope("attn"):
        h = llama._rms_norm(x, lp["rms1"], cfg.rms_eps)
        obs.event(
            "mla.attn", d_qk=cfg.d_qk, d_v=cfg.v_head, padded_to=cfg.d_qk,
            heads=cfg.n_head, rotated=True, rope_dim=cfg.qk_rope,
            scale=cfg.softmax_scale, mscale=cfg.softmax_mscale,
        )
        with jax.named_scope("mla"):
            x = x + mla.mla_mixer(
                h, lp, attn_fn, cfg, cfg.softmax_scale, (cos, sin)
            )
    with jax.named_scope("mlp"):
        h = llama._rms_norm(x, lp["rms2"], cfg.rms_eps)
        if ffn == DENSE:
            return x + llama.swiglu(h, lp), jnp.zeros((), jnp.float32)
        y, _ = moe.moe_mlp(lp["moe"], h, cfg.moe_cfg)
        return x + y, balance_loss(h, lp["moe"]["router"], cfg)


def default_attention_for(cfg: DeepseekV2Config) -> Callable:
    """The chooser every family uses (flash on the TPU from 512 tokens
    up); the latent mixer gives it its scale."""
    from dlrover_tpu.models import gpt

    return gpt.default_attention_for(cfg)


def backbone_with_aux(
    params: Params,
    tokens: jax.Array,
    cfg: DeepseekV2Config,
    attn_fn: Optional[Callable] = None,
) -> tuple:
    """[B, T] tokens -> ([B, T, E] hidden after the final norm, the
    balance losses summed over the expert layers)."""
    from dlrover_tpu.accelerate.remat import wire_block

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    obs.event(
        "hybrid.pattern", layer_types=[f"mla+{f}" for f in cfg.ffns],
        mla_layers=cfg.n_layer, dense_layers=cfg.ffns.count(DENSE),
        moe_layers=cfg.ffns.count(MOE), in_line=cfg.n_layer, scanned=0,
        rotation=cfg.rope.rope_type,
    )
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
    cos, sin = mellum.rope_table(cfg.rope, cfg.qk_rope, tokens.shape[1])
    blocks = {
        ffn: wire_block(
            functools.partial(_layer, cfg=cfg, ffn=ffn, cos=cos, sin=sin),
            cfg.remat, attn_fn,
        )
        for ffn in set(cfg.ffns)
    }
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("layers"):
        for name, ffn in zip(cfg.layer_names, cfg.ffns):
            x, layer_aux = blocks[ffn](x, params["layers"][name])
            aux = aux + layer_aux
    return llama._rms_norm(x, params["rmsf"], cfg.rms_eps), aux


def loss_fn(params, tokens, targets, cfg: DeepseekV2Config,
            attn_fn=None) -> jax.Array:
    x, aux = backbone_with_aux(params, tokens, cfg, attn_fn)
    logp = jax.nn.log_softmax(llama.head_logits(params, x), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll) + aux


def loss_fn_fused(params, tokens, targets, cfg: DeepseekV2Config,
                  attn_fn=None, num_chunks: int = 8) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x, aux = backbone_with_aux(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        loss = fused_cross_entropy(
            x.reshape(n, -1), params["lm_head"], targets.reshape(n),
            num_chunks,
        )
    return loss + aux
