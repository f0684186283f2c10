"""Expert language model whose attention layers differ by position
(the Mellum 2 shape, huggingface ``mellum``): a stack in a published
pattern (``layer_types``) of sliding-window and full attention layers,
each kind with a rotation of its own (``rope_parameters``), every
layer followed by an expert layer.

    h = wte[tokens]
    every layer:  h = h + attention(rms_1(h));  h = h + experts(rms_2(h))
    logits = rms_f(h) @ lm_head^T                      (untied head)

* attention is models/llama.py's attention half: grouped queries, no
  bias, heads of ``head_dim`` columns, which is a size of its own and
  not ``n_embd / n_head`` (the projections are ``n_embd x n_head *
  head_dim`` and back). A ``sliding_attention`` layer sees keys
  ``(i - sliding_window, i]`` and a ``full_attention`` layer every key
  up to ``i``: the window is static where the layer is traced, as the
  flash kernel's is.
* the rotation is the kind's: the plain table (``rope_type``
  ``default``) or YaRN's (:func:`rope_table`), both built once outside
  the stack and applied by ``llama.apply_rope``.
* the expert layer is models/moe.py's held path with a softmax router
  over all ``n_experts``, ``top_k`` a token, the chosen weights over
  their sum, this chip's ``held`` experts from ``first_expert`` on, no
  shared expert and no choice bias. The load-balancing loss is added
  as the Llama family adds it, a layer's over the number of layers.
* the norms are ``llama._rms_norm``, the head ``llama.head_logits`` and
  the loss ``fused_cross_entropy``: shared with the other families.

The unit of the stack is one period of the pattern (the shortest
prefix whose repetition gives ``layer_types``; three sliding layers
and a full one as published). Within a period each layer is a call in
line with its own kind's window and table; the parameter tree holds
one subtree a layer of the period (``periods/<index>_<kind>``), each
leaf shaped ``[periods, ...]``, and more than one period is a
``lax.scan`` over them: one step program whatever the depth. Event
``hybrid.pattern`` says how the stack ran.
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
from dlrover_tpu.models import llama, moe

Params = Dict[str, Any]
SLIDING, FULL = "sliding_attention", "full_attention"
SCOPES = {SLIDING: "attn_window", FULL: "attn_full"}


@dataclasses.dataclass(frozen=True)
class Rope:
    """One entry of the published ``rope_parameters``."""

    rope_type: str = "default"  # or "yarn"
    theta: float = 500000.0
    # YaRN's alone.
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None  # published; yarn has one

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"unknown rope_type {self.rope_type!r}")
        if self.rope_type == "yarn" and (
            self.original_max_position <= 0 or self.attention_factor is None
        ):
            raise ValueError(
                "yarn needs original_max_position and attention_factor"
            )


def rope_table(rope: Rope, head_dim: int, t: int):
    """cos and sin ``[t, head_dim / 2]`` float32 of one kind's rotation.

    ``default``: ``inv_freq_i = theta^(-2i / d)``. ``yarn`` (Peng et
    al. 2023, as huggingface's ``_compute_yarn_parameters`` has it): a
    dimension that turns more than ``beta_fast`` times within the
    original context keeps its frequency, one that turns fewer than
    ``beta_slow`` times has it divided by ``factor``, and between the
    two dimensions where that holds (``d ln(L / (2 pi beta)) / (2 ln
    theta)``, floored and ceiled) the two are blended by a linear
    ramp; cos and sin carry the published ``attention_factor``. At
    ``factor`` and ``attention_factor`` 1 it is the plain table."""
    half = head_dim // 2
    plain = rope.theta ** (-np.arange(half, dtype=np.float64) / half)
    scale = 1.0
    if rope.rope_type == "yarn":
        def turns_at(beta):
            return head_dim * math.log(
                rope.original_max_position / (beta * 2 * math.pi)
            ) / (2 * math.log(rope.theta))

        low = max(math.floor(turns_at(rope.beta_fast)), 0)
        high = min(math.ceil(turns_at(rope.beta_slow)), head_dim - 1)
        ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
        plain = plain / rope.factor * ramp + plain * (1.0 - ramp)
        scale = rope.attention_factor
    ang = (
        jnp.arange(t, dtype=jnp.float32)[:, None]
        * jnp.asarray(plain, jnp.float32)[None, :]
    )
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The defaults are Mellum2-12B-A2.5B's published values."""

    vocab_size: int = 98304
    block_size: int = 8192
    layer_types: Tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 7
    n_embd: int = 2304
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_sliding: Rope = Rope()
    rope_full: Rope = Rope(
        rope_type="yarn", factor=16.0, original_max_position=8192,
        beta_fast=32.0, beta_slow=1.0,
        attention_factor=1.2772588722239782,
    )
    n_experts: int = 64
    top_k: int = 8
    expert_hidden: int = 896
    renorm_top_k: bool = True
    first_expert: int = 0
    held: int = 0  # 0: all n_experts
    aux_loss_weight: float = 0.001
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
    # What ``llama.attention_half`` reads besides the sizes; constants
    # of the family, not fields.
    qkv_bias = False
    qk_norm = False

    def __post_init__(self):
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)!r}")
        if self.n_head % self.n_kv_head or self.head_dim % 2:
            raise ValueError(
                f"{self.n_head} heads over {self.n_kv_head} key/value "
                f"heads of {self.head_dim}"
            )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def q_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` whose repetition
        gives all of it."""
        types, n = tuple(self.layer_types), len(self.layer_types)
        for p in range(1, n + 1):
            if n % p == 0 and types[:p] * (n // p) == types:
                return types[:p]
        raise AssertionError

    @property
    def periods(self) -> int:
        return self.n_layer // len(self.period)

    @property
    def layer_names(self) -> Tuple[str, ...]:
        """The period's layers in the parameter tree."""
        return tuple(f"{i}_{kind}" for i, kind in enumerate(self.period))

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == SLIDING else None

    def rope_of(self, kind: str) -> Rope:
        return self.rope_sliding if kind == SLIDING else self.rope_full

    @property
    def moe_cfg(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            n_embd=self.n_embd, n_experts=self.n_experts,
            expert_hidden=self.expert_hidden, top_k=self.top_k,
            aux_loss_weight=self.aux_loss_weight, z_loss_weight=0.0,
            dtype=self.dtype, gated=True, renorm_top_k=self.renorm_top_k,
            scoring="softmax", first_expert=self.first_expert,
            held=self.held or self.n_experts,
        )

    @staticmethod
    def tiny() -> "MellumConfig":
        """Test size: two periods of ``sliding, sliding, full``, a
        window of 24 in 64 tokens, 4/2 heads of 24 on a hidden size of
        64, 4 of 16 experts held, 4 a token."""
        return MellumConfig(
            vocab_size=256, block_size=64,
            layer_types=(SLIDING, SLIDING, FULL) * 2,
            n_embd=64, n_head=4, n_kv_head=2, head_dim=24,
            sliding_window=24,
            rope_sliding=Rope(theta=10000.0),
            rope_full=Rope(
                rope_type="yarn", theta=10000.0, factor=4.0,
                original_max_position=32, beta_fast=8.0, beta_slow=1.0,
                attention_factor=1.1386294361119891,
            ),
            n_experts=16, top_k=4, expert_hidden=32, first_expert=4,
            held=4, dtype=jnp.float32, remat=False,
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: MellumConfig) -> Dict[str, Any]:
    """One layer's tree of (shape, logical axes), a pair a leaf (both
    kinds hold the same leaves)."""
    E, X = cfg.n_embd, cfg.expert_hidden
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    shapes = {
        "rms1": ((E,), (None,)),
        "rms2": ((E,), (None,)),
        "wq": ((E, q), ("embed", "heads")),
        "wk": ((E, kv), ("embed", "heads")),
        "wv": ((E, kv), ("embed", "heads")),
        "wo": ((q, E), ("heads", "embed")),
    }
    n, axes = cfg.moe_cfg.experts_here, moe.moe_logical_axes(gated=True)
    sizes = {
        "router": (E, cfg.n_experts), "wi": (n, E, X), "wg": (n, E, X),
        "wo": (n, X, E),
    }
    shapes["moe"] = {leaf: (sizes[leaf], axes[leaf]) for leaf in sizes}
    return shapes


def _is_spec(x) -> bool:
    return isinstance(x, tuple)


def _init_leaf(key, name: str, shape, cfg: MellumConfig):
    f32 = jnp.float32
    if name in ("rms1", "rms2"):
        return 1.0 + cfg.jitter * jax.random.normal(key, shape, f32)
    std = cfg.init_std
    if name == "wo":  # attention's and the experts'
        std = std / np.sqrt(2 * cfg.n_layer)
    value = jax.random.normal(key, shape, f32) * std
    # The router stays float32: tiny, and a top-k choice flips on the
    # last bits.
    return value if name == "router" else value.astype(cfg.dtype)


def _init_layer(key, cfg: MellumConfig) -> Params:
    """One layer of the period for every period: leaves
    ``[periods, ...]``."""
    specs = _layer_shapes(cfg)
    tree = jax.tree.structure(specs, is_leaf=_is_spec)
    keys = tree.unflatten(list(jax.random.split(key, tree.num_leaves)))
    return jax.tree_util.tree_map_with_path(
        lambda path, spec, k: _init_leaf(
            k, path[-1].key, (cfg.periods,) + spec[0], cfg
        ),
        specs, keys, is_leaf=_is_spec,
    )


def init_params(key: jax.Array, cfg: MellumConfig) -> Params:
    k_table, k_head, k_final, k_layers = jax.random.split(key, 4)
    layers = {
        name: _init_layer(k_layer, cfg)
        for name, k_layer in zip(
            cfg.layer_names, jax.random.split(k_layers, len(cfg.period))
        )
    }

    def table(k):
        rows = jax.random.normal(k, (cfg.vocab_size, cfg.n_embd), jnp.float32)
        return (rows * cfg.init_std).astype(cfg.dtype)

    return {
        "wte": table(k_table),
        "periods": layers,
        "rmsf": 1.0 + cfg.jitter * jax.random.normal(
            k_final, (cfg.n_embd,), jnp.float32
        ),
        "lm_head": table(k_head),
    }


def param_logical_axes(cfg: MellumConfig) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table: ``embed`` on fsdp, ``heads`` / ``mlp`` / ``vocab`` on
    tensor, ``expert`` on expert); a leaf's leading dim is its
    periods."""
    layer = jax.tree.map(
        lambda spec: ("layers",) + spec[1], _layer_shapes(cfg),
        is_leaf=_is_spec,
    )
    return {
        "wte": ("vocab", "embed"),
        "periods": {name: layer for name in cfg.layer_names},
        "rmsf": (None,),
        "lm_head": ("vocab", "embed"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def balance_loss(h, router, cfg: MellumConfig):
    """The load-balancing loss of one layer's router on the normed
    ``h`` [B, T, E], weighted, over the number of layers: what
    ``llama.mlp_tail`` adds for a sorted expert layer. The held path
    (models/moe.py) returns none, since a share's layer may be
    balanced by a bias instead; this router is not. The router's
    product, softmax and choice are formed here a second time, small
    beside a layer ([n, E] x [E, 64]), under a scope of their own,
    ``moe_balance``, so that ``moe_route`` reads the held path's
    routing alone."""
    mcfg = cfg.moe_cfg
    with jax.named_scope("moe_balance"):
        logits = moe.router_logits(h.reshape(-1, h.shape[-1]), router)
        probs = jax.nn.softmax(logits, axis=-1)
        _, experts = moe.top_k_route(probs, mcfg.top_k, False)
        losses = moe.router_losses(
            logits, probs, moe.expert_counts(experts, mcfg.n_experts)
        )
    return mcfg.aux_loss_weight * losses["aux_loss"] / cfg.n_layer


def _layer(x, lp, attn_fn, *, cfg: MellumConfig, kind: str, cos, sin):
    """One layer of ``kind``; ``attn_fn`` has the kind's window bound.
    Returns (x, the layer's share of the router loss)."""
    with jax.named_scope("attn"):
        h = llama._rms_norm(x, lp["rms1"], cfg.rms_eps)
        with jax.named_scope(SCOPES[kind]):
            att = llama.attention_half(h, lp, cfg, attn_fn, cos, sin)
    with jax.named_scope("mlp"):
        x = x + att
        h = llama._rms_norm(x, lp["rms2"], cfg.rms_eps)
        y, _ = moe.moe_mlp(lp["moe"], h, cfg.moe_cfg)
        return x + y, balance_loss(h, lp["moe"]["router"], cfg)


def default_attention_for(cfg: MellumConfig) -> Callable:
    """The chooser every family uses (flash on the TPU from 512 tokens
    up) with no window bound: each layer binds its own kind's. A
    caller that binds an ``attn_fn`` of its own takes ``window`` as a
    keyword too."""
    from dlrover_tpu.models import gpt

    return gpt.default_attention_for(
        dataclasses.replace(cfg, sliding_window=None)
    )


def backbone_with_aux(
    params: Params,
    tokens: jax.Array,
    cfg: MellumConfig,
    attn_fn: Optional[Callable] = None,
) -> tuple:
    """[B, T] tokens -> ([B, T, E] hidden after the final norm, the
    router losses summed over the layers)."""
    from dlrover_tpu.accelerate.remat import wire_block

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    t = tokens.shape[1]
    kinds = sorted(set(cfg.period))
    obs.event(
        "hybrid.pattern", layer_types=list(cfg.layer_types),
        period=len(cfg.period), periods=cfg.periods,
        in_line=len(cfg.period), scanned=cfg.periods > 1,
        windows={kind: cfg.window_of(kind) for kind in kinds},
        rotations={kind: cfg.rope_of(kind).rope_type for kind in kinds},
    )
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
    layer = {}
    for kind in kinds:
        cos, sin = rope_table(cfg.rope_of(kind), cfg.head_dim, t)
        windowed = attn_fn
        if cfg.window_of(kind) is not None:
            windowed = functools.partial(attn_fn, window=cfg.window_of(kind))
        layer[kind] = wire_block(
            functools.partial(_layer, cfg=cfg, kind=kind, cos=cos, sin=sin),
            cfg.remat, windowed,
        )

    def one_period(carry, period_params):
        x, aux_sum = carry
        for name, kind in zip(cfg.layer_names, cfg.period):
            x, aux = layer[kind](x, period_params[name])
            aux_sum = aux_sum + aux
        return (x, aux_sum), None

    carry = (x, jnp.zeros((), jnp.float32))
    with jax.named_scope("layers"):
        if cfg.periods == 1:
            carry, _ = one_period(
                carry, jax.tree.map(lambda a: a[0], params["periods"])
            )
        else:
            carry, _ = jax.lax.scan(one_period, carry, params["periods"])
    x, aux = carry
    return llama._rms_norm(x, params["rmsf"], cfg.rms_eps), aux


def backbone(params, tokens, cfg: MellumConfig, attn_fn=None) -> jax.Array:
    return backbone_with_aux(params, tokens, cfg, attn_fn)[0]


def forward(params, tokens, cfg: MellumConfig, attn_fn=None):
    """[B, T, V] float32 logits."""
    return llama.head_logits(params, backbone(params, tokens, cfg, attn_fn))


def loss_fn(params, tokens, targets, cfg: MellumConfig,
            attn_fn=None) -> jax.Array:
    x, aux = backbone_with_aux(params, tokens, cfg, attn_fn)
    logp = jax.nn.log_softmax(llama.head_logits(params, x), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll) + aux


def loss_fn_fused(params, tokens, targets, cfg: MellumConfig,
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
