"""Hybrid linear-attention / latent-attention expert model (the Kimi
Linear shape, arXiv:2510.26692; huggingface ``kimi_linear``): a stack
whose layers are of three kinds by two published lists. The mixer is
Kimi Delta Attention (``kda``) or latent attention without positions
(``mla``); the feed-forward is a dense SwiGLU MLP in the leading
layers and an expert layer in every other.

    h = wte[tokens]
    every layer:  h = h + mixer(rms_1(h));  h = h + ffn(rms_2(h))
    logits = rms_f(h) @ lm_head^T                      (untied head)

* ``kda`` (ops/kda.py holds the rule): ``q, k, v`` are three
  projections, each through a depthwise causal convolution of width
  ``conv`` and SiLU (ops/causal_conv.py, the three read where they lie
  in one ``[T, 3 x inner]`` product; no bias, so the kernel is handed
  a constant zero row); ``q`` and ``k`` L2-normalised a head, ``q``
  times ``d^-0.5``; the log decay a channel of the key
  ``g = -exp(A_log[head]) * softplus((u w_fa) w_fb + dt_bias)``;
  ``beta = sigmoid(u w_b)`` a head; the rule; an RMS norm over each
  head's output times ``sigmoid((u w_ga) w_gb)``; ``w_o``.
* ``mla`` (models/mla.py holds the mixer): ``q = u w_q`` in heads of
  ``qk_nope + qk_rope``; the latent ``[c | k_r] = u w_kva``;
  ``[k_n | v] = rms(c) w_kvb`` a head;
  the key of a head is ``[k_n | k_r]``, ``k_r`` shared by the heads
  and, as the published configuration has it (``mla_use_nope``),
  nothing is rotated; causal softmax attention at scale
  ``(qk_nope + qk_rope)^-0.5`` with values of width ``v_head``
  (ops/flash_attention.py takes the two head sizes); ``w_o``.
* the expert layer is models/moe.py's held path: a sigmoid router
  over all ``n_experts`` whose bias chooses and does not weigh,
  renormalised weights times ``routed_scale``, this chip's ``held``
  experts from ``first_expert`` on, and a shared expert.
* the dense MLP is ``llama.swiglu``, the norms ``llama._rms_norm`` and
  the loss ``fused_cross_entropy``: shared with the other families.

The layers are calls in a row of one traced program, each with a
parameter subtree of its own (``layers/<index>_<mixer>_<ffn>``): a
stack of three kinds is short where a chip holds a share of it (five
layers in the benchmark's cell), and a short stack in line is the fast
form (PERF.md, PR 51). Event ``hybrid.pattern`` says so.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.models import llama, mla
from dlrover_tpu.models.moe import MoEConfig, moe_logical_axes, moe_mlp

Params = Dict[str, Any]
KDA, MLA = "kda", "mla"
DENSE, MOE = "dense", "moe"
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The defaults are Kimi-Linear-48B-A3B's published values."""

    vocab_size: int = 163840
    block_size: int = 8192
    # One entry a layer: the mixer and the feed-forward.
    mixers: Tuple[str, ...] = ((KDA,) * 3 + (MLA,)) * 6 + (KDA, KDA, MLA)
    ffns: Tuple[str, ...] = (DENSE,) + (MOE,) * 26
    n_embd: int = 2304
    n_head: int = 32
    kda_head_dim: int = 128
    conv: int = 4
    gate_rank: int = 128  # of the decay's and the output gate's two-step projections
    kv_rank: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    intermediate: int = 9216
    n_experts: int = 256
    top_k: int = 8
    expert_hidden: int = 1024
    shared_hidden: int = 1024
    routed_scale: float = 2.446
    renorm_top_k: bool = True
    scoring: str = "sigmoid"
    first_expert: int = 0
    held: int = 0  # 0: all n_experts
    rms_eps: float = 1e-5
    # Initial values: normal(0, init_std) matrices, the projections
    # back into the residual stream over sqrt(2 x layers);
    # A_log = log(uniform(a_min, a_max)); dt_bias the inverse
    # softplus of a step drawn log-uniformly from [dt_min, dt_max];
    # gains, the convolution and the router's bias are drawn around
    # their usual values (``jitter``, ``bias_std``), not set to them: a
    # gain of exactly 1 or a bias of exactly 0 would hide its own
    # omission from a check against a reference.
    init_std: float = 0.02
    a_min: float = 1.0
    a_max: float = 16.0
    dt_min: float = 0.001
    dt_max: float = 0.1
    jitter: float = 0.1
    bias_std: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: Any = True  # accelerate/remat.py's named policies
    use_flash_attention: Optional[bool] = None

    def __post_init__(self):
        if len(self.mixers) != len(self.ffns) or not self.mixers:
            raise ValueError("one mixer and one feed-forward a layer")
        unknown = (set(self.mixers) - {KDA, MLA}) | (
            set(self.ffns) - {DENSE, MOE}
        )
        if unknown:
            raise ValueError(f"layer kinds {sorted(unknown)!r}")

    @property
    def n_layer(self) -> int:
        return len(self.mixers)

    @property
    def kda_inner(self) -> int:
        return self.n_head * self.kda_head_dim

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return tuple(
            f"{i}_{m}_{f}"
            for i, (m, f) in enumerate(zip(self.mixers, self.ffns))
        )

    @property
    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(
            n_embd=self.n_embd, n_experts=self.n_experts,
            expert_hidden=self.expert_hidden, top_k=self.top_k,
            aux_loss_weight=0.0, z_loss_weight=0.0, dtype=self.dtype,
            gated=True, renorm_top_k=self.renorm_top_k,
            scoring=self.scoring, choice_bias=True,
            routed_scale=self.routed_scale,
            shared_hidden=self.shared_hidden,
            first_expert=self.first_expert,
            held=self.held or self.n_experts,
        )

    @staticmethod
    def tiny() -> "KimiLinearConfig":
        """Test size: dense-KDA, KDA, MLA, KDA with experts; 4 of 16
        experts held, 4 a token."""
        return KimiLinearConfig(
            vocab_size=256, block_size=64,
            mixers=(KDA, KDA, MLA, KDA), ffns=(DENSE, MOE, MOE, MOE),
            n_embd=64, n_head=4, kda_head_dim=16, gate_rank=8, kv_rank=24,
            qk_nope=16, qk_rope=8, v_head=16, intermediate=128,
            n_experts=16, top_k=4, expert_hidden=32, shared_hidden=32,
            first_expert=4, held=4, bias_std=0.1, dtype=jnp.float32,
            remat=False,
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: KimiLinearConfig, mixer: str, ffn: str) -> Dict:
    """Leaf path -> (shape, logical axes) of one layer."""
    E, H = cfg.n_embd, cfg.n_head
    shapes = {"rms1": ((E,), (None,)), "rms2": ((E,), (None,))}
    if mixer == KDA:
        inner, r = cfg.kda_inner, cfg.gate_rank
        shapes.update(
            # [q | k | v] side by side, each read where it lies by its
            # convolution: the width stays whole.
            w_qkv=((E, 3 * inner), ("embed", None)),
            conv_w=((cfg.conv, 3 * inner), (None, None)),
            w_fa=((E, r), ("embed", None)),
            w_fb=((r, inner), (None, None)),
            dt_bias=((inner,), (None,)),
            A_log=((H,), (None,)),
            w_b=((E, H), ("embed", None)),
            w_ga=((E, r), ("embed", None)),
            w_gb=((r, inner), (None, None)),
            o_norm=((cfg.kda_head_dim,), (None,)),
            w_o=((inner, E), (None, "embed")),
        )
    else:
        shapes.update(
            wq=((E, H * (cfg.qk_nope + cfg.qk_rope)), ("embed", "heads")),
            w_kva=((E, cfg.kv_rank + cfg.qk_rope), ("embed", None)),
            kv_norm=((cfg.kv_rank,), (None,)),
            w_kvb=((cfg.kv_rank, H * (cfg.qk_nope + cfg.v_head)),
                   (None, "heads")),
            w_o=((H * cfg.v_head, E), ("heads", "embed")),
        )
    if ffn == DENSE:
        I = cfg.intermediate
        shapes.update(
            w_gate=((E, I), ("embed", "mlp")),
            w_up=((E, I), ("embed", "mlp")),
            w_down=((I, E), ("mlp", "embed")),
        )
    else:
        moe = cfg.moe_cfg
        n, X, S = moe.experts_here, cfg.expert_hidden, cfg.shared_hidden
        axes = moe_logical_axes(True, True, True)
        sizes = {
            "router": (E, cfg.n_experts), "router_bias": (cfg.n_experts,),
            "wi": (n, E, X), "wg": (n, E, X), "wo": (n, X, E),
        }
        for leaf, shape in sizes.items():
            shapes[f"moe/{leaf}"] = (shape, axes[leaf])
        for leaf, shape in (
            ("w_gate", (E, S)), ("w_up", (E, S)), ("w_down", (S, E))
        ):
            shapes[f"moe/shared/{leaf}"] = (shape, axes["shared"][leaf])
    return shapes


def _init_leaf(key, path: str, shape, cfg: KimiLinearConfig):
    name = path.split("/")[-1]
    f32 = jnp.float32
    if name in ("rms1", "rms2", "o_norm", "kv_norm"):
        return 1.0 + cfg.jitter * jax.random.normal(key, shape, f32)
    if name == "A_log":
        rate = jax.random.uniform(key, shape, f32, cfg.a_min, cfg.a_max)
        return jnp.log(rate)
    if name == "dt_bias":
        lo, hi = np.log(cfg.dt_min), np.log(cfg.dt_max)
        step = jnp.exp(jax.random.uniform(key, shape, f32, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
    if name == "conv_w":
        # torch's Conv1d default: uniform within one over the root of
        # the fan-in (the kernel's width).
        bound = 1.0 / np.sqrt(cfg.conv)
        return jax.random.uniform(key, shape, f32, -bound, bound).astype(
            cfg.dtype
        )
    if name == "router_bias":
        return cfg.bias_std * jax.random.normal(key, shape, f32)
    std = cfg.init_std
    if name in ("w_o", "w_down", "wo"):
        std = std / np.sqrt(2 * cfg.n_layer)
    value = jax.random.normal(key, shape, f32) * std
    # The router stays float32: tiny, and a top-k choice flips on the
    # last bits.
    return value if name == "router" else value.astype(cfg.dtype)


def init_params(key: jax.Array, cfg: KimiLinearConfig) -> Params:
    k_table, k_head, k_final, k_layers = jax.random.split(key, 4)
    layers = {}
    for name, mixer, ffn, k_layer in zip(
        cfg.layer_names, cfg.mixers, cfg.ffns,
        jax.random.split(k_layers, cfg.n_layer),
    ):
        shapes = _layer_shapes(cfg, mixer, ffn)
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


def param_logical_axes(cfg: KimiLinearConfig) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table: ``embed`` on fsdp, ``heads`` / ``mlp`` / ``vocab`` on
    tensor, ``expert`` on expert)."""
    return {
        "wte": ("vocab", "embed"),
        "layers": {
            name: mla.nested({
                path: axes
                for path, (_, axes) in _layer_shapes(cfg, mixer, ffn).items()
            })
            for name, mixer, ffn in zip(
                cfg.layer_names, cfg.mixers, cfg.ffns
            )
        },
        "rmsf": (None,),
        "lm_head": ("vocab", "embed"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _per_head(heads: int, d: int):
    """A head's sum over its ``d`` channels of a float32
    ``[..., heads * d]`` array whose heads lie side by side, as
    ``[..., heads]``, and a head's value back on its channels: products
    with the heads' constant 0/1 membership ``[heads * d, heads]``,
    float32 in fact. Not a reduction over a ``[..., heads, d]`` view:
    on the chip no 4-D layout is a bitcast of the 3-D tiling the
    convolutions write and the rule's kernels read, so the view is a
    copy of the whole array, and so is its gradient's (PERF.md
    section 6, PR 56)."""
    member = (
        jnp.arange(heads * d)[:, None] // d == jnp.arange(heads)
    ).astype(jnp.float32)
    product = functools.partial(
        jnp.einsum, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return (
        lambda x: product("...i,ih->...h", x, member),
        lambda s: product("...h,ih->...i", s, member),
    )


def kda_mixer(u, lp, cfg: KimiLinearConfig):
    """The KDA mixer on the normed input ``u`` [B, T, E], without the
    residual. From the convolutions to ``w_o`` everything stays
    ``[B, T, inner]``, a head's channels side by side."""
    from dlrover_tpu.accelerate.remat import KDA_IN, keep
    from dlrover_tpu.ops import kda as rule
    from dlrover_tpu.ops.causal_conv import conv_silu

    heads, d, inner = cfg.n_head, cfg.kda_head_dim, cfg.kda_inner
    f32 = jnp.float32
    head_sum, on_channels = _per_head(heads, d)
    # Named for remat="full" (accelerate/remat.py KEPT) with the
    # rule's output and chunk states (ops/kda.py): the convolutions,
    # the gates and the norm are recomputed.
    proj = keep(u @ lp["w_qkv"], KDA_IN)
    with jax.named_scope("kda_conv"):
        w = lp["conv_w"]
        no_bias = jnp.zeros((inner,), w.dtype)
        q, k, v = (
            conv_silu(
                proj, w[:, i * inner: (i + 1) * inner], no_bias,
                start=i * inner,
            )
            for i in range(3)
        )

        def unit(x, scale=1.0):
            x = x.astype(f32)
            norm = jax.lax.rsqrt(head_sum(jnp.square(x)) + L2_EPS)
            return (x * on_channels(norm * scale)).astype(u.dtype)

        q, k = unit(q, d ** -0.5), unit(k)
    with jax.named_scope("kda_gate"):
        step = (u @ lp["w_fa"]) @ lp["w_fb"]
        step = jax.nn.softplus(step.astype(f32) + lp["dt_bias"])
        g = jnp.repeat(-jnp.exp(lp["A_log"]), d) * step
        beta = jax.nn.sigmoid((u @ lp["w_b"]).astype(f32))
    with jax.named_scope("kda_scan"):
        o = rule.kda_wide(q, k, v, g, beta)
    with jax.named_scope("kda_gate"):
        o = o.astype(f32)
        o = o * on_channels(
            jax.lax.rsqrt(head_sum(jnp.square(o)) / d + cfg.rms_eps)
        )
        gate = jax.nn.sigmoid(((u @ lp["w_ga"]) @ lp["w_gb"]).astype(f32))
        y = (o * jnp.tile(lp["o_norm"], heads) * gate).astype(u.dtype)
    return y @ lp["w_o"]


def _layer(x, lp, attn_fn, *, cfg: KimiLinearConfig, mixer: str, ffn: str):
    with jax.named_scope("attn"):
        h = llama._rms_norm(x, lp["rms1"], cfg.rms_eps)
        if mixer == KDA:
            with jax.named_scope("kda"):
                x = x + kda_mixer(h, lp, cfg)
        else:
            d_qk = cfg.qk_nope + cfg.qk_rope
            obs.event(
                "mla.attn", d_qk=d_qk, d_v=cfg.v_head, padded_to=d_qk,
                heads=cfg.n_head, rotated=False,
            )
            with jax.named_scope("mla"):
                x = x + mla.mla_mixer(h, lp, attn_fn, cfg, d_qk ** -0.5)
    with jax.named_scope("mlp"):
        h = llama._rms_norm(x, lp["rms2"], cfg.rms_eps)
        if ffn == DENSE:
            return x + llama.swiglu(h, lp)
        y, _ = moe_mlp(lp["moe"], h, cfg.moe_cfg)
        return x + y


def default_attention_for(cfg: KimiLinearConfig) -> Callable:
    """The chooser every family uses (flash on the TPU from 512 tokens
    up); the latent mixer gives it its scale."""
    from dlrover_tpu.models import gpt

    return gpt.default_attention_for(cfg)


def backbone(
    params: Params,
    tokens: jax.Array,
    cfg: KimiLinearConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """[B, T] tokens -> [B, T, E] hidden after the final norm."""
    from dlrover_tpu.accelerate.remat import wire_block

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    obs.event(
        "hybrid.pattern",
        layer_types=[f"{m}+{f}" for m, f in zip(cfg.mixers, cfg.ffns)],
        kda_layers=cfg.mixers.count(KDA), mla_layers=cfg.mixers.count(MLA),
        dense_layers=cfg.ffns.count(DENSE), moe_layers=cfg.ffns.count(MOE),
        in_line=cfg.n_layer, scanned=0,
    )
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
    blocks = {
        kind: wire_block(
            functools.partial(_layer, cfg=cfg, mixer=kind[0], ffn=kind[1]),
            cfg.remat, attn_fn,
        )
        for kind in set(zip(cfg.mixers, cfg.ffns))
    }
    with jax.named_scope("layers"):
        for name, mixer, ffn in zip(cfg.layer_names, cfg.mixers, cfg.ffns):
            x = blocks[mixer, ffn](x, params["layers"][name])
    return llama._rms_norm(x, params["rmsf"], cfg.rms_eps)


def forward(params, tokens, cfg: KimiLinearConfig, attn_fn=None):
    """[B, T, V] float32 logits."""
    return llama.head_logits(params, backbone(params, tokens, cfg, attn_fn))


def loss_fn(params, tokens, targets, cfg: KimiLinearConfig,
            attn_fn=None) -> jax.Array:
    logp = jax.nn.log_softmax(forward(params, tokens, cfg, attn_fn), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def loss_fn_fused(params, tokens, targets, cfg: KimiLinearConfig,
                  attn_fn=None, num_chunks: int = 8) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x = backbone(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        return fused_cross_entropy(
            x.reshape(n, -1), params["lm_head"], targets.reshape(n),
            num_chunks,
        )
