"""Hybrid state-space / attention language model (the Granite 4.0-H
shape, huggingface ``GraniteMoeHybrid`` with no routed experts): a
stack whose layers are of two kinds in a published pattern
(``layer_types``), Mamba-2 mixers among grouped-query attention
layers, every layer followed by the same SwiGLU MLP.

    h = wte[tokens] * embedding_multiplier
    every layer:  h = h + residual_multiplier * mixer(rms_1(h))
                  h = h + residual_multiplier * mlp(rms_2(h))
    logits = rms_f(h) @ wte^T / logits_scaling            (tied table)

* attention mixer: models/llama.py's attention half (grouped queries,
  no bias) with the softmax scale ``attention_multiplier`` and no
  positional embedding ("nope");
* Mamba-2 mixer: ``[z | xBC | dt] = u @ w_in``; a causal depthwise
  convolution of width ``ssm_conv`` with bias over ``xBC``, then SiLU
  (ops/causal_conv.py);
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)``; the SSD recurrence (ops/ssd.py, chunked);
  the gate ``y * silu(z)`` BEFORE an RMS norm over the whole inner
  width; ``w_out``;
* the MLP is ``llama.swiglu`` and the loss ``fused_cross_entropy`` on
  the tied table: shared with the other families, not copied.

Layers of one kind that follow each other are stacked and scanned.
The unit of the outer scan is one period of the pattern (the
shortest prefix whose repetition gives ``layer_types``): the
published 40 layers are four periods of ``5 x mamba, attention,
4 x mamba``, and the parameter tree holds one subtree a run of the
period (``runs``: ``0_mamba``, ``1_attention``, ``2_mamba``), each
leaf shaped ``[periods, layers in the run, ...]``. One step program
whatever the depth.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.models import llama

Params = Dict[str, Any]
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The defaults are granite-4.0-h-micro's published values."""

    vocab_size: int = 100352
    block_size: int = 4096
    layer_types: Tuple[str, ...] = (
        (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    ) * 4
    n_embd: int = 2048
    n_head: int = 32
    n_kv_head: int = 8
    intermediate: int = 8192  # shared_intermediate_size
    rms_eps: float = 1e-5
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    # Initial values: normal(0, init_std) matrices, the projections
    # back into the residual stream over sqrt(2 x layers) as
    # models/llama.py; dt_bias the inverse softplus of a step drawn
    # log-uniformly from [dt_min, dt_max]; A_log = log(a_scale x
    # (1..heads)) (huggingface's Mamba-2 initial values at a_scale 1;
    # below 1 the heads remember for longer). Gains, D and the
    # convolution are drawn around their usual values (``jitter``),
    # not set to them: a gain of exactly 1 or a bias of exactly 0
    # would hide its own omission from a check against a reference.
    init_std: float = 0.02
    dt_min: float = 0.001
    dt_max: float = 0.1
    a_scale: float = 1.0
    jitter: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: Any = True  # accelerate/remat.py's named policies
    use_flash_attention: Optional[bool] = None

    def __post_init__(self):
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)!r}")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"{self.ssm_heads} state-space heads do not divide "
                f"into {self.ssm_groups} groups"
            )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

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
    def runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """One period as runs of one kind: (name in the parameter
        tree, kind, layers)."""
        out = []
        for kind in self.period:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return tuple(
            (f"{i}_{kind}", kind, n) for i, (kind, n) in enumerate(out)
        )

    @property
    def attention_cfg(self) -> llama.LlamaConfig:
        """What models/llama.py's attention half and the attention
        chooser read, from this configuration."""
        return llama.LlamaConfig(
            vocab_size=self.vocab_size, block_size=self.block_size,
            n_layer=self.n_layer, n_head=self.n_head,
            n_kv_head=self.n_kv_head, n_embd=self.n_embd,
            intermediate=self.intermediate, rms_eps=self.rms_eps,
            dtype=self.dtype, remat=self.remat,
            use_flash_attention=self.use_flash_attention,
        )

    @staticmethod
    def tiny() -> "GraniteHybridConfig":
        """Test size: two periods of ``mamba, mamba, attention,
        mamba``, two B/C groups, chunks of 16."""
        return GraniteHybridConfig(
            vocab_size=256, block_size=64,
            layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA) * 2,
            n_embd=64, n_head=4, n_kv_head=2, intermediate=128,
            ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=2,
            ssm_chunk=16, attention_multiplier=0.25,
            dtype=jnp.float32, remat=False,
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: GraniteHybridConfig, kind: str) -> Dict[str, tuple]:
    """Leaf name -> (shape, logical axes) of one layer of ``kind``."""
    E, I = cfg.n_embd, cfg.intermediate
    shapes = {
        "rms1": ((E,), (None,)),
        "rms2": ((E,), (None,)),
        "w_gate": ((E, I), ("embed", "mlp")),
        "w_up": ((E, I), ("embed", "mlp")),
        "w_down": ((I, E), ("mlp", "embed")),
    }
    if kind == ATTENTION:
        kv = cfg.n_kv_head * (E // cfg.n_head)
        shapes.update(
            wq=((E, E), ("embed", "heads")),
            wk=((E, kv), ("embed", "heads")),
            wv=((E, kv), ("embed", "heads")),
            wo=((E, E), ("heads", "embed")),
        )
        return shapes
    H, inner = cfg.ssm_heads, cfg.d_inner
    shapes.update(
        # [z | xBC | dt] side by side: a split over ``tensor`` would
        # cut across them, so the width stays whole.
        w_in=((E, inner + cfg.conv_dim + H), ("embed", None)),
        conv_w=((cfg.ssm_conv, cfg.conv_dim), (None, None)),
        conv_b=((cfg.conv_dim,), (None,)),
        dt_bias=((H,), (None,)),
        A_log=((H,), (None,)),
        D=((H,), (None,)),
        ssm_norm=((inner,), (None,)),
        w_out=((inner, E), (None, "embed")),
    )
    return shapes


def _init_leaf(key, name, shape, cfg: GraniteHybridConfig):
    """One leaf for every layer of a run: ``shape`` is
    [periods, layers in the run, ...]."""
    if name in ("rms1", "rms2", "ssm_norm", "D"):
        return 1.0 + cfg.jitter * jax.random.normal(key, shape, jnp.float32)
    if name == "A_log":
        heads = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(cfg.a_scale * heads), shape)
    if name == "dt_bias":
        lo, hi = np.log(cfg.dt_min), np.log(cfg.dt_max)
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
    if name in ("conv_w", "conv_b"):
        # torch's Conv1d default: uniform within one over the root of
        # the fan-in (the kernel's width).
        bound = 1.0 / np.sqrt(cfg.ssm_conv)
        return jax.random.uniform(
            key, shape, jnp.float32, -bound, bound
        ).astype(cfg.dtype)
    std = cfg.init_std
    if name in ("wo", "w_out", "w_down"):
        std = std / np.sqrt(2 * cfg.n_layer)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(cfg.dtype)


def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    periods = cfg.n_layer // len(cfg.period)
    k_table, k_final, k_runs = jax.random.split(key, 3)
    runs = {}
    for (name, kind, n), k_run in zip(
        cfg.runs, jax.random.split(k_runs, len(cfg.runs))
    ):
        shapes = _layer_shapes(cfg, kind)
        runs[name] = {
            leaf: _init_leaf(k, leaf, (periods, n) + shape, cfg)
            for (leaf, (shape, _)), k in zip(
                sorted(shapes.items()),
                jax.random.split(k_run, len(shapes)),
            )
        }
    table = jax.random.normal(
        k_table, (cfg.vocab_size, cfg.n_embd), jnp.float32
    )
    return {
        "wte": (table * cfg.init_std).astype(cfg.dtype),
        "runs": runs,
        "rmsf": 1.0 + cfg.jitter * jax.random.normal(
            k_final, (cfg.n_embd,), jnp.float32
        ),
    }


def param_logical_axes(cfg: GraniteHybridConfig) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table: ``embed`` on fsdp, ``heads`` / ``mlp`` / ``vocab`` on
    tensor); the two leading dims of a run are its periods and its
    layers."""
    return {
        "wte": ("vocab", "embed"),
        "runs": {
            name: {
                leaf: ("layers", "layers") + axes
                for leaf, (_, axes) in _layer_shapes(cfg, kind).items()
            }
            for name, kind, _ in cfg.runs
        },
        "rmsf": (None,),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _scaled(branch, multiplier):
    """``branch * multiplier`` formed in float32 and rounded once (a
    multiplier rounded to bf16 first would be off by up to 0.4%)."""
    return (branch.astype(jnp.float32) * multiplier).astype(branch.dtype)


def mamba_mixer(u, lp, cfg: GraniteHybridConfig):
    """The Mamba-2 mixer on the normed input ``u`` [B, T, E], without
    the residual."""
    from dlrover_tpu.accelerate.remat import SSM_IN, keep
    from dlrover_tpu.ops.causal_conv import conv_silu
    from dlrover_tpu.ops.ssd import ssd

    bsz, t, _ = u.shape
    inner, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    # Named for remat="full" (accelerate/remat.py KEPT) with the
    # scan's output and chunk states (ops/ssd.py): the convolution,
    # the gate and the norm are recomputed, the kernel is not run
    # again.
    proj = keep(u @ lp["w_in"], SSM_IN)
    z = proj[..., :inner]
    dt = proj[..., inner + cfg.conv_dim:]
    with jax.named_scope("ssm_conv"):
        # xBC's convolution is depthwise, so x's columns and B|C's take
        # a call each, read where they lie in the projection: each
        # result goes to the scan whole and each cotangent comes back
        # whole, where one call's result is sliced for every consumer
        # and their cotangents are laid side by side again (PERF.md
        # section 6, PR 52).
        w, bias = lp["conv_w"], lp["conv_b"]
        x = conv_silu(proj, w[:, :inner], bias[:inner], start=inner)
        bc = conv_silu(proj, w[:, inner:], bias[inner:], start=2 * inner)
    b = bc[..., :gn].reshape(bsz, t, cfg.ssm_groups, -1)
    c = bc[..., gn:].reshape(bsz, t, cfg.ssm_groups, -1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    with jax.named_scope("ssd"):
        y = ssd(
            x, dt, -jnp.exp(lp["A_log"]), b, c, lp["D"],
            chunk=min(cfg.ssm_chunk, t),
        )
    with jax.named_scope("ssm_norm"):
        # The gate before the norm, one group over the whole width.
        v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        v = v * jax.lax.rsqrt(
            jnp.mean(jnp.square(v), axis=-1, keepdims=True) + cfg.rms_eps
        )
        y = (v * lp["ssm_norm"]).astype(u.dtype)
    return y @ lp["w_out"]


def _layer(x, lp, attn_fn, *, cfg: GraniteHybridConfig, kind: str):
    h = llama._rms_norm(x, lp["rms1"], cfg.rms_eps)
    if kind == MAMBA:
        with jax.named_scope("ssm"):
            mixed = mamba_mixer(h, lp, cfg)
    else:
        with jax.named_scope("attn"):
            mixed = llama.attention_half(
                h, lp, cfg.attention_cfg, attn_fn, None, None
            )
    x = x + _scaled(mixed, cfg.residual_multiplier)
    with jax.named_scope("mlp"):
        h = llama._rms_norm(x, lp["rms2"], cfg.rms_eps)
        return x + _scaled(llama.swiglu(h, lp), cfg.residual_multiplier)


def default_attention_for(cfg: GraniteHybridConfig) -> Callable:
    """The chooser every family uses (flash on the TPU from 512 tokens
    up), with this family's softmax scale. A caller that binds an
    ``attn_fn`` of its own gives it the scale too."""
    return functools.partial(
        llama.default_attention_for(cfg.attention_cfg),
        scale=cfg.attention_multiplier,
    )


def backbone(
    params: Params,
    tokens: jax.Array,
    cfg: GraniteHybridConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """[B, T] tokens -> [B, T, E] hidden after the final norm."""
    from dlrover_tpu.accelerate.remat import wire_block

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    obs.event(
        "hybrid.pattern", layer_types=list(cfg.layer_types),
        mamba_layers=cfg.layer_types.count(MAMBA),
        attention_layers=cfg.layer_types.count(ATTENTION),
        period=len(cfg.period),
    )
    with jax.named_scope("embed"):
        x = _scaled(
            params["wte"][tokens].astype(cfg.dtype), cfg.embedding_multiplier
        )
    layer = {
        kind: wire_block(
            functools.partial(_layer, cfg=cfg, kind=kind), cfg.remat, attn_fn
        )
        for kind in set(cfg.period)
    }

    def one_period(x, run_params):
        for name, kind, n in cfg.runs:
            lp = run_params[name]
            if n == 1:
                x = layer[kind](x, jax.tree.map(lambda a: a[0], lp))
            else:
                with jax.named_scope("layers"):
                    x, _ = jax.lax.scan(
                        lambda x, lp, kind=kind: (layer[kind](x, lp), None),
                        x, lp,
                    )
        return x, None

    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(one_period, x, params["runs"])
    return llama._rms_norm(x, params["rmsf"], cfg.rms_eps)


def forward(params, tokens, cfg: GraniteHybridConfig, attn_fn=None):
    """[B, T, V] float32 logits."""
    x = backbone(params, tokens, cfg, attn_fn)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...te,ve->...tv", x, params["wte"],
            preferred_element_type=jnp.float32,
        ) / cfg.logits_scaling


def loss_fn(params, tokens, targets, cfg: GraniteHybridConfig,
            attn_fn=None) -> jax.Array:
    logp = jax.nn.log_softmax(forward(params, tokens, cfg, attn_fn), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def loss_fn_fused(params, tokens, targets, cfg: GraniteHybridConfig,
                  attn_fn=None, num_chunks: int = 8) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x = backbone(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        # The logits' divisor on the hidden state: one over a power of
        # two is exact in any float dtype.
        return fused_cross_entropy(
            _scaled(x.reshape(n, -1), 1.0 / cfg.logits_scaling),
            params["wte"], targets.reshape(n), num_chunks,
        )
