"""Llama-family language model, TPU-first.

Capability parity with the reference's Llama-2 pretraining/finetune
examples (/root/reference/atorch/examples/llama2/fsdp_llama2.py — HF
LlamaDecoderLayer + atorch auto_accelerate FSDP; ds_3d_llama2.py for
the 3D-parallel variant), built as an idiomatic JAX program rather
than an HF wrapper:

* pure-functional param pytree with logical sharding axes per leaf —
  the same (mesh, rules) pair that shards GPT drives Llama through
  DP/FSDP/TP/SP (parallel/sharding.py), replacing the reference's
  FSDP-wrapper + device-mesh plumbing;
* layers stacked and executed with ``lax.scan`` (one compiled block);
* RMSNorm in f32, rotary embeddings precomputed once outside the
  scan, SwiGLU MLP, optional grouped-query attention (n_kv_head <
  n_head, Llama-3 style);
* the same Pallas flash-attention kernel and named remat policies as
  GPT (ops/flash_attention.py, accelerate/remat.py);
* fused chunked cross-entropy against the (untied) lm_head for the
  loss (ops/cross_entropy.py).

``make_sharded_init`` (trainer/step.py) plays the role of the
reference's ``init_empty_weights_with_disk_offload``
(atorch/utils/meta_model_utils.py): params are materialized directly
into their shards on device, never gathered on one host.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    block_size: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32  # < n_head enables grouped-query attention
    n_embd: int = 4096
    intermediate: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: Any = True  # same named policies as GPTConfig.remat
    use_flash_attention: Optional[bool] = None
    # Declared attention masking (read by the auto_accelerate
    # seq-parallel binding, like GPTConfig.causal).
    causal: bool = True
    # > 0 switches every block's MLP to a mixture of SwiGLU experts
    # (models/moe.py: routed by sort and dropless, or one-hot under
    # an ``expert`` mesh axis). ``intermediate`` then sets the
    # per-expert hidden width.
    n_experts: int = 0
    moe_top_k: int = 2
    # The one-hot path's alone (a mesh with an ``expert`` axis).
    moe_capacity_factor: float = 1.25
    # True: the top-k weights are renormalised to sum to 1 (Mixtral);
    # False: the full softmax's values as they are (OLMoE's
    # ``norm_topk_prob`` false).
    moe_renorm_top_k: bool = True
    # Weights of the load-balancing loss and the router z-loss, each
    # averaged over layers (Mixtral's and OLMoE's
    # ``router_aux_loss_coef``; the OLMoE paper's z-loss).
    moe_aux_loss_weight: float = 1e-2
    moe_z_loss_weight: float = 1e-3
    # RMSNorm with a learned gain over the whole projected query and
    # key vectors, before the split into heads and the rotation
    # (OLMoE's ``q_norm`` / ``k_norm``).
    qk_norm: bool = False
    # Mistral-style sliding-window attention: query i sees keys
    # (i-sliding_window, i]. None = full causal attention. The flash
    # kernel skips kv blocks entirely below the band (O(T*window)
    # work); the plain fallback applies the same band mask.
    sliding_window: Optional[int] = None
    # Flash tile override (block_q, block_k, block_q_bwd, block_k_bwd)
    # — same contract as GPTConfig.attn_blocks.
    attn_blocks: Optional[tuple] = None
    # Learned bias on the q/k/v projections (the ChatGLM2/3 shape —
    # models/glm.py; Llama/Mistral keep the default False).
    qkv_bias: bool = False
    # Prefix-LM generation semantics (GLM): prompts prefill with the
    # full bidirectional mask — every layer's prompt k/v depends on
    # the mask through the hiddens — then decode steps run causally.
    prefix_lm: bool = False
    # Fraction of head_dim that receives rotary embedding; the rest
    # passes through unrotated (ChatGLM applies RoPE to half the
    # dims). 1.0 = full-dim RoPE (Llama).
    rotary_pct: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def q_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_head={self.n_head} not divisible by "
                f"n_kv_head={self.n_kv_head}"
            )
        rot = int(self.head_dim * self.rotary_pct)
        if not 0 < rot <= self.head_dim or rot % 2:
            raise ValueError(
                f"rotary_pct={self.rotary_pct} gives {rot} rotary "
                f"dims of head_dim={self.head_dim}; need an even "
                "count in (0, head_dim]"
            )
        if self.prefix_lm and self.sliding_window is not None:
            # A one-sided band over a bidirectional prefix is not a
            # defined mask; reject at config time rather than deep
            # inside the prefill scan (flash and the XLA fallback
            # both refuse window with causal=False).
            raise ValueError(
                "prefix_lm and sliding_window are mutually "
                "exclusive: the bidirectional prefix has no causal "
                "band to window"
            )

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256,
            block_size=8192,
            n_layer=32,
            n_head=32,
            n_kv_head=8,
            n_embd=4096,
            intermediate=14336,
            rope_theta=500000.0,
        )

    @staticmethod
    def tiny() -> "LlamaConfig":
        """Test-size config (GQA on, so tests cover the kv-repeat path)."""
        return LlamaConfig(
            vocab_size=256,
            block_size=64,
            n_layer=2,
            n_head=4,
            n_kv_head=2,
            n_embd=64,
            intermediate=128,
            dtype=jnp.float32,
            remat=False,
        )

    @staticmethod
    def mistral_7b() -> "LlamaConfig":
        """Mistral-7B-v0.1: Llama backbone + GQA + 4k sliding window
        over an 8k context."""
        return LlamaConfig(
            vocab_size=32000,
            block_size=8192,
            n_layer=32,
            n_head=32,
            n_kv_head=8,
            n_embd=4096,
            intermediate=14336,
            rope_theta=10000.0,
            sliding_window=4096,
        )

    @staticmethod
    def moe_8x7b() -> "LlamaConfig":
        """Mixtral-8x7B-shaped: Llama-2 backbone, 8 experts, top-2."""
        return LlamaConfig(
            vocab_size=32000,
            block_size=4096,
            n_layer=32,
            n_head=32,
            n_kv_head=8,
            n_embd=4096,
            intermediate=14336,
            rope_theta=1e6,
            n_experts=8,
            moe_top_k=2,
        )

    @staticmethod
    def olmoe_1b_7b() -> "LlamaConfig":
        """OLMoE-1B-7B (Muennighoff et al. 2024): 64 experts of width
        1024, 8 a token with the softmax's weights unrenormalised,
        normalised queries and keys, full attention."""
        return LlamaConfig(
            vocab_size=50304,
            block_size=4096,
            n_layer=16,
            n_head=16,
            n_kv_head=16,
            n_embd=2048,
            intermediate=1024,
            rope_theta=10000.0,
            rms_eps=1e-5,
            n_experts=64,
            moe_top_k=8,
            moe_renorm_top_k=False,
            qk_norm=True,
        )

    @staticmethod
    def moe_tiny() -> "LlamaConfig":
        return dataclasses.replace(
            LlamaConfig.tiny(), n_experts=4, moe_top_k=2
        )

    def _moe_cfg(self):
        from dlrover_tpu.models.moe import MoEConfig

        return MoEConfig(
            n_embd=self.n_embd,
            n_experts=self.n_experts,
            expert_hidden=self.intermediate,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_weight=self.moe_aux_loss_weight,
            z_loss_weight=self.moe_z_loss_weight,
            dtype=self.dtype,
            gated=True,  # SwiGLU experts, as Mixtral and OLMoE
            renorm_top_k=self.moe_renorm_top_k,
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Llama init: normal(0, 0.02) everywhere, residual-output
    projections scaled down by 1/sqrt(2*n_layer) (GPT-2 convention the
    reference inherits through HF init overrides)."""
    E, L, I = cfg.n_embd, cfg.n_layer, cfg.intermediate
    D, Hkv = cfg.head_dim, cfg.n_kv_head
    std = 0.02
    resid_std = std / np.sqrt(2 * L)
    keys = jax.random.split(key, 9)

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(
            cfg.dtype
        )

    def stack(k, shape, s=std):
        return norm(k, (L,) + shape, s)

    blocks = {
        "rms1": jnp.ones((L, E), jnp.float32),
        "wq": stack(keys[1], (E, E)),
        "wk": stack(keys[2], (E, Hkv * D)),
        "wv": stack(keys[3], (E, Hkv * D)),
        "wo": stack(keys[4], (E, E), resid_std),
        "rms2": jnp.ones((L, E), jnp.float32),
    }
    if cfg.qkv_bias:
        blocks.update(
            bq=jnp.zeros((L, E), cfg.dtype),
            bk=jnp.zeros((L, Hkv * D), cfg.dtype),
            bv=jnp.zeros((L, Hkv * D), cfg.dtype),
        )
    if cfg.qk_norm:
        blocks.update(
            q_norm=jnp.ones((L, E), jnp.float32),
            k_norm=jnp.ones((L, Hkv * D), jnp.float32),
        )
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import init_moe_params

        per_layer = [
            init_moe_params(k, cfg._moe_cfg())
            for k in jax.random.split(keys[5], L)
        ]
        blocks["moe"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *per_layer
        )
    else:
        blocks.update(
            w_gate=stack(keys[5], (E, I)),
            w_up=stack(keys[6], (E, I)),
            w_down=stack(keys[7], (I, E), resid_std),
        )
    return {
        "wte": norm(keys[0], (cfg.vocab_size, E)),
        "blocks": blocks,
        "rmsf": jnp.ones((E,), jnp.float32),
        "lm_head": norm(keys[8], (cfg.vocab_size, E)),
    }


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Logical sharding axes per leaf (tensor axis on heads/mlp, fsdp
    on embed — the same rule table as GPT, parallel/sharding.py)."""
    blocks = {
        "rms1": ("layers", None),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "heads"),
        "wv": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "embed"),
        "rms2": ("layers", None),
    }
    if cfg.qkv_bias:
        blocks.update(
            bq=("layers", "heads"),
            bk=("layers", "heads"),
            bv=("layers", "heads"),
        )
    if cfg.qk_norm:
        blocks.update(
            q_norm=("layers", "heads"),
            k_norm=("layers", "heads"),
        )
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import moe_logical_axes

        blocks["moe"] = {
            name: ("layers",) + axes
            for name, axes in moe_logical_axes(
                gated=cfg._moe_cfg().gated
            ).items()
        }
    else:
        blocks.update(
            w_gate=("layers", "embed", "mlp"),
            w_up=("layers", "embed", "mlp"),
            w_down=("layers", "mlp", "embed"),
        )
    return {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "rmsf": (None,),
        "lm_head": ("vocab", "embed"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps
    )
    return (x32 * scale * g).astype(x.dtype)


def rope_table(cfg: LlamaConfig, t: int) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables [T, rot/2] in f32, computed once outside the
    layer scan (the reference recomputes them per forward inside the
    HF rotary module). ``rot = head_dim * rotary_pct`` — partial
    rotary (GLM) just shrinks the table; apply_rope reads the rotated
    width off the table shape."""
    d2 = int(cfg.head_dim * cfg.rotary_pct) // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (np.arange(0, d2, dtype=np.float32) / d2)
    )
    pos = jnp.arange(t, dtype=jnp.float32)
    ang = pos[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, T, H, D] -> rotated, split-halves convention (HF
    Llama). When the table covers fewer than D dims (rotary_pct < 1),
    the trailing D - 2*table dims pass through unrotated."""
    d2 = cos.shape[-1]
    x1, x2 = x[..., :d2], x[..., d2:2 * d2]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    parts = [x1 * c - x2 * s, x2 * c + x1 * s]
    if 2 * d2 < x.shape[-1]:
        parts.append(x[..., 2 * d2:])
    return jnp.concatenate(parts, axis=-1)


def attention_half(h, lp, cfg: LlamaConfig, attn_fn, cos, sin):
    """The attention half of a block on the normed input ``h``:
    projections, rotation, attention, out-projection. Returns
    ``att @ wo`` WITHOUT the residual, so that a family that scales
    the branch (models/granite_hybrid.py) can put its multiplier
    between. ``cos`` None leaves queries and keys unrotated (no
    positional embedding).

    Where ``attn_fn`` is the flash kernels' and a head fills whole
    lanes (``flash_attention.wide_form``, ``wide_head_size``), q, k,
    v and the result stay ``[B, T, H*D]`` from the projections to
    ``wo``: the kernels read a head as a column block, a key-value
    head by the block's index. Every other attention function and
    head size takes ``[B, T, H, D]`` views."""
    B, T, E = h.shape
    H, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    from dlrover_tpu.accelerate.remat import ATTN_IN, keep
    from dlrover_tpu.ops.flash_attention import wide_form, wide_head_size

    wide = wide_form(attn_fn) if wide_head_size(D) else None
    # Named for remat="full" (accelerate/remat.py KEPT), before
    # the head repeat; ``att @ wo`` below is recomputed from the
    # flash forward's kept output. On the wide path q and k are named
    # as the kernels read them, rotated, where nothing between the
    # projection and the rotation needs the projection's own value
    # for its backward (a norm does): the same bytes kept, and the
    # backward does not rotate them a second time.
    rotated = wide is not None and cos is not None and not cfg.qk_norm
    q, k, v = (
        h @ lp[w] if rotated and w != "wv" else keep(h @ lp[w], ATTN_IN)
        for w in ("wq", "wk", "wv")
    )
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if cfg.qk_norm:
        q = _rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = _rms_norm(k, lp["k_norm"], cfg.rms_eps)
    if wide is not None:
        from dlrover_tpu.ops.rope import rope_wide

        def attend(q, k, v, wo):
            if cos is not None:
                q = rope_wide(q, cos, sin, H)
                k = rope_wide(k, cos, sin, Hkv)
            if rotated:
                q, k = keep(q, ATTN_IN), keep(k, ATTN_IN)
            return wide(q, k, v, n_head=H, n_kv_head=Hkv) @ wo

        # A call of its own (``jax.jit``), as models/moe._sorted_moe's
        # and for its reason: where a block under ``jax.checkpoint``
        # itself consumes a kept value it rounds it once more, and
        # ``o`` as the kernel wrote it has no neighbour on the chip to
        # fuse that into (the 4-D entry's transposition was one): a
        # pass of its own over ``o``. Kept and consumed inside a call
        # it is left as it is.
        return jax.jit(attend)(q, k, v, lp["wo"])
    q = q.reshape(B, T, H, D)
    k = k.reshape(B, T, Hkv, D)
    v = v.reshape(B, T, Hkv, D)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if Hkv != H and not getattr(attn_fn, "supports_gqa", False):
        # grouped-query: broadcast each kv head over its query
        # group. GQA-aware attention (the seq-parallel
        # constructors) takes the COMPACT k/v instead — the
        # ring/a2a then move 1/q_per_kv the bytes and broadcast
        # per block on-device; the flash kernels' wide entry above
        # has it by construction, the group in a block's index.
        k = jnp.repeat(k, cfg.q_per_kv, axis=2)
        v = jnp.repeat(v, cfg.q_per_kv, axis=2)
    att = attn_fn(q, k, v).reshape(B, T, H * D)
    return att @ lp["wo"]


def _block(x, lp, cfg: LlamaConfig, attn_fn, cos, sin):
    """One block. Returns (x, aux_loss) — aux is 0 for dense MLPs,
    this layer's share of the router losses for MoE blocks."""
    # The scopes models/gpt.py has: the module profiler and the
    # device trace's operation metadata attribute cost by them.
    with jax.named_scope("attn"):
        h = _rms_norm(x, lp["rms1"], cfg.rms_eps)
        att_out = attention_half(h, lp, cfg, attn_fn, cos, sin)
    with jax.named_scope("mlp"):
        x = x + att_out
        h = _rms_norm(x, lp["rms2"], cfg.rms_eps)
        return mlp_tail(x, h, lp, cfg)


def swiglu(h, lp):
    """The dense SwiGLU MLP on the normed input ``h``, WITHOUT the
    residual (a family that scales the branch puts its multiplier
    between, models/granite_hybrid.py)."""
    from dlrover_tpu.accelerate.remat import MLP_HIDDEN, keep

    gate = keep(h @ lp["w_gate"], MLP_HIDDEN)
    up = keep(h @ lp["w_up"], MLP_HIDDEN)
    gated = jax.nn.silu(gate) * up
    return gated @ lp["w_down"]


def mlp_tail(x, h, lp, cfg: LlamaConfig):
    """Dense-SwiGLU or expert-routed MLP tail of a block. Shared by
    the training block and the decode paths (models/generate.py).
    Returns (x + mlp(h), aux_loss): the layer's weighted router losses
    over ``n_layer``, so that the callers' sum over layers (the scan
    here, the pipeline's stages) is the mean over layers."""
    if cfg.n_experts > 0:
        from dlrover_tpu.models.moe import moe_mlp

        y, aux = moe_mlp(lp["moe"], h, cfg._moe_cfg())
        return x + y.astype(x.dtype), aux / cfg.n_layer
    return x + swiglu(h, lp), jnp.zeros((), jnp.float32)


def head_logits(params: Params, x: jax.Array) -> jax.Array:
    """lm_head projection in f32 — the single definition shared by
    forward() and the loss paths."""
    with jax.named_scope("head"):
        return jnp.einsum(
            "...te,ve->...tv", x, params["lm_head"],
            preferred_element_type=jnp.float32,
        )


def default_attention_for(cfg: LlamaConfig) -> Callable:
    """Same auto-selection as GPT (gpt.default_attention_for reads
    only block_size/use_flash_attention, which both configs carry)."""
    from dlrover_tpu.models import gpt

    return gpt.default_attention_for(cfg)


def backbone_with_aux(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Optional[Callable] = None,
) -> tuple:
    """Forward without the head: ([B,T,E] hidden, summed MoE aux
    loss — 0 for dense configs)."""
    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    B, T = tokens.shape
    cos, sin = rope_table(cfg, T)
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)

    from dlrover_tpu.accelerate.remat import wire_block
    from dlrover_tpu.models import layers

    block = wire_block(
        lambda x, lp, af: _block(
            x, lp, cfg=cfg, attn_fn=af, cos=cos, sin=sin
        ),
        cfg.remat,
        attn_fn,
    )

    def layer(carry, lp):
        x, aux_sum = carry
        x, aux = block(x, lp)
        return x, aux_sum + aux

    with jax.named_scope("layers"):
        x, aux = layers.run(
            layer, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
    return _rms_norm(x, params["rmsf"], cfg.rms_eps), aux


def backbone(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    return backbone_with_aux(params, tokens, cfg, attn_fn)[0]


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    return head_logits(params, backbone(params, tokens, cfg, attn_fn))


def loss_fn(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    x, aux = backbone_with_aux(params, tokens, cfg, attn_fn)
    logits = head_logits(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll) + aux


def loss_fn_fused(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Optional[Callable] = None,
    num_chunks: int = 8,
) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x, aux = backbone_with_aux(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        loss = fused_cross_entropy(
            x.reshape(n, -1),
            params["lm_head"],
            targets.reshape(n),
            num_chunks,
        )
    return loss + aux


def flops_per_token(cfg: LlamaConfig) -> float:
    """PaLM-convention training FLOPs/token (matches the reference's
    compute_llama2_training_flops in examples/llama2/example_utils.py:
    6 * matmul params + attention score/value matmuls). MoE counts
    only the *active* experts' matmuls (top_k) plus the router."""
    E, L, I = cfg.n_embd, cfg.n_layer, cfg.intermediate
    kv = cfg.n_kv_head * cfg.head_dim
    if cfg.n_experts > 0:
        # SwiGLU experts: gate+in+out matmuls per active expert
        mlp = 3 * cfg.moe_top_k * E * I + E * cfg.n_experts
    else:
        mlp = 3 * E * I  # gate + up + down
    per_layer = E * E + 2 * E * kv + E * E + mlp
    n_matmul = L * per_layer + cfg.vocab_size * E
    attn = 12 * L * cfg.block_size * E
    return 6.0 * n_matmul + attn
