"""GPT language model, TPU-first.

Capability parity with the reference's nanoGPT example
(/root/reference/examples/pytorch/nanogpt/train.py — the model DLRover
uses for its elastic-training demos and BASELINE north star), designed
as an idiomatic JAX program rather than a port:

* pure-functional param pytree with *logical sharding axes* per leaf
  (parallel/sharding.py) — GSPMD shards it for DP/FSDP/TP/SP from one
  rule table, replacing torch DDP/FSDP wrappers;
* layers stacked and executed with ``lax.scan`` (one compile of one
  block regardless of depth);
* bf16 activations/weights with f32 layernorm + logits, MXU-friendly
  head dims;
* optional ring attention over the ``seq`` mesh axis for long context;
* ``jax.checkpoint`` rematerialization policy for HBM headroom.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    block_size: int = 1024
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    dropout: float = 0.0  # elastic training defaults to 0 (nanoGPT)
    dtype: Any = jnp.bfloat16
    # Policy names from accelerate/remat.py: "none" | "full" |
    # "attention" | "dots" | "offload" (block residuals to pinned
    # host RAM). True = full block remat; False = none; "attention" =
    # checkpoint
    # only the attention inner fn — the [B,H,T,T] softmax is the one
    # activation that doesn't fit, and recomputing it costs ~4% FLOPs
    # vs ~33% for full remat (measured on v5e: 0.29 -> 0.37 MFU).
    remat: Any = True
    # None = auto (flash on TPU at long context); True/False forces.
    use_flash_attention: Optional[bool] = None
    # Declared attention masking. Decoder-only LMs are causal; the
    # auto_accelerate seq-parallel binding reads this so a non-causal
    # model config is never silently given a causal mask.
    causal: bool = True
    # Flash-attention tile override (block_q, block_k, block_q_bwd,
    # block_k_bwd); None = kernel defaults (default_block_sizes + the
    # forward blocks for the backward). The hardware autotune sweep
    # (tools/autotune_bwd_blocks.py) pins its winner here.
    attn_blocks: Optional[tuple] = None
    # lax.scan unroll factor for the layer stack. 1 = rolled (one
    # compiled block, smallest program); k>1 lets XLA fuse across k
    # consecutive layers and amortize the scan-carry
    # dynamic-update-slice traffic the r5 step profile attributes
    # ~16% of step time to. Any k >= 1 works — lax.scan handles a
    # remainder group and clamps k > n_layer (tests assert both). A
    # hardware-autotune axis, not a semantic knob.
    scan_unroll: int = 1

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @staticmethod
    def nano() -> "GPTConfig":
        """The reference nanoGPT 'baby GPT' demo size."""
        return GPTConfig(
            vocab_size=50304, block_size=256, n_layer=6, n_head=6,
            n_embd=384,
        )

    @staticmethod
    def gpt2() -> "GPTConfig":
        return GPTConfig()


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: GPTConfig) -> Params:
    """GPT-2-style init (normal 0.02, residual projections scaled by
    1/sqrt(2*n_layer)). Layer params are stacked on a leading 'layers'
    dim for lax.scan."""
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    std = 0.02
    resid_std = 0.02 / np.sqrt(2 * cfg.n_layer)
    E, H, L = cfg.n_embd, cfg.n_head, cfg.n_layer

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(
            cfg.dtype
        )

    ks = jax.random.split(k_blocks, 6)

    def stack(k, shape, s=std):
        return norm(k, (L,) + shape, s)

    params: Params = {
        "wte": norm(k_wte, (cfg.vocab_size, E)),
        "wpe": norm(k_wpe, (cfg.block_size, E)),
        "blocks": {
            "ln1_g": jnp.ones((L, E), jnp.float32),
            "ln1_b": jnp.zeros((L, E), jnp.float32),
            "wqkv": stack(ks[0], (E, 3 * E)),
            "wo": stack(ks[1], (E, E), resid_std),
            "ln2_g": jnp.ones((L, E), jnp.float32),
            "ln2_b": jnp.zeros((L, E), jnp.float32),
            "wi": stack(ks[2], (E, 4 * E)),
            "bi": jnp.zeros((L, 4 * E), cfg.dtype),
            "wo2": stack(ks[3], (4 * E, E), resid_std),
            "bo2": jnp.zeros((L, E), cfg.dtype),
        },
        "lnf_g": jnp.ones((E,), jnp.float32),
        "lnf_b": jnp.zeros((E,), jnp.float32),
    }
    return params


def param_logical_axes(cfg: GPTConfig) -> Params:
    """Logical sharding axes per parameter leaf (same tree shape)."""
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_g": ("layers", None),
            "ln1_b": ("layers", None),
            "wqkv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed"),
            "ln2_g": ("layers", None),
            "ln2_b": ("layers", None),
            "wi": ("layers", "embed", "mlp"),
            "bi": ("layers", "mlp"),
            "wo2": ("layers", "mlp", "embed"),
            "bo2": ("layers", None),
        },
        "lnf_g": (None,),
        "lnf_b": (None,),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _layer_norm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * g + b
    return out.astype(x.dtype)


def _default_attention(q, k, v, causal=True, window=None, scale=None):
    """Plain fused attention (single-shard fallback; the sharded path
    comes from parallel.ring_attention.make_sharded_attention).
    ``window`` applies the same Mistral-style sliding-window band as
    the flash kernel (query i sees keys (i-window, i]); ``scale``
    multiplies the scores, as the flash kernel's does (None: one over
    the root of the head size)."""
    if window is not None and not causal:
        # Same contract as flash_attention: a one-sided band with
        # bidirectional attention would mean different models per
        # backend, not a graceful fallback.
        raise ValueError(
            "window (sliding-window attention) requires causal=True"
        )
    b, lq, h, d = q.shape
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    s = s / np.sqrt(d) if scale is None else s * scale
    if causal or window is not None:
        pos = jnp.arange(lq)
        mask = jnp.ones((lq, lq), bool)
        if causal:
            mask &= pos[:, None] >= pos[None, :]
        if window is not None:
            mask &= (pos[:, None] - pos[None, :]) < window
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block(x, lp, cfg: GPTConfig, attn_fn):
    """One transformer block. lp = this layer's param slice.

    The ``jax.named_scope`` annotations are load-bearing, for two
    readers: the module profiler (utils/module_profiler.py)
    attributes FLOPs / bytes per scope from the jaxpr, feeding the
    strategy engine's roofline prior and the TP planner's per-edge
    costs; ``obs.profiling.compiled_scopes`` reads them back from the
    compiled step's ``op_name`` metadata, so that a device profile
    can be read by scope (docs/OBSERVABILITY.md, "Device time by
    scope")."""
    # What remat="full" keeps is named here (accelerate/remat.py
    # KEPT): the projection into attention and the MLP's hidden
    # product. ``att @ wo`` is not: it is recomputed from the flash
    # forward's kept output.
    from dlrover_tpu.accelerate.remat import ATTN_IN, MLP_HIDDEN, keep

    B, T, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    with jax.named_scope("attn"):
        h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = keep(h @ lp["wqkv"], ATTN_IN)  # [B,T,3E]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        att = attn_fn(q, k, v).reshape(B, T, E)
        att_out = att @ lp["wo"]
    with jax.named_scope("mlp"):
        x = x + att_out
        h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"])
        h = jax.nn.gelu(keep(h @ lp["wi"], MLP_HIDDEN) + lp["bi"])
        x = x + h @ lp["wo2"] + lp["bo2"]
    return x


def default_attention_for(cfg: GPTConfig) -> Callable:
    """Pick the attention implementation for this config.

    On TPU the Pallas flash kernel (ops/flash_attention.py) wins from
    ~512 context up (measured v5e, GPT-2 shapes: fwd+bwd 6.3ms/layer
    flash vs 9.4ms XLA at 1024 — XLA materializes [B,H,T,T] f32 scores
    in HBM) and is mandatory beyond ~4k where the scores exceed HBM.
    ``cfg.use_flash_attention`` forces either path; None auto-selects
    (flash on TPU from 512 context up).
    """
    use_flash = cfg.use_flash_attention
    if use_flash is None:
        use_flash = (
            jax.default_backend() == "tpu" and cfg.block_size >= 512
        )
    causal = getattr(cfg, "causal", True)
    window = getattr(cfg, "sliding_window", None)
    if use_flash:
        from dlrover_tpu.ops.flash_attention import flash_attention

        from dlrover_tpu.ops.flash_attention import blocks_kwargs

        block_kwargs = blocks_kwargs(getattr(cfg, "attn_blocks", None))
        return functools.partial(
            flash_attention, causal=causal, window=window,
            **block_kwargs,
        )
    return functools.partial(
        _default_attention, causal=causal, window=window
    )


def backbone(
    params: Params,
    tokens: jax.Array,
    cfg: GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """Forward WITHOUT the unembedding: [B, T] -> final hidden
    [B, T, E]. Loss paths that fuse the vocab projection (fused
    cross-entropy) start here."""
    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"][tokens] + params["wpe"][:T][None]
        x = x.astype(cfg.dtype)
    from dlrover_tpu.accelerate.remat import wire_block
    from dlrover_tpu.models import layers

    block = wire_block(
        lambda x, lp, af: _block(x, lp, cfg=cfg, attn_fn=af),
        cfg.remat,
        attn_fn,
    )
    # "layers" owns what the stack itself costs (models/layers.py: of
    # the scanned layers the slices of the stacked parameters, the
    # stacking of what they keep, the while; of the in-line ones next
    # to nothing): obs.profiling compiled_scopes puts device time down
    # to it.
    with jax.named_scope("layers"):
        x = layers.run(block, x, params["blocks"], unroll=cfg.scan_unroll)
    return _layer_norm(x, params["lnf_g"], params["lnf_b"])


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    x = backbone(params, tokens, cfg, attn_fn)
    # Tied embeddings (nanoGPT): logits via wte^T, f32 for stable loss.
    with jax.named_scope("head"):
        logits = jnp.einsum(
            "bte,ve->btv",
            x,
            params["wte"],
            preferred_element_type=jnp.float32,
        )
    return logits


def loss_fn(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: GPTConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    logits = forward(params, tokens, cfg, attn_fn)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def loss_fn_fused(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: GPTConfig,
    attn_fn: Optional[Callable] = None,
    num_chunks: int = 8,
) -> jax.Array:
    """Same loss via the fused chunked cross-entropy
    (ops/cross_entropy.py): never materializes [B*T, V] log-softmax,
    backward matmuls get bf16 cotangents. Use for big batch*seq."""
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x = backbone(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        return fused_cross_entropy(
            x.reshape(n, -1), params["wte"], targets.reshape(n),
            num_chunks,
        )


def num_params(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def flops_per_token(cfg: GPTConfig) -> float:
    """Training FLOPs per token via the standard PaLM MFU convention:
    6*N_matmul + 12*L*T*E (attention score+value matmuls, no causal
    discount). Used for MFU/HFU accounting (ref atorch AProfiler role).

    Per-layer matmul params: wqkv 3E^2 + wo E^2 + wi 4E^2 + wo2 4E^2
    = 12E^2; plus the (tied) unembedding V*E.
    """
    E, L = cfg.n_embd, cfg.n_layer
    n_matmul = 12 * L * E * E + cfg.vocab_size * E
    attn = 12 * L * cfg.block_size * E
    return 6.0 * n_matmul + attn
