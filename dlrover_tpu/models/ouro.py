"""Looped language model (the Ouro shape: ByteDance's ``OuroForCausalLM``;
Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models"): ONE stack of layers run ``ut_steps`` times a step on the
same weights, a learned exit gate, and a training loss that the gate
weighs over the passes' logits.

    block:  a = x + n2(attn(n1(x)))          four RMS norms a layer
            y = a + n4(swiglu(n3(a)))        ("sandwich": in and out)
    loop:   h_0 = wte[tokens]
            h_t = rmsf(stack(h_{t-1}))       t = 1..ut_steps
    gate:   lambda_t = sigmoid(h_t . gate_w + gate_b)      float32
            p_1 = lambda_1,  p_t = lambda_t prod_{j<t}(1 - lambda_j),
            p_last = prod_{j<last}(1 - lambda_j)           the mass left
    loss:   mean_i [ sum_t p_t(i) nll_t(i) - beta H(p(i)) ]

``stack`` is the same ``n_layer`` layers every pass; the final norm
closes every pass, and its output is both what the head reads at that
pass and what the next pass starts from. ``nll_t`` is the
cross-entropy of ``h_t @ lm_head^T``; ``H`` the entropy of the
``ut_steps``-way exit distribution.

* the passes are ``ut_steps`` calls in a row of ONE jitted pass (the
  layers' ``lax.scan`` over the stacked parameters, then the closing
  norm), traced and lowered once whatever ``ut_steps``: autodiff sums
  each weight's gradient over the passes, and what a block keeps
  under ``remat="full"`` (accelerate/remat.py ``KEPT``) is stacked
  once, ``[n_layer, ...]`` a pass, by the scan whose backward reads it
  (a ``lax.scan`` over the passes would stack it again, ``[ut_steps,
  n_layer, ...]``, a copy of every kept byte in and another out);
* the attention half, the SwiGLU and the rotary tables are
  models/llama.py's, the attention chooser every family's (flash on
  the TPU from 512 tokens up);
* the loss is ONE call of ops/cross_entropy.py's head on the
  ``ut_steps x B x T`` rows of all passes, each row weighted by its
  ``p_t(i) / (B T)``; the rows' losses come back as the weights'
  gradient, which is how the gate learns.

``ut_steps`` is the model's own published key (``total_ut_steps``): no
flag chooses a looped or an unlooped program. The early exit at
inference (``early_exit_threshold``) is serving's and is not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.models import llama

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The defaults are Ouro-2.6B's published values."""

    vocab_size: int = 49152
    block_size: int = 4096
    n_layer: int = 48
    n_head: int = 16
    n_kv_head: int = 16
    n_embd: int = 2048
    intermediate: int = 5632
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # ``total_ut_steps``: how many times a step runs the stack.
    ut_steps: int = 4
    # beta, the weight of the exit distribution's entropy in the loss.
    exit_entropy_coef: float = 0.05
    init_std: float = 0.02
    # Norm gains are drawn as 1 + jitter x normal and the gate's bias as
    # jitter x normal: a gain of exactly 1 or a bias of exactly 0 hides
    # its own omission from a comparison with a reference.
    jitter: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: Any = "full"
    use_flash_attention: Optional[bool] = None

    @property
    def attention_cfg(self) -> llama.LlamaConfig:
        """What models/llama.py's attention half, rotary table and
        attention chooser read, from this configuration."""
        return llama.LlamaConfig(
            vocab_size=self.vocab_size, block_size=self.block_size,
            n_layer=self.n_layer, n_head=self.n_head,
            n_kv_head=self.n_kv_head, n_embd=self.n_embd,
            intermediate=self.intermediate, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype, remat=self.remat,
            use_flash_attention=self.use_flash_attention,
        )

    @staticmethod
    def tiny() -> "OuroConfig":
        return OuroConfig(
            vocab_size=256, block_size=64, n_layer=2, n_head=4,
            n_kv_head=4, n_embd=64, intermediate=128, ut_steps=4,
            jitter=0.1, dtype=jnp.float32, remat=False,
        )


_NORMS = ("rms1", "rms2", "rms3", "rms4")


def init_params(key: jax.Array, cfg: OuroConfig) -> Params:
    """normal(0, init_std) matrices, the two projections back into the
    residual stream scaled by 1/sqrt(2 x n_layer) as the program's
    other families are; one set of layers whatever ``ut_steps``."""
    E, L, I = cfg.n_embd, cfg.n_layer, cfg.intermediate
    kvd = cfg.n_kv_head * (E // cfg.n_head)
    std = cfg.init_std
    resid_std = std / np.sqrt(2 * L)
    keys = iter(jax.random.split(key, 16))

    def normal(shape, s=std):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * s
        ).astype(cfg.dtype)

    def gain(shape):
        return 1.0 + cfg.jitter * jax.random.normal(
            next(keys), shape, jnp.float32
        )

    blocks = {name: gain((L, E)) for name in _NORMS}
    blocks.update(
        wq=normal((L, E, E)),
        wk=normal((L, E, kvd)),
        wv=normal((L, E, kvd)),
        wo=normal((L, E, E), resid_std),
        w_gate=normal((L, E, I)),
        w_up=normal((L, E, I)),
        w_down=normal((L, I, E), resid_std),
    )
    return {
        "wte": normal((cfg.vocab_size, E)),
        "blocks": blocks,
        "rmsf": gain((E,)),
        "gate_w": jax.random.normal(next(keys), (E,), jnp.float32) * std,
        "gate_b": cfg.jitter * jax.random.normal(
            next(keys), (1,), jnp.float32
        ),
        "lm_head": normal((cfg.vocab_size, E)),
    }


def param_logical_axes(cfg: OuroConfig) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table, as models/llama.py's)."""
    blocks = {name: ("layers", None) for name in _NORMS}
    blocks.update(
        wq=("layers", "embed", "heads"),
        wk=("layers", "embed", "heads"),
        wv=("layers", "embed", "heads"),
        wo=("layers", "heads", "embed"),
        w_gate=("layers", "embed", "mlp"),
        w_up=("layers", "embed", "mlp"),
        w_down=("layers", "mlp", "embed"),
    )
    return {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "rmsf": (None,),
        "gate_w": (None,),
        "gate_b": (None,),
        "lm_head": ("vocab", "embed"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _block(x, lp, attn_fn, *, cfg: OuroConfig, cos, sin):
    eps = cfg.rms_eps
    with jax.named_scope("attn"):
        h = llama._rms_norm(x, lp["rms1"], eps)
        h = llama.attention_half(h, lp, cfg.attention_cfg, attn_fn, cos, sin)
        x = x + llama._rms_norm(h, lp["rms2"], eps)
    with jax.named_scope("mlp"):
        h = llama._rms_norm(x, lp["rms3"], eps)
        return x + llama._rms_norm(llama.swiglu(h, lp), lp["rms4"], eps)


def _close_pass(x, params, cfg: OuroConfig):
    """The norm that closes a pass -> (what the next pass starts from,
    what the head and the gate read at this one): the same array."""
    h = llama._rms_norm(x, params["rmsf"], cfg.rms_eps)
    return h, h


def default_attention_for(cfg: OuroConfig) -> Callable:
    return llama.default_attention_for(cfg.attention_cfg)


def passes(
    params: Params,
    tokens: jax.Array,
    cfg: OuroConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """[B, T] tokens -> [B, ut_steps, T, E]: the hidden state after the
    norm that closes each pass. Batch rows outermost, so that under a
    mesh a device's rows of every pass are its own."""
    from dlrover_tpu.accelerate import remat

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    cos, sin = llama.rope_table(cfg.attention_cfg, tokens.shape[1])
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
    block = remat.wire_block(
        lambda x, lp, af: _block(x, lp, af, cfg=cfg, cos=cos, sin=sin),
        cfg.remat, attn_fn,
    )

    # One call (``jax.jit``), made here, once a trace of the loss: the
    # block and both flash kernels are traced and lowered once whatever
    # ``ut_steps``, and what stands in the module's place of
    # ``_close_pass`` while the loss is traced is what the program runs.
    @jax.jit
    def one_pass(x, blocks, closing):
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(
                lambda x, lp: (block(x, lp), None), x, blocks
            )
        return _close_pass(x, closing, cfg)

    closing = {"rmsf": params["rmsf"]}
    hs = []
    with jax.named_scope("ut_loop"):
        for _ in range(cfg.ut_steps):
            x, h = one_pass(x, params["blocks"], closing)
            hs.append(h)
        hs = jnp.stack(hs, axis=1)
    full = remat.canonical(cfg.remat) == "full"
    obs.event(
        "ouro.loop", ut_steps=cfg.ut_steps, layers=cfg.n_layer,
        layer_scans=cfg.ut_steps,
        kept_names=list(remat.last_kept()) if full else [],
    )
    return hs


def exit_distribution(params: Params, hs: jax.Array):
    """hs [B, S, T, E], one row a pass -> (p [B, S, T] float32, the
    exit distribution over the passes at each position; H [B, T], its
    entropy)."""
    with jax.named_scope("exit_gate"):
        logits = jnp.einsum(
            "...e,e->...", hs.astype(jnp.float32), params["gate_w"],
            precision=jax.lax.Precision.HIGHEST,
        ) + params["gate_b"][0]
        # log lambda_t and log (1 - lambda_t); the mass that reaches
        # pass t is the product of the (1 - lambda_j) before it.
        log_exit = jax.nn.log_sigmoid(logits)
        log_stay = jax.nn.log_sigmoid(-logits)
        reached = jnp.cumsum(log_stay, axis=-2) - log_stay
        n = logits.shape[-2]
        is_last = (jnp.arange(n) == n - 1)[:, None]
        log_p = reached + jnp.where(is_last, 0.0, log_exit)
        p = jnp.exp(log_p)
        return p, -jnp.sum(p * log_p, axis=-2)


def forward(params, tokens, cfg: OuroConfig, attn_fn=None):
    """([B, ut_steps, T, V] float32 logits, [B, ut_steps, T] exit
    distribution)."""
    hs = passes(params, tokens, cfg, attn_fn)
    p, _ = exit_distribution(params, hs)
    return llama.head_logits(params, hs), p


def loss_fn(params, tokens, targets, cfg: OuroConfig, attn_fn=None):
    """The loss with every pass's logits whole (tests, small sizes)."""
    hs = passes(params, tokens, cfg, attn_fn)
    p, entropy = exit_distribution(params, hs)
    logp = jax.nn.log_softmax(llama.head_logits(params, hs), axis=-1)
    gold = jnp.broadcast_to(targets[:, None, :], hs.shape[:-1])
    nll = -jnp.take_along_axis(logp, gold[..., None], axis=-1)[..., 0]
    return jnp.mean(
        jnp.sum(p * nll, axis=1) - cfg.exit_entropy_coef * entropy
    )


def loss_fn_fused(params, tokens, targets, cfg: OuroConfig,
                  attn_fn=None, num_chunks: int = 8) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    hs = passes(params, tokens, cfg, attn_fn)
    p, entropy = exit_distribution(params, hs)
    b, s, t, e = hs.shape
    with jax.named_scope("head"):
        weighted = fused_cross_entropy(
            hs.reshape(b * s * t, e),
            params["lm_head"],
            jnp.broadcast_to(targets[:, None, :], (b, s, t)).reshape(-1),
            num_chunks,
            (p / (b * t)).reshape(-1),
        )
    with jax.named_scope("exit_gate"):
        return weighted - cfg.exit_entropy_coef * jnp.mean(entropy)
