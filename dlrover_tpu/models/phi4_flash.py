"""Decoder-hybrid-decoder language model (the Phi-4-mini-flash shape,
huggingface ``phi4flash``; Ren et al. 2025, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", SambaY): a
self-decoder of Mamba-1 mixers and sliding-window attention, one full
attention layer, and a cross-decoder whose layers read what two
layers before them made.

    h = wte[tokens]
    every layer:  h = h + mixer(ln_1(h));  h = h + mlp(ln_2(h))
    logits = ln_f(h) @ wte^T                          (tied table)

For ``n`` layers (``n % 4 == 0``), layer ``l`` from 0 has a state-space
mixer where ``l`` is even and attention where it is odd:

* ``l < n/2``: ``mamba`` (even), ``attn_window`` (odd, keys
  ``(t - sliding_window, t]``);
* ``l = n/2``: ``mamba_memory``: a Mamba layer whose scan output
  (before the gate, with the ``D`` skip) is **the memory** ``m``;
* ``l = n/2 + 1``: ``attn_full``, whose keys and values are **the
  shared K, V**;
* ``l >= n/2 + 2``: ``gmu`` (even): ``(m * silu(u w_in)) w_out``, the
  memory gated element by element by the layer's own input;
  ``attn_cross`` (odd): queries of its own on the shared K, V.

* the Mamba-1 mixer: ``[xc | z] = u w_in``; ``xs = silu(conv(xc))``
  (ops/causal_conv.py, width ``d_conv``, with bias); ``[dr | B | C] =
  xs w_x``; ``dt = softplus(dr w_dt + b_dt)``; ``A = -exp(A_log)``;
  the selective scan (ops/selective_scan.py); ``(y * silu(z)) w_out``.
* differential attention (window, full and cross alike): adjacent
  heads are a pair, ``a = P1 V - lam P2 V`` with ``V`` the pair's two
  value heads side by side, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` by the layer's PUBLISHED
  index; an RMS norm over the pair's ``2 d`` columns with one gain a
  layer, times ``1 - lam0``; ``w_o``. Two calls of the attention
  function a layer, ``(q1, k1, V)`` and ``(q2, k2, V)``: keys ``d``
  wide and values ``2 d``, which ``ops/flash_attention.py`` takes as
  it is. No rotation and no position table: the state-space layers
  carry position.
* LayerNorm is ``gpt._layer_norm``, the MLP ``llama.swiglu`` and the
  loss ``fused_cross_entropy`` on the tied table: shared with the
  other families, not copied.

A configuration holds an explicit tuple of layer kinds and the
published index of its first layer: the whole stack by the rule, or a
slice of it (a pipeline stage). A slice must hold the producer of
whatever its layers read; the constructor refuses one that does not.
Adjacent ``(mamba, attn_window)`` and ``(gmu, attn_cross)`` pairs are
units; equal units in a row are one run, stacked on a leading axis
and run by ``models/layers.py`` ``run`` (in line up to three, scanned
beyond: one step program whatever the depth). The memory and the
shared K, V enter a run as constants of its scan, and autodiff sums
their cotangents over the readers. ``remat="full"`` keeps them by name
where they are made (accelerate/remat.py), so a reader formed again in
the backward never runs the producer's layer.
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
from dlrover_tpu.models import gpt, llama

Params = Dict[str, Any]
MAMBA, WINDOW, MEMORY = "mamba", "attn_window", "mamba_memory"
FULL, GMU, CROSS = "attn_full", "gmu", "attn_cross"
KINDS = (MAMBA, WINDOW, MEMORY, FULL, GMU, CROSS)
ATTENTION = (WINDOW, FULL, CROSS)
# Adjacent layers that are one unit of a run.
PAIRS = ((MAMBA, WINDOW), (GMU, CROSS))
# The kind whose layer makes what a kind reads, and what that is.
READS = {GMU: MEMORY, CROSS: FULL}
MAKES = {MEMORY: "memory", FULL: "kv"}


def layer_kinds(n: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """The kinds of layers 0 .. n - 1 by the published rule."""
    if n % 4 or n <= 0 or mb_per_layer != 2:
        raise ValueError(
            f"{n} layers with a state-space mixer every {mb_per_layer}: "
            "the rule needs a multiple of 4 and mb_per_layer 2"
        )
    half = n // 2
    kinds = []
    for l in range(n):
        ssm = l % mb_per_layer == 0
        if l < half:
            kinds.append(MAMBA if ssm else WINDOW)
        elif l == half:
            kinds.append(MEMORY)
        elif l == half + 1:
            kinds.append(FULL)
        else:
            kinds.append(GMU if ssm else CROSS)
    return tuple(kinds)


def lam0(layer: int) -> float:
    """Differential attention's initial lambda of the layer with that
    published index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The defaults are Phi-4-mini-flash-reasoning's published values;
    the Mamba-1 sizes its config does not give are the family's
    (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` a
    sixteenth of the hidden size)."""

    vocab_size: int = 200064
    block_size: int = 4096
    kinds: Tuple[str, ...] = layer_kinds(32)
    first_layer: int = 0  # the published index of ``kinds[0]``
    n_embd: int = 2560
    n_head: int = 40
    n_kv_head: int = 20
    intermediate: int = 10240
    sliding_window: int = 512
    ln_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    scan_chunk: int = 64
    # Initial values: normal(0, init_std) matrices, the projections
    # back into the residual stream over sqrt(2 x layers) as the other
    # families; Mamba-1's own for the mixer (A_log = log(a_scale x
    # (1..d_state)) a channel, b_dt the inverse softplus of a step
    # drawn log-uniformly from [dt_min, dt_max], w_dt uniform within
    # dt_rank^-0.5, the convolution uniform within d_conv^-0.5); the
    # four lambda vectors normal(0, lambda_std). Gains, D and biases
    # are drawn around their usual values (``jitter``), not set to
    # them: a gain of exactly 1 or a bias of exactly 0 would hide its
    # own omission from a check against a reference.
    init_std: float = 0.02
    dt_min: float = 0.001
    dt_max: float = 0.1
    a_scale: float = 1.0
    lambda_std: float = 0.1
    subln_gain: float = 1.0  # the pair norm's gain is drawn around it
    jitter: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: Any = True  # accelerate/remat.py's named policies
    use_flash_attention: Optional[bool] = None

    def __post_init__(self):
        unknown = set(self.kinds) - set(KINDS)
        if unknown or not self.kinds:
            raise ValueError(f"kinds holds {sorted(unknown)!r}")
        for reader, maker in READS.items():
            what = MAKES[maker]
            if reader in self.kinds and (
                maker not in self.kinds
                or self.kinds.index(maker) > self.kinds.index(reader)
            ):
                raise ValueError(
                    f"a {reader} layer reads the {what} of a {maker} "
                    f"layer, which layers {self.first_layer} to "
                    f"{self.first_layer + len(self.kinds) - 1} do not hold "
                    "before it"
                )
        if any(self.kinds.count(maker) > 1 for maker in MAKES):
            raise ValueError("one memory and one set of shared keys a stack")
        if self.n_head % self.n_kv_head or self.n_kv_head % 2:
            raise ValueError(
                f"{self.n_head} heads over {self.n_kv_head} key/value "
                "heads do not pair"
            )

    @classmethod
    def stack(cls, n: int, first: int = 0, count: Optional[int] = None,
              **fields) -> "Phi4FlashConfig":
        """Layers ``[first, first + count)`` of the ``n``-layer stack
        the rule gives (all of it by default)."""
        kinds = layer_kinds(n)
        last = n if count is None else first + count
        if not 0 <= first < last <= n:
            raise ValueError(f"no layers {first} to {last - 1} of {n}")
        return cls(kinds=kinds[first:last], first_layer=first, **fields)

    @property
    def n_layer(self) -> int:
        return len(self.kinds)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def d_inner(self) -> int:
        return self.expand * self.n_embd

    @property
    def dt_rank(self) -> int:
        return -(-self.n_embd // 16)

    @property
    def runs(self) -> Tuple[Tuple[str, Tuple[str, ...], int, int], ...]:
        """The stack as runs of equal units: (name in the parameter
        tree, the unit's kinds, units in the run, the published index
        of the run's first layer)."""
        units, i = [], 0
        while i < len(self.kinds):
            pair = self.kinds[i: i + 2]
            unit = pair if pair in PAIRS else pair[:1]
            units.append((unit, self.first_layer + i))
            i += len(unit)
        out = []
        for unit, index in units:
            if out and out[-1][0] == unit:
                out[-1][1] += 1
            else:
                out.append([unit, 1, index])
        return tuple(
            (f"{i}_" + "__".join(unit), unit, count, index)
            for i, (unit, count, index) in enumerate(out)
        )

    def index_of(self, kind: str) -> Optional[int]:
        """The published index of the stack's one layer of ``kind``."""
        if kind not in self.kinds:
            return None
        return self.first_layer + self.kinds.index(kind)

    @staticmethod
    def tiny(n: int = 8, first: int = 0, count: Optional[int] = None,
             **fields) -> "Phi4FlashConfig":
        """Test size: 8/4 heads of 8 (4 / 2 pairs), a window of 4 in
        64 tokens, 128 channels of 4 states, chunks of 16."""
        fields = dict(dict(
            vocab_size=256, block_size=64, n_embd=64, n_head=8,
            n_kv_head=4, intermediate=128, sliding_window=4, d_state=4,
            scan_chunk=16, dtype=jnp.float32, remat=False,
        ), **fields)
        return Phi4FlashConfig.stack(n, first, count, **fields)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: Phi4FlashConfig, kind: str) -> Dict[str, tuple]:
    """Leaf name -> (shape, logical axes) of one layer of ``kind``."""
    E, I, Di, N = cfg.n_embd, cfg.intermediate, cfg.d_inner, cfg.d_state
    shapes = {
        "ln1_g": ((E,), (None,)), "ln1_b": ((E,), (None,)),
        "ln2_g": ((E,), (None,)), "ln2_b": ((E,), (None,)),
        "w_gate": ((E, I), ("embed", "mlp")),
        "w_up": ((E, I), ("embed", "mlp")),
        "w_down": ((I, E), ("mlp", "embed")),
    }
    if kind in (MAMBA, MEMORY):
        R = cfg.dt_rank
        shapes.update(
            # [xc | z] side by side: a split over ``tensor`` would cut
            # across them, so the width stays whole.
            w_in=((E, 2 * Di), ("embed", None)),
            conv_w=((cfg.d_conv, Di), (None, None)),
            conv_b=((Di,), (None,)),
            w_x=((Di, R + 2 * N), (None, None)),
            w_dt=((R, Di), (None, None)),
            b_dt=((Di,), (None,)),
            A_log=((Di, N), (None, None)),
            D=((Di,), (None,)),
            w_out=((Di, E), (None, "embed")),
        )
    elif kind == GMU:
        shapes.update(
            w_in=((E, Di), ("embed", None)),
            w_out=((Di, E), (None, "embed")),
        )
    else:
        d = cfg.head_dim
        q, kv = cfg.n_head * d, cfg.n_kv_head * d
        width = q if kind == CROSS else q + 2 * kv
        shapes.update(
            wqkv=((E, width), ("embed", "heads")),
            bqkv=((width,), (None,)),
            wo=((q, E), ("heads", "embed")),
            bo=((E,), (None,)),
            lambda_q1=((d,), (None,)), lambda_k1=((d,), (None,)),
            lambda_q2=((d,), (None,)), lambda_k2=((d,), (None,)),
            subln=((2 * d,), (None,)),
        )
    return shapes


def _init_leaf(key, name: str, shape, cfg: Phi4FlashConfig):
    """One leaf for every unit of a run: ``shape`` is [units, ...]."""
    f32 = jnp.float32
    if name in ("ln1_g", "ln2_g", "subln", "D"):
        around = cfg.subln_gain if name == "subln" else 1.0
        return around * (1.0 + cfg.jitter * jax.random.normal(key, shape, f32))
    if name in ("ln1_b", "ln2_b"):
        return cfg.jitter * jax.random.normal(key, shape, f32)
    if name in ("bqkv", "bo"):
        return (cfg.jitter * jax.random.normal(key, shape, f32)).astype(
            cfg.dtype
        )
    if name.startswith("lambda_"):
        return cfg.lambda_std * jax.random.normal(key, shape, f32)
    if name == "A_log":
        states = jnp.arange(1, shape[-1] + 1, dtype=f32)
        return jnp.broadcast_to(jnp.log(cfg.a_scale * states), shape)
    if name == "b_dt":
        lo, hi = np.log(cfg.dt_min), np.log(cfg.dt_max)
        step = jnp.exp(jax.random.uniform(key, shape, f32, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
    if name in ("conv_w", "conv_b", "w_dt"):
        # torch's Conv1d default, and Mamba-1's dt projection: uniform
        # within one over the root of the fan-in.
        fan_in = cfg.dt_rank if name == "w_dt" else cfg.d_conv
        bound = 1.0 / np.sqrt(fan_in)
        return jax.random.uniform(key, shape, f32, -bound, bound).astype(
            cfg.dtype
        )
    std = cfg.init_std
    if name in ("wo", "w_out", "w_down"):
        std = std / np.sqrt(2 * cfg.n_layer)
    return (jax.random.normal(key, shape, f32) * std).astype(cfg.dtype)


def init_params(key: jax.Array, cfg: Phi4FlashConfig) -> Params:
    k_table, k_final, k_runs = jax.random.split(key, 3)
    runs = {}
    for (name, unit, count, _), k_run in zip(
        cfg.runs, jax.random.split(k_runs, len(cfg.runs))
    ):
        runs[name] = {}
        for kind, k_kind in zip(unit, jax.random.split(k_run, len(unit))):
            shapes = _layer_shapes(cfg, kind)
            runs[name][kind] = {
                leaf: _init_leaf(k, leaf, (count,) + shape, cfg)
                for (leaf, (shape, _)), k in zip(
                    sorted(shapes.items()),
                    jax.random.split(k_kind, len(shapes)),
                )
            }
    table = jax.random.normal(
        k_table, (cfg.vocab_size, cfg.n_embd), jnp.float32
    )
    k_g, k_b = jax.random.split(k_final)
    return {
        "wte": (table * cfg.init_std).astype(cfg.dtype),
        "runs": runs,
        "lnf_g": 1.0 + cfg.jitter * jax.random.normal(
            k_g, (cfg.n_embd,), jnp.float32
        ),
        "lnf_b": cfg.jitter * jax.random.normal(
            k_b, (cfg.n_embd,), jnp.float32
        ),
    }


def param_logical_axes(cfg: Phi4FlashConfig) -> Params:
    """Logical sharding axes per leaf (parallel/sharding.py's rule
    table: ``embed`` on fsdp, ``heads`` / ``mlp`` / ``vocab`` on
    tensor); a leaf's leading dim is its run's units."""
    return {
        "wte": ("vocab", "embed"),
        "runs": {
            name: {
                kind: {
                    leaf: ("layers",) + axes
                    for leaf, (_, axes) in _layer_shapes(cfg, kind).items()
                }
                for kind in unit
            }
            for name, unit, _, _ in cfg.runs
        },
        "lnf_g": (None,),
        "lnf_b": (None,),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def mamba_mixer(u, lp, cfg: Phi4FlashConfig):
    """The Mamba-1 mixer on the normed input ``u`` [B, T, E], without
    the residual: (its result, the scan's output ``y`` before the
    gate, which is the memory where the layer is the one that makes
    it)."""
    from dlrover_tpu.accelerate.remat import SSM_IN, keep
    from dlrover_tpu.ops.causal_conv import conv_silu
    from dlrover_tpu.ops.selective_scan import selective_scan

    t = u.shape[1]
    inner, n, rank = cfg.d_inner, cfg.d_state, cfg.dt_rank
    # Named for remat="full" (accelerate/remat.py KEPT) with the
    # scan's output and chunk states (ops/selective_scan.py): the
    # convolution, the two small projections and the gate are
    # recomputed, the scan is not run again.
    proj = keep(u @ lp["w_in"], SSM_IN)
    z = proj[..., inner:]
    with jax.named_scope("ssm_conv"):
        xs = conv_silu(proj, lp["conv_w"], lp["conv_b"], start=0)
    dbc = xs @ lp["w_x"]
    b, c = dbc[..., rank: rank + n], dbc[..., rank + n:]
    dt = jax.nn.softplus(
        jnp.einsum(
            "btr,rd->btd", dbc[..., :rank], lp["w_dt"],
            preferred_element_type=jnp.float32,
        ) + lp["b_dt"]
    )
    with jax.named_scope("selscan"):
        y = selective_scan(
            xs, dt, -jnp.exp(lp["A_log"]), b, c, lp["D"],
            chunk=min(cfg.scan_chunk, t),
        )
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return gated.astype(u.dtype) @ lp["w_out"], y


def gmu_mixer(u, lp, memory):
    """The gated memory unit on the normed input ``u``: another
    layer's scan output gated element by element by this layer's own
    projection of its input."""
    from dlrover_tpu.accelerate.remat import SSM_IN, keep

    gate = keep(u @ lp["w_in"], SSM_IN)
    gated = memory.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return gated.astype(u.dtype) @ lp["w_out"]


def differential_attention(q, k, v, lp, attn_fn, cfg: Phi4FlashConfig,
                           window: Optional[int] = None):
    """q [B, T, H d]; k, v [B, T, Hkv d] -> [B, T, H d]: the pairs'
    two softmax maps on the pair's values side by side, their
    difference under the learned lambda, the norm a pair. ``lp`` holds
    the four lambda vectors, the norm's gain and ``lam0`` (a float32
    scalar: the layer's published index decides it, and a scanned
    run's layers differ in it). ``attn_fn`` has the layer's window
    bound; ``window`` is said for the event alone."""
    bsz, t, _ = q.shape
    d, pairs, kv_pairs = cfg.head_dim, cfg.n_head // 2, cfg.n_kv_head // 2
    obs.event(
        "attn.differential", pairs=pairs, kv_pairs=kv_pairs, head_dim=d,
        v_width=2 * d, window=window,
    )
    q = q.reshape(bsz, t, pairs, 2, d)
    k = k.reshape(bsz, t, kv_pairs, 2, d)
    # Built once a layer: a query pair reads key-value pair i // group.
    group = pairs // kv_pairs
    k1, k2, wide_v = (
        jnp.repeat(x, group, axis=2) for x in (
            k[:, :, :, 0], k[:, :, :, 1], v.reshape(bsz, t, kv_pairs, 2 * d),
        )
    )
    a1 = attn_fn(q[:, :, :, 0], k1, wide_v)
    a2 = attn_fn(q[:, :, :, 1], k2, wide_v)
    with jax.named_scope("attn_diff"):
        f32 = jnp.float32
        lam_0 = lp["lam0"].astype(f32)
        lam = (
            jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]).astype(f32))
            - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]).astype(f32))
            + lam_0
        )
        a = a1.astype(f32) - lam * a2.astype(f32)
        a = a * jax.lax.rsqrt(
            jnp.mean(jnp.square(a), axis=-1, keepdims=True) + cfg.ln_eps
        )
        a = a * lp["subln"] * (1.0 - lam_0)
        return a.astype(q.dtype).reshape(bsz, t, pairs * 2 * d)


def _layer(x, lp, attn_fn, *, cfg: Phi4FlashConfig, kind: str):
    """One layer of ``kind``. ``lp`` holds the layer's parameters and,
    beside them, what it reads of another layer (``memory``, ``kv``)
    and its ``lam0``. A layer that makes something for later layers
    returns (x, what it made)."""
    from dlrover_tpu.accelerate.remat import (
        ATTN_IN, LAYER_MEMORY, SHARED_KV, keep,
    )

    made = None
    h = gpt._layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.ln_eps)
    if kind in (MAMBA, MEMORY):
        with jax.named_scope("ssm"):
            mixed, y = mamba_mixer(h, lp, cfg)
            if kind == MEMORY:
                made = keep(y, LAYER_MEMORY)
    elif kind == GMU:
        with jax.named_scope("ssm"), jax.named_scope("gmu"):
            mixed = gmu_mixer(h, lp, lp["memory"])
    else:
        with jax.named_scope("attn"), jax.named_scope(kind):
            q_width = cfg.n_head * cfg.head_dim
            qkv = keep(h @ lp["wqkv"] + lp["bqkv"], ATTN_IN)
            if kind == CROSS:
                q, (k, v) = qkv, lp["kv"]
            else:
                q = qkv[..., :q_width]
                k, v = jnp.split(qkv[..., q_width:], 2, axis=-1)
            if kind == FULL:
                made = k, v = keep(k, SHARED_KV), keep(v, SHARED_KV)
            att = differential_attention(
                q, k, v, lp, attn_fn, cfg,
                window=cfg.sliding_window if kind == WINDOW else None,
            )
            mixed = att @ lp["wo"] + lp["bo"]
    x = x + mixed
    with jax.named_scope("mlp"):
        h = gpt._layer_norm(x, lp["ln2_g"], lp["ln2_b"], cfg.ln_eps)
        x = x + llama.swiglu(h, lp)
    return x if made is None else (x, made)


def default_attention_for(cfg: Phi4FlashConfig) -> Callable:
    """The chooser every family uses (flash on the TPU from 512 tokens
    up) with no window bound: the windowed layers bind theirs. A
    caller that binds an ``attn_fn`` of its own takes ``window`` as a
    keyword too."""
    return gpt.default_attention_for(
        dataclasses.replace(cfg, sliding_window=None)
    )


def _say_pattern(cfg: Phi4FlashConfig) -> None:
    indices = range(cfg.first_layer, cfg.first_layer + cfg.n_layer)
    readers = {
        MAKES[maker]: [i for i, k in zip(indices, cfg.kinds) if k == reader]
        for reader, maker in READS.items()
    }
    obs.event(
        "sambay.pattern", kinds=list(cfg.kinds), indices=list(indices),
        runs=[[name, count] for name, _, count, _ in cfg.runs],
        memory_from=cfg.index_of(MEMORY), memory_readers=readers["memory"],
        kv_from=cfg.index_of(FULL), kv_readers=readers["kv"],
    )
    if readers["memory"]:
        obs.event(
            "gmu.memory", from_layer=cfg.index_of(MEMORY),
            readers=readers["memory"],
        )


def backbone(
    params: Params,
    tokens: jax.Array,
    cfg: Phi4FlashConfig,
    attn_fn: Optional[Callable] = None,
) -> jax.Array:
    """[B, T] tokens -> [B, T, E] hidden after the final norm."""
    from dlrover_tpu.accelerate.remat import wire_block
    from dlrover_tpu.models import layers

    if attn_fn is None:
        attn_fn = default_attention_for(cfg)
    _say_pattern(cfg)
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
    block = {
        kind: wire_block(
            functools.partial(_layer, cfg=cfg, kind=kind), cfg.remat,
            functools.partial(attn_fn, window=cfg.sliding_window)
            if kind == WINDOW else attn_fn,
        )
        for kind in set(cfg.kinds)
    }
    shared = {}  # "memory", "kv": what later layers read

    def beside(lp, kind, index):
        """A layer's parameters with what else it takes: ``lam0`` by
        its published index, [units] like the leaves."""
        if kind not in ATTENTION:
            return lp
        return dict(lp, lam0=jnp.asarray(
            [lam0(index + 2 * i) for i in range(lp["subln"].shape[0])],
            jnp.float32,
        ))

    with jax.named_scope("layers"):
        for name, unit, _, index in cfg.runs:
            stacked = {
                kind: beside(params["runs"][name][kind], kind, index + i)
                for i, kind in enumerate(unit)
            }
            if unit[0] in MAKES:
                # A producer stands alone in its run, and what it makes
                # leaves the layer beside the residual stream.
                (kind,) = unit
                x, shared[MAKES[kind]] = block[kind](
                    x, jax.tree.map(lambda a: a[0], stacked[kind])
                )
                continue

            def one_unit(x, lp, unit=unit):
                # What a reader takes is a constant of the run's scan.
                for kind in unit:
                    read = MAKES.get(READS.get(kind))
                    x = block[kind](
                        x, dict(lp[kind], **{read: shared[read]})
                        if read else lp[kind],
                    )
                return x

            x = layers.run(one_unit, x, stacked)
    return gpt._layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.ln_eps)


def forward(params, tokens, cfg: Phi4FlashConfig, attn_fn=None):
    """[B, T, V] float32 logits."""
    x = backbone(params, tokens, cfg, attn_fn)
    with jax.named_scope("head"):
        return jnp.einsum(
            "bte,ve->btv", x, params["wte"],
            preferred_element_type=jnp.float32,
        )


def loss_fn(params, tokens, targets, cfg: Phi4FlashConfig,
            attn_fn=None) -> jax.Array:
    logp = jax.nn.log_softmax(forward(params, tokens, cfg, attn_fn), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def loss_fn_fused(params, tokens, targets, cfg: Phi4FlashConfig,
                  attn_fn=None, num_chunks: int = 8) -> jax.Array:
    from dlrover_tpu.ops.cross_entropy import fused_cross_entropy

    x = backbone(params, tokens, cfg, attn_fn)
    n = x.shape[0] * x.shape[1]
    with jax.named_scope("head"):
        return fused_cross_entropy(
            x.reshape(n, -1), params["wte"], targets.reshape(n), num_chunks,
        )
