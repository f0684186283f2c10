"""Ring attention: sequence/context parallelism over a mesh axis.

Supersedes the reference's blockwise distributed attention
(atorch/modules/distributed_transformer/distributed_attention.py:21-186:
allgathered micro-Q + global-softmax allreduce + reduce-scattered
context, overlapped on a second CUDA stream). The TPU-idiomatic design
instead keeps Q resident and rotates K/V blocks around the ``seq`` mesh
axis with ``lax.ppermute`` (ICI neighbor hops), merging each block with
a numerically-stable *online softmax* — communication volume is O(seq)
per device independent of world size, and XLA overlaps the permute with
the block matmuls.

Use :func:`ring_attention` inside ``shard_map`` (or via
:func:`make_sharded_attention` which wraps it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from dlrover_tpu.parallel.mesh import under_mesh


def _block_attn(q, k, v, scale, mask):
    """Scores + weighted values for one K/V block.

    q: [b, lq, h, d]; k/v: [b, lk, h, d]; mask broadcastable to
    [b, h, lq, lk] (True = keep). Returns (scores_max, exp_scores_sum,
    out_unnormalized) for online-softmax merging, all float32.
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [b,h,q]
    # Guard fully-masked rows (causal ring blocks entirely in the
    # future): exp(-inf - -inf) would be NaN.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)  # [b,h,q]
    o = jnp.einsum(
        "bhqk,bkhd->bqhd",
        p.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return m_safe, l, o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention where q/k/v are sharded over ``axis_name`` on the
    sequence dimension. Shapes (per-device): [batch, seq_local, heads,
    head_dim]. Must run inside shard_map with ``axis_name`` unmapped.

    ``window`` (requires ``causal=True``) applies the sliding-window
    band by masking only — every ring step still runs, so this XLA
    fallback is correct but O(T^2/shards); the flash path
    (:func:`ring_attention_flash`) statically skips band-dead ring
    steps and is the one to use for long windowed sequences.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (d**0.5)
    if window is not None and not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True"
        )
    expand_kv = _gqa_expander(h, k.shape[2])

    q_pos = my_idx * lq + jnp.arange(lq)  # global query positions

    def step(carry, t):
        k_blk, v_blk, m_acc, l_acc, o_acc = carry
        src_idx = (my_idx - t) % n  # where this K/V block originated
        if causal:
            kv_pos = src_idx * lk + jnp.arange(lk)
            mask = q_pos[None, None, :, None] >= kv_pos[None, None, None, :]
            if window is not None:
                mask &= (
                    q_pos[None, None, :, None] - kv_pos[None, None, None, :]
                ) < window
        else:
            mask = None
        m_blk, l_blk, o_blk = _block_attn(
            q, expand_kv(k_blk), expand_kv(v_blk), scale, mask
        )
        # Online-softmax merge of block stats into the accumulator.
        m_new = jnp.maximum(m_acc, m_blk)
        corr_acc = jnp.exp(m_acc - m_new)
        corr_blk = jnp.exp(m_blk - m_new)
        l_new = l_acc * corr_acc + l_blk * corr_blk
        o_new = (
            o_acc * corr_acc.transpose(0, 2, 1)[..., None]
            + o_blk * corr_blk.transpose(0, 2, 1)[..., None]
        )
        # Rotate K/V to the next ring position (ICI neighbor hop).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m_new, l_new, o_new), None

    m0 = jnp.full((b, h, lq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, lq), dtype=jnp.float32)
    o0 = jnp.zeros((b, lq, h, d), dtype=jnp.float32)
    (_, _, m_f, l_f, o_f), _ = jax.lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n)
    )
    l_f = jnp.maximum(l_f, 1e-20)  # fully-masked rows divide by ~0
    out = o_f / l_f.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


_NEG = -1e30  # "-inf" that keeps exp/logaddexp NaN-free


def _gqa_expander(h_q: int, h_kv: int):
    """Grouped-query support for the ring families: K/V ride the ring
    COMPACT (h_kv heads — 1/q_per_kv the ppermute bytes of the
    expanded layout models used to pre-broadcast) and are broadcast
    over their query group only at the per-block kernel call, where
    XLA folds the repeat into the kernel's input copy. Returns the
    per-block expansion fn."""
    if h_kv == h_q:
        return lambda x: x
    if h_q % h_kv:
        raise ValueError(
            f"grouped-query attention needs q heads ({h_q}) divisible "
            f"by kv heads ({h_kv})"
        )
    g = h_q // h_kv
    return lambda x: jnp.repeat(x, g, axis=2)


def ring_attention_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel as the per-block
    engine: each ring step runs flash attention against the resident
    K/V block (O(lq) memory — the [lq, lk] score tile never reaches
    HBM, unlike :func:`ring_attention`'s XLA path) and merges the
    normalized block output via its logsumexp. This is the Ring
    Attention construction (blockwise-parallel ring, PAPERS.md) with
    the inner block computed by ops/flash_attention.py, including its
    lse-cotangent backward.

    Causal runs dispatch one of three per-block programs: K/V from an
    earlier ring slot attends densely, the resident slot runs the
    causal kernel, later slots are skipped (zero compute beyond the
    branch). Per-device work is therefore imbalanced by ring position
    — inherent to causal ring attention.

    ``window`` (requires ``causal=True``) runs Mistral-style
    sliding-window attention with a STATICALLY truncated ring: a K/V
    block at ring distance t spans key offsets [t*lq - lq + 1,
    t*lq + lq - 1] from its queries, so once (t-1)*lq + 1 > window-1
    the block is outside the band for EVERY device and the schedule
    stops — both compute and ppermute hops truncate to
    t_stop = min(n-1, (window + lq - 2) // lq), giving
    O(T * window / shards) work and O(window) communication per
    device instead of O(T^2/shards) / O(T). Live non-resident steps
    run the rectangular banded kernel (flash_attention_rect with
    q_offset = t*lq) at exact cost.
    """
    from dlrover_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_rect,
    )

    b, lq, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (d**0.5)
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= n * lq:
            window = None  # band covers the global sequence
    expand_kv = _gqa_expander(h, k.shape[2])

    def flash_blk(q_, k_, v_, causal_):
        o, lse = flash_attention(
            q_, expand_kv(k_), expand_kv(v_), causal=causal_,
            scale=scale, interpret=interpret, return_lse=True,
        )
        return o.astype(jnp.float32), lse

    if window is not None:
        return _ring_flash_windowed(
            q, k, v, axis_name, int(window), scale, interpret,
            flash_attention, flash_attention_rect,
        )

    def step(carry, t):
        k_blk, v_blk, lse_acc, o_acc = carry
        src = (my_idx - t) % n
        if causal:
            idx = jnp.where(src < my_idx, 0, jnp.where(src == my_idx, 1, 2))
            o_blk, lse_blk = jax.lax.switch(
                idx,
                [
                    lambda q_, k_, v_: flash_blk(q_, k_, v_, False),
                    lambda q_, k_, v_: flash_blk(q_, k_, v_, True),
                    lambda q_, k_, v_: (
                        jnp.zeros((b, lq, h, d), jnp.float32),
                        jnp.full((b, h, lq), _NEG, jnp.float32),
                    ),
                ],
                q, k_blk, v_blk,
            )
        else:
            o_blk, lse_blk = flash_blk(q, k_blk, v_blk, False)
        lse_new = jnp.logaddexp(lse_acc, lse_blk)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_blk = jnp.exp(lse_blk - lse_new)
        o_new = (
            o_acc * w_acc.transpose(0, 2, 1)[..., None]
            + o_blk * w_blk.transpose(0, 2, 1)[..., None]
        )
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, lse_new, o_new), None

    lse0 = jnp.full((b, h, lq), _NEG, jnp.float32)
    o0 = jnp.zeros((b, lq, h, d), jnp.float32)
    (_, _, _, o_f), _ = jax.lax.scan(
        step, (k, v, lse0, o0), jnp.arange(n)
    )
    return o_f.astype(q.dtype)


def _ring_flash_windowed(
    q, k, v, axis_name, window, scale, interpret,
    flash_attention, flash_attention_rect,
):
    """Sliding-window causal ring (see ring_attention_flash docstring).

    The loop over ring distance t is a STATIC Python loop (n is the
    static mesh-axis size), so the band-dead tail of the ring —
    distances with (t-1)*lq + 1 > window-1 — is never traced at all:
    no flash calls, no ppermute hops. Per live step:

    * t = 0: the resident block, square causal+window kernel;
    * t >= 1: the block sits at static key offset t*lq below the
      queries — devices with my_idx >= t run the banded rectangular
      kernel (q_offset = t*lq makes the causal compare inactive and
      the window compare exact); devices with my_idx < t would
      receive a wrapped FUTURE block, and contribute zeros via
      lax.cond. (Per the SPMD cond caveat on ring_prefix_lm_attention,
      XLA may compute both branches and select — correctness is
      unaffected; the static truncation above is where the asymptotic
      saving lives and it does not depend on cond lowering.)
    """
    b, lq, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_stop = min(n - 1, (window + lq - 2) // lq)
    expand_kv = _gqa_expander(h, k.shape[2])

    zeros = (
        jnp.zeros((b, lq, h, d), jnp.float32),
        jnp.full((b, h, lq), _NEG, jnp.float32),
    )

    def resident(q_, k_, v_):
        o, lse = flash_attention(
            q_, expand_kv(k_), expand_kv(v_), causal=True,
            window=window, scale=scale, interpret=interpret,
            return_lse=True,
        )
        return o.astype(jnp.float32), lse

    def banded(q_, k_, v_, off):
        o, lse = flash_attention_rect(
            q_, expand_kv(k_), expand_kv(v_), causal=True,
            q_offset=off, window=window, scale=scale,
            interpret=interpret, return_lse=True,
        )
        return o.astype(jnp.float32), lse

    lse_acc = jnp.full((b, h, lq), _NEG, jnp.float32)
    o_acc = jnp.zeros((b, lq, h, d), jnp.float32)
    k_blk, v_blk = k, v
    for t in range(t_stop + 1):
        if t == 0:
            o_blk, lse_blk = resident(q, k_blk, v_blk)
        else:
            o_blk, lse_blk = jax.lax.cond(
                my_idx >= t,
                lambda q_, k_, v_, t=t: banded(q_, k_, v_, t * lq),
                lambda *_: zeros,
                q, k_blk, v_blk,
            )
        lse_new = jnp.logaddexp(lse_acc, lse_blk)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_blk = jnp.exp(lse_blk - lse_new)
        o_acc = (
            o_acc * w_acc.transpose(0, 2, 1)[..., None]
            + o_blk * w_blk.transpose(0, 2, 1)[..., None]
        )
        lse_acc = lse_new
        if t < t_stop:
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return o_acc.astype(q.dtype)


def make_sharded_attention(
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    impl: str = "auto",
    window: Optional[int] = None,
):
    """Wrap ring attention in shard_map for the given mesh.

    Sequence parallelism composes with tensor parallelism: heads are
    sharded over ``tensor`` while sequence blocks ride the ``seq`` ring.

    ``impl``: "flash" uses the Pallas per-block kernel
    (ring_attention_flash), "xla" the einsum path (ring_attention),
    "auto" picks flash on TPU.

    ``window`` (requires ``causal=True``) applies Mistral-style
    sliding-window attention on every path: the flash ring statically
    skips band-dead ring hops (O(T*window/shards) work), the XLA ring
    masks, and the single-shard fallbacks pass it to the kernel.
    """
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    if window is not None and not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True"
        )
    use_flash = (
        impl == "flash"
        or (impl == "auto" and jax.default_backend() == "tpu")
    )
    spec = P(batch_axes, axis_name, head_axis, None)

    if mesh.shape.get(axis_name, 1) == 1:
        if use_flash:
            from dlrover_tpu.ops.flash_attention import flash_attention

            # XLA cannot partition the Mosaic call; traced under the
            # mesh the kernel splits itself over batch and heads.
            return _expand_kv_wrapper(
                under_mesh(
                    functools.partial(
                        flash_attention, causal=causal, window=window
                    ),
                    mesh,
                )
            )

        # No sequence sharding: plain (still jit-fused) attention —
        # the one definition of the dense causal/window mask lives in
        # gpt._default_attention (ulysses.py's degenerate path ends
        # here too).
        from dlrover_tpu.models.gpt import _default_attention

        return _expand_kv_wrapper(
            functools.partial(
                _default_attention, causal=causal, window=window
            )
        )

    fn = functools.partial(
        ring_attention_flash if use_flash else ring_attention,
        axis_name=axis_name,
        causal=causal,
        window=window,
    )
    sharded = shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    tp = mesh.shape.get(head_axis, 1) if head_axis is not None else 1

    def attn(q, k, v):
        # Compact K/V needs its head dim to split over the tensor
        # axis; when it can't (h_kv < tensor shards), pre-broadcast —
        # correct, just without the traffic saving.
        if k.shape[2] != q.shape[2] and k.shape[2] % tp:
            expand = _gqa_expander(q.shape[2], k.shape[2])
            k, v = expand(k), expand(v)
        return sharded(q, k, v)

    # Models may pass COMPACT grouped-query K/V (h_kv < h heads): the
    # ring rotates the small tensors and broadcasts per block.
    attn.supports_gqa = True
    return attn


def _expand_kv_wrapper(fn):
    """Equal-heads kernels behind a constructor that advertises
    grouped-query support: broadcast compact K/V over the query
    groups right before the call (XLA folds the repeat into the
    kernel's input transpose/copy)."""

    def attn(q, k, v, **kw):
        expand = _gqa_expander(q.shape[2], k.shape[2])
        return fn(q, expand(k), expand(v), **kw)

    attn.supports_gqa = True
    return attn


def ring_prefix_lm_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    prefix_len: int,
    axis_name: str = "seq",
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    attn_blocks: Optional[tuple] = None,
) -> jax.Array:
    """GLM prefix-LM attention with the sequence sharded over a ring.

    ONE fused ring scan with two online-softmax accumulators, keeping
    the SPMD program uniform across devices (per-device static row
    splits would break shard_map):

    * the CAUSAL accumulator collects each block under the causal
      ring schedule (earlier slot: dense; resident slot: causal
      kernel; later: skip) — the exact result for suffix rows, whose
      prefix keys are a subset of their causal keys;
    * the PREFIX accumulator collects the same blocks under the
      prefix-bidirectional schedule: blocks before the boundary
      attend densely, the ONE block containing the boundary (index
      ``prefix_len // block`` — static) contributes through a
      static-shape rectangular flash call over its first
      ``prefix_len % block`` keys, later blocks are skipped;
    * rows at global position < prefix_len take the prefix result,
      the rest the causal one.

    K/V rotate the ring ONCE; a block needed densely by both
    accumulators is computed once and merged twice. Worst-case cost
    is under 2x a plain causal ring step — the price of
    sequence-sharding a mask the collectives can't express directly;
    single-shard GLM uses the exact-cost composition in
    ops/prefix_lm.py.

    Cost caveat (unverified on hardware): the ``lax.cond``/
    ``lax.switch`` predicates here depend on the traced
    ``axis_index``, and under SPMD partitioning XLA may lower such
    conditionals to compute-both-branches + select rather than a real
    branch. If it does, the skip/dense gating saves nothing and a
    worst-case step costs up to dense + causal + rect per slot (~3x a
    causal ring step) in FLOPs — still correct, and still O(T^2 /
    shards) memory, but the FLOP saving advertised above should be
    confirmed with a per-op profile on a real chip before relying on
    it (a ``jax.profiler`` trace). A masking-based schedule (zeroing
    contributions instead of branching) would make the cost explicit
    and uniform if profiling shows both branches execute.

    ``prefix_len`` is the GLOBAL prefix length (static), validated
    against the global sequence n * block.
    """
    from dlrover_tpu.ops.flash_attention import (
        blocks_kwargs,
        flash_attention,
        flash_attention_rect,
    )

    b, lq, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (d**0.5)
    expand_kv = _gqa_expander(h, k.shape[2])
    p = int(prefix_len)
    if not 0 <= p <= n * lq:
        raise ValueError(
            f"prefix_len={p} outside [0, {n * lq}] (global seq = "
            f"{n} ring blocks x {lq})"
        )
    bkw = blocks_kwargs(attn_blocks)
    if p == 0:
        return ring_attention_flash(
            q, k, v, axis_name, causal=True, scale=scale,
            interpret=interpret,
        )

    b_p = p // lq   # the ring block containing the boundary (static)
    rem = p - b_p * lq  # prefix keys inside that block (static)

    zeros = (
        jnp.zeros((b, lq, h, d), jnp.float32),
        jnp.full((b, h, lq), _NEG, jnp.float32),
    )

    def dense_blk(q_, k_, v_):
        o, lse = flash_attention(
            q_, expand_kv(k_), expand_kv(v_), causal=False,
            scale=scale, interpret=interpret, return_lse=True, **bkw,
        )
        return o.astype(jnp.float32), lse

    def causal_blk(q_, k_, v_):
        o, lse = flash_attention(
            q_, expand_kv(k_), expand_kv(v_), causal=True,
            scale=scale, interpret=interpret, return_lse=True, **bkw,
        )
        return o.astype(jnp.float32), lse

    def rect_blk(q_, k_, v_):
        o, lse = flash_attention_rect(
            q_, expand_kv(k_[:, :rem]), expand_kv(v_[:, :rem]),
            causal=False, q_offset=0, scale=scale,
            interpret=interpret, return_lse=True,
        )
        return o.astype(jnp.float32), lse

    def merge(acc, blk):
        lse_acc, o_acc = acc
        o_blk, lse_blk = blk
        lse_new = jnp.logaddexp(lse_acc, lse_blk)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_blk = jnp.exp(lse_blk - lse_new)
        o_new = (
            o_acc * w_acc.transpose(0, 2, 1)[..., None]
            + o_blk * w_blk.transpose(0, 2, 1)[..., None]
        )
        return lse_new, o_new

    def step(carry, t):
        k_blk, v_blk, acc_c, acc_p = carry
        src = (my_idx - t) % n
        # The dense block value is shared: computed once when EITHER
        # schedule needs it (causal: src < my_idx; prefix: src < b_p).
        need_dense = jnp.logical_or(src < my_idx, src < b_p)
        dense = jax.lax.cond(
            need_dense, dense_blk, lambda *_: zeros, q, k_blk, v_blk
        )

        # Causal accumulator: dense for earlier slots, the causal
        # kernel on the resident slot, skip for later slots.
        c_idx = jnp.where(
            src < my_idx, 0, jnp.where(src == my_idx, 1, 2)
        )
        blk_c = jax.lax.switch(
            c_idx,
            [lambda: dense, lambda: causal_blk(q, k_blk, v_blk),
             lambda: zeros],
        )
        acc_c = merge(acc_c, blk_c)

        # Prefix accumulator: dense before the boundary block, the
        # rectangular slice on it (when it has prefix keys), skip
        # after.
        if rem > 0:
            p_idx = jnp.where(
                src < b_p, 0, jnp.where(src == b_p, 1, 2)
            )
            blk_p = jax.lax.switch(
                p_idx,
                [lambda: dense, lambda: rect_blk(q, k_blk, v_blk),
                 lambda: zeros],
            )
        else:
            p_idx = jnp.where(src < b_p, 0, 1)
            blk_p = jax.lax.switch(
                p_idx, [lambda: dense, lambda: zeros]
            )
        acc_p = merge(acc_p, blk_p)

        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, acc_c, acc_p), None

    acc0 = (
        jnp.full((b, h, lq), _NEG, jnp.float32),
        jnp.zeros((b, lq, h, d), jnp.float32),
    )
    (_, _, (_, o_causal), (_, o_prefix)), _ = jax.lax.scan(
        step, (k, v, acc0, acc0), jnp.arange(n)
    )

    pos = my_idx * lq + jnp.arange(lq)  # global row positions
    take_prefix = (pos < p)[None, :, None, None]
    return jnp.where(take_prefix, o_prefix, o_causal).astype(q.dtype)


def make_sharded_prefix_attention(
    mesh: Mesh,
    prefix_len: int,
    axis_name: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    attn_blocks: Optional[tuple] = None,
):
    """Prefix-LM attention for a mesh — the GLM analogue of
    :func:`make_sharded_attention`. With ``seq`` sharding it runs the
    fused two-accumulator ring (:func:`ring_prefix_lm_attention`);
    without, the exact-cost single-shard composition
    (ops/prefix_lm.py). ``attn_blocks`` threads the tuned flash
    tiles through either path (model configs carry it)."""
    if mesh.shape.get(axis_name, 1) == 1:
        from dlrover_tpu.ops.prefix_lm import prefix_lm_attention

        return functools.partial(
            prefix_lm_attention, prefix_len=prefix_len,
            attn_blocks=attn_blocks,
        )
    spec = P(batch_axes, axis_name, head_axis, None)
    fn = functools.partial(
        ring_prefix_lm_attention,
        prefix_len=prefix_len,
        axis_name=axis_name,
        attn_blocks=attn_blocks,
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
