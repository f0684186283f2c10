"""All-to-all (Ulysses-style) sequence parallelism.

The second context-parallel family next to parallel/ring_attention.py
(ref: atorch's sequence-parallel integrations; DeepSpeed-Ulysses is
the public construction, PAPERS.md): instead of rotating K/V blocks
around a ring, one ``all_to_all`` swaps the sharded dimension —
sequence-sharded activations [B, T/s, H, D] become head-sharded
full-sequence activations [B, T, H/s, D], every device runs ordinary
(flash) attention over its head group, and the inverse all_to_all
restores sequence sharding.

Trade-offs vs the ring (why both exist):

* two all_to_alls move 3x and 1x the activation bytes once, instead
  of (s-1) K/V block hops — fewer, larger transfers that XLA overlaps
  poorly but ICI switches handle well;
* causal work is perfectly load-balanced (every device sees the full
  sequence), where the causal ring is inherently imbalanced by ring
  position;
* requires heads % seq_shards == 0 and holds full-T activations per
  device for the attention itself — the ring keeps O(T/s) memory and
  scales past head count.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def a2a_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = False,
    attn_fn=None,
) -> jax.Array:
    """Attention over ``axis_name``-sharded sequences via head/seq
    all-to-all. Per-device shapes [batch, seq_local, heads, head_dim];
    must run inside shard_map with ``axis_name`` unmapped. ``attn_fn``
    computes full-sequence attention on [B, T, H/s, D] (defaults to
    the models' plain causal attention; pass the flash kernel on TPU).
    """
    n = jax.lax.psum(1, axis_name)
    b, lt, h, d = q.shape
    h_kv = k.shape[2]
    if h % n != 0:
        raise ValueError(
            f"a2a sequence parallelism needs heads ({h}) divisible "
            f"by the '{axis_name}' axis size ({n}); use ring "
            "attention when sequence shards outnumber heads"
        )
    if h_kv != h and h % h_kv:
        raise ValueError(
            f"grouped-query attention needs q heads ({h}) divisible "
            f"by kv heads ({h_kv})"
        )
    if attn_fn is None:
        from dlrover_tpu.models.gpt import _default_attention

        attn_fn = functools.partial(_default_attention, causal=causal)

    # [B, T/s, H, D] -> [B, T, H/s, D]: split the head dim n ways,
    # exchange so each device concatenates every peer's sequence
    # block (axis-index order = global sequence order).
    def swap_to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    g = h // h_kv
    if g > 1 and h_kv % n:
        # Compact kv heads don't split n ways: broadcast BEFORE the
        # exchange (correct, no traffic saving).
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        g = 1
    qh = swap_to_heads(q)
    kh = swap_to_heads(k)
    vh = swap_to_heads(v)
    if g > 1:
        # Compact grouped-query K/V crossed the a2a at 1/g the bytes;
        # broadcast over the query groups only now, locally.
        kh = jnp.repeat(kh, g, axis=2)
        vh = jnp.repeat(vh, g, axis=2)
    out = attn_fn(qh, kh, vh)
    # [B, T, H/s, D] -> [B, T/s, H, D]
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    ).astype(q.dtype)


def make_a2a_attention(
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    impl: str = "auto",
    window: Optional[int] = None,
):
    """shard_map wrapper mirroring ring_attention.make_sharded_attention
    — drop-in for a model's ``attn_fn`` on a mesh with a ``seq`` axis.

    ``impl``: "flash" runs the Pallas kernel on the full-sequence head
    group, "xla" the einsum path, "auto" picks flash on TPU. Composes
    with tensor parallelism the same way the ring does (heads shard
    over ``tensor`` first; the a2a then needs heads_per_tensor_shard %
    seq_shards == 0).

    ``window`` (requires ``causal=True``): after the all_to_all every
    device holds the FULL sequence for its head group, so the band is
    just the inner kernel's ``window`` — the flash kernel skips
    band-dead kv blocks (O(T*window) per device), the plain path
    masks. Communication is unchanged (the a2a moves activations, not
    K/V blocks, so unlike the ring there is no band-dead traffic to
    skip).
    """
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown a2a attention impl {impl!r}")
    if window is not None and not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True"
        )
    use_flash = (
        impl == "flash"
        or (impl == "auto" and jax.default_backend() == "tpu")
    )
    if mesh.shape.get(axis_name, 1) == 1:
        from dlrover_tpu.parallel.ring_attention import (
            make_sharded_attention,
        )

        # No sequence sharding: identical to the ring's degenerate
        # case — reuse its plain/flash single-device paths.
        return make_sharded_attention(
            mesh, causal=causal, axis_name=axis_name,
            batch_axes=batch_axes, head_axis=head_axis, impl=impl,
            window=window,
        )

    if use_flash:
        from dlrover_tpu.ops.flash_attention import flash_attention

        inner = functools.partial(
            flash_attention, causal=causal, window=window
        )
    elif window is not None:
        from dlrover_tpu.models.gpt import _default_attention

        inner = functools.partial(
            _default_attention, causal=causal, window=window
        )
    else:
        inner = None  # a2a_attention's default plain path

    spec = P(batch_axes, axis_name, head_axis, None)
    fn = functools.partial(
        a2a_attention,
        axis_name=axis_name,
        causal=causal,
        attn_fn=inner,
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
        # Same tensor-axis constraint as the ring wrapper: compact
        # K/V must split its head dim over `tensor`, else
        # pre-broadcast (correct, no traffic saving). _gqa_expander
        # also validates the head ratio on the GLOBAL counts.
        if k.shape[2] != q.shape[2] and k.shape[2] % tp:
            from dlrover_tpu.parallel.ring_attention import (
                _gqa_expander,
            )

            expand = _gqa_expander(q.shape[2], k.shape[2])
            k, v = expand(k), expand(v)
        return sharded(q, k, v)

    # Compact grouped-query K/V accepted: it crosses the a2a at
    # 1/q_per_kv the bytes when kv heads split over the axis, and is
    # broadcast locally otherwise (a2a_attention).
    attn.supports_gqa = True
    return attn
