"""Multi-head latent attention: the mixer that the families with a
compressed key-value latent share (models/kimi_linear.py, where
nothing is rotated; models/deepseek_v2.py, where the shared key part
and the queries' last columns are), and the helper both use to lay a
layer's leaves out as a subtree.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama


def nested(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``{"moe/shared/w_up": leaf}`` -> ``{"moe": {"shared": {"w_up":
    leaf}}}``: a layer's leaves by path, as the subtree they are."""
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def mla_mixer(u, lp, attn_fn, cfg, scale, rope=None):
    """The latent-attention mixer on the normed input ``u`` [B, T, E],
    without the residual: ``q = u w_q`` in heads of ``qk_nope +
    qk_rope`` columns; the latent ``[c | k_r] = u w_kva``; ``[k_n | v]
    = rms(c) w_kvb`` a head; a head's key is ``[k_n | k_r]``, ``k_r``
    one vector a token that the heads share; causal softmax attention
    at ``scale`` with values of ``v_head`` columns; ``w_o``.

    ``cfg`` is the family's configuration (``n_head``, ``kv_rank``,
    ``qk_nope``, ``qk_rope``, ``v_head``, ``rms_eps``). ``rope``, a
    ``(cos, sin)`` pair ``[T, qk_rope / 2]``, turns ``k_r`` (as one
    head, once, before the heads share it) and the queries' last
    ``qk_rope`` columns under the scope ``mla_rope``; with None
    nothing is rotated. The rotation is ``llama.apply_rope`` looked
    up at the call: the benchmark's controls swap that attribute."""
    from dlrover_tpu.accelerate.remat import ATTN_IN, MLA_LATENT, keep

    bsz, t, _ = u.shape
    heads, rank, d_n, d_r = cfg.n_head, cfg.kv_rank, cfg.qk_nope, cfg.qk_rope
    q = keep(u @ lp["wq"], ATTN_IN).reshape(bsz, t, heads, d_n + d_r)
    latent = keep(u @ lp["w_kva"], MLA_LATENT)
    c = llama._rms_norm(latent[..., :rank], lp["kv_norm"], cfg.rms_eps)
    kv = (c @ lp["w_kvb"]).reshape(bsz, t, heads, d_n + cfg.v_head)
    k_r = latent[..., None, rank:]
    if rope is not None:
        with jax.named_scope("mla_rope"):
            k_r = llama.apply_rope(k_r, *rope)
            q = jnp.concatenate(
                [q[..., :d_n], llama.apply_rope(q[..., d_n:], *rope)], axis=-1
            )
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r, (bsz, t, heads, d_r))], axis=-1
    )
    att = attn_fn(q, k, kv[..., d_n:], scale=scale)
    return att.reshape(bsz, t, heads * cfg.v_head) @ lp["w_o"]
