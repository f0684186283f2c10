"""Autoregressive decoding with a KV cache (GPT + Llama).

Counterpart of the reference's sampling paths — nanoGPT's
``model.generate`` loop in the example the framework demos train
(/root/reference/examples/pytorch/nanogpt/train.py builds the same
GPT this repo's models/gpt.py implements) and the HF ``generate`` its
Llama examples inherit — built the XLA way:

* static shapes end to end: the cache is a preallocated
  [layers, batch, max_len, heads, head_dim] pytree, positions write
  via ``lax.dynamic_update_slice``; one compile regardless of prompt
  or output length;
* the whole decode loop is a single ``lax.scan`` (no per-token Python
  dispatch), layers run under the same stacked-params scan as
  training;
* sampling: greedy, temperature, and top-k via ``jax.random``.

The per-token block math intentionally reuses each model's weights
layout but re-derives the single-position forward (rope at one
position, attention against the cache) — training forwards stay
scan-over-sequence and never pay cache plumbing.
"""

from __future__ import annotations


import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import gpt as gpt_mod
from dlrover_tpu.models import llama as llama_mod


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, T_max, H_kv, D]
    v: jax.Array


def _cache_for(cfg, batch: int, max_len: int, n_kv: int) -> KVCache:
    shape = (cfg.n_layer, batch, max_len, n_kv, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype), v=jnp.zeros(shape, cfg.dtype)
    )


def _cached_attention(q, k_cache, v_cache, pos, window=None):
    """q [B,1,H,D] against cache [B,T,H_kv,D]; positions > pos
    masked. H may be a q_per_kv multiple of H_kv (grouped-query):
    query heads fold into a group dim and attend the UN-expanded
    cache — no repeated K/V copies in the decode hot path.
    ``window`` applies the Mistral sliding band — the decode step
    sees keys (pos-window, pos], matching the training mask."""
    b, t, hkv, d = k_cache.shape
    h = q.shape[2]
    g = h // hkv
    qg = q.reshape(b, 1, hkv, g, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) / np.sqrt(d)
    idx = jnp.arange(t)[None, None, None, None, :]
    mask = idx <= pos
    if window is not None:
        mask &= (pos - idx) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache)
    return o.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# Per-model single-token steps
# ---------------------------------------------------------------------------


def gpt_decode_step(params, cache: KVCache, token, pos, cfg):
    """One token through GPT with cache. token [B] int32, pos scalar.
    Returns (logits [B, vocab] f32, new cache)."""
    B = token.shape[0]
    H, D, E = cfg.n_head, cfg.head_dim, cfg.n_embd
    wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], pos, 1, 0)
    x = params["wte"][token][:, None, :] + wpe[None]
    x = x.astype(cfg.dtype)  # [B,1,E]

    def body(x, layer):
        lp, k_c, v_c = layer
        h = gpt_mod._layer_norm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = h @ lp["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, 1, H, D)
        k_c = jax.lax.dynamic_update_slice(
            k_c, k.reshape(B, 1, H, D), (0, pos, 0, 0)
        )
        v_c = jax.lax.dynamic_update_slice(
            v_c, v.reshape(B, 1, H, D), (0, pos, 0, 0)
        )
        att = _cached_attention(q, k_c, v_c, pos).reshape(B, 1, E)
        x = x + att @ lp["wo"]
        h = gpt_mod._layer_norm(x, lp["ln2_g"], lp["ln2_b"])
        h = jax.nn.gelu(h @ lp["wi"] + lp["bi"])
        x = x + h @ lp["wo2"] + lp["bo2"]
        return x, (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = gpt_mod._layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum(
        "boe,ve->bov", x, params["wte"],
        preferred_element_type=jnp.float32,
    )[:, 0]
    return logits, KVCache(k=k_new, v=v_new)


def _llama_mlp(x, h, lp, cfg):
    """Decode-path wrapper over the training block's MLP tail
    (llama.mlp_tail — single definition); the aux loss is irrelevant
    at inference and dropped."""
    y, _ = llama_mod.mlp_tail(x, h, lp, cfg)
    return y


def gpt_prefill(params, cache: KVCache, tokens, cfg):
    """Batched prompt pass: one forward over [B, T0] fills cache
    positions 0..T0 and returns the last position's logits — the
    time-to-first-token path (vs T0 sequential decode steps)."""
    B, T0 = tokens.shape
    H, D, E = cfg.n_head, cfg.head_dim, cfg.n_embd
    x = params["wte"][tokens] + params["wpe"][:T0][None]
    x = x.astype(cfg.dtype)

    def body(x, layer):
        lp, k_c, v_c = layer
        h = gpt_mod._layer_norm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = h @ lp["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T0, H, D)
        k = k.reshape(B, T0, H, D)
        v = v.reshape(B, T0, H, D)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, 0, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, 0, 0, 0))
        att = gpt_mod._default_attention(
            q, k, v, causal=True
        ).reshape(B, T0, E)
        x = x + att @ lp["wo"]
        h = gpt_mod._layer_norm(x, lp["ln2_g"], lp["ln2_b"])
        h = jax.nn.gelu(h @ lp["wi"] + lp["bi"])
        x = x + h @ lp["wo2"] + lp["bo2"]
        return x, (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = gpt_mod._layer_norm(
        x[:, -1:], params["lnf_g"], params["lnf_b"]
    )
    logits = jnp.einsum(
        "boe,ve->bov", x, params["wte"],
        preferred_element_type=jnp.float32,
    )[:, 0]
    return logits, KVCache(k=k_new, v=v_new)


def _llama_qkv(h, lp, cfg, B, T):
    """q/k/v projections incl. the optional GLM-style bias and
    OLMoE-style query/key norm, reshaped to [B, T, heads, D]."""
    q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
    if getattr(cfg, "qkv_bias", False):
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if getattr(cfg, "qk_norm", False):
        q = llama_mod._rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = llama_mod._rms_norm(k, lp["k_norm"], cfg.rms_eps)
    D = cfg.head_dim
    return (
        q.reshape(B, T, cfg.n_head, D),
        k.reshape(B, T, cfg.n_kv_head, D),
        v.reshape(B, T, cfg.n_kv_head, D),
    )


def llama_prefill(params, cache: KVCache, tokens, cfg, rope=None,
                  causal=True):
    """``causal=False`` runs the prompt bidirectionally — GLM
    prefix-LM generation (models/glm.py): the prompt is the prefix,
    so its k/v (at EVERY layer — deeper layers' k/v depend on the
    mask through the hiddens) must be contextualized with the full
    bidirectional mask before causal decode steps extend it."""
    B, T0 = tokens.shape
    H, Hkv, D, E = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.n_embd
    cos_t, sin_t = rope if rope is not None else llama_mod.rope_table(
        cfg, cfg.block_size
    )
    cos, sin = cos_t[:T0], sin_t[:T0]
    x = params["wte"][tokens].astype(cfg.dtype)

    def body(x, layer):
        lp, k_c, v_c = layer
        h = llama_mod._rms_norm(x, lp["rms1"], cfg.rms_eps)
        q, k, v = _llama_qkv(h, lp, cfg, B, T0)
        q = llama_mod.apply_rope(q, cos, sin)
        k = llama_mod.apply_rope(k, cos, sin)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, 0, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, 0, 0, 0))
        if Hkv != H:
            k = jnp.repeat(k, cfg.q_per_kv, axis=2)
            v = jnp.repeat(v, cfg.q_per_kv, axis=2)
        att = gpt_mod._default_attention(
            q, k, v, causal=causal,
            window=getattr(cfg, "sliding_window", None),
        ).reshape(B, T0, E)
        x = x + att @ lp["wo"]
        h = llama_mod._rms_norm(x, lp["rms2"], cfg.rms_eps)
        return _llama_mlp(x, h, lp, cfg), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = llama_mod._rms_norm(x[:, -1:], params["rmsf"], cfg.rms_eps)
    logits = llama_mod.head_logits(params, x)[:, 0]
    return logits, KVCache(k=k_new, v=v_new)


def llama_decode_step(params, cache: KVCache, token, pos, cfg,
                      rope=None):
    B = token.shape[0]
    H, Hkv, D, E = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.n_embd
    x = params["wte"][token][:, None, :].astype(cfg.dtype)  # [B,1,E]
    cos_t, sin_t = rope if rope is not None else llama_mod.rope_table(
        cfg, cfg.block_size
    )
    cos = jax.lax.dynamic_slice_in_dim(cos_t, pos, 1, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_t, pos, 1, 0)

    def body(x, layer):
        lp, k_c, v_c = layer
        h = llama_mod._rms_norm(x, lp["rms1"], cfg.rms_eps)
        q, k, v = _llama_qkv(h, lp, cfg, B, 1)
        q = llama_mod.apply_rope(q, cos, sin)
        k = llama_mod.apply_rope(k, cos, sin)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, pos, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, pos, 0, 0))
        # GQA handled inside _cached_attention (grouped einsum) —
        # never materialize a q_per_kv-expanded cache copy per step.
        att = _cached_attention(
            q, k_c, v_c, pos,
            window=getattr(cfg, "sliding_window", None),
        ).reshape(B, 1, E)
        x = x + att @ lp["wo"]
        h = llama_mod._rms_norm(x, lp["rms2"], cfg.rms_eps)
        return _llama_mlp(x, h, lp, cfg), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = llama_mod._rms_norm(x, params["rmsf"], cfg.rms_eps)
    logits = llama_mod.head_logits(params, x)[:, 0]
    return logits, KVCache(k=k_new, v=v_new)


# ---------------------------------------------------------------------------
# Serving path: ragged (per-lane-position) decode + lane-granular
# prefill over one shared multi-lane cache. This is the model half of
# the continuous-batching scheduler (dlrover_tpu/serving/scheduler.py):
# every batch lane hosts a DIFFERENT sequence at a DIFFERENT position,
# so positions are vectors, cache writes are per-lane scatters, and
# prompt prefill lands chunk-by-chunk into one lane without touching
# the others. Llama-family configs only (the serving fleet's family);
# GPT's absolute position table would slot in the same way.
# ---------------------------------------------------------------------------


def _apply_rope_gathered(x, cos_t, sin_t, pos):
    """Rotate x [B, 1, H, D] with each lane at its OWN position:
    ``pos`` [B] int32 gathers per-lane rows from the precomputed
    tables. Same split-halves convention as llama.apply_rope."""
    cos = cos_t[pos][:, None, None, :]  # [B, 1, 1, d2]
    sin = sin_t[pos][:, None, None, :]
    d2 = cos.shape[-1]
    x1, x2 = x[..., :d2], x[..., d2:2 * d2]
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    parts = [x1 * c - x2 * s, x2 * c + x1 * s]
    if 2 * d2 < x.shape[-1]:
        parts.append(x[..., 2 * d2:])
    return jnp.concatenate(parts, axis=-1)


def _cached_attention_ragged(q, k_cache, v_cache, pos, window=None):
    """q [B,1,H,D] against cache [B,T,H_kv,D] with PER-LANE positions
    ``pos`` [B]: lane b sees keys idx <= pos[b] (band-clamped under a
    sliding window). Grouped-query handled exactly like
    :func:`_cached_attention` — no expanded cache copies."""
    b, t, hkv, d = k_cache.shape
    h = q.shape[2]
    g = h // hkv
    qg = q.reshape(b, 1, hkv, g, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) / np.sqrt(d)
    idx = jnp.arange(t)[None, None, None, None, :]
    p = pos[:, None, None, None, None]
    mask = idx <= p
    if window is not None:
        mask &= (p - idx) < window
    s = jnp.where(mask, s, -1e30)
    att = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", att, v_cache)
    return o.reshape(b, 1, h, d)


def llama_decode_step_ragged(params, cache: KVCache, token, pos, cfg,
                             rope=None, active=None):
    """One continuous-batching decode step: token [B] int32, pos [B]
    int32 — every lane advances at its own position. Cache updates are
    one vectorized scatter per layer (``.at[lane, pos[lane]].set``);
    rope rows gather per lane; attention masks per lane. Returns
    (logits [B, vocab] f32, new cache).

    ``active`` [B] bool masks the CACHE WRITES: an inactive lane (no
    sequence, or one still mid-prefill) must not have its own cache
    touched — without the mask, every decode step would scatter a
    garbage key at ``pos[b]`` of lane b (the scheduler passes 0 for
    idle lanes), clobbering position 0 of a lane whose chunked
    prefill is still in flight. Inactive lanes still COMPUTE garbage
    logits the scheduler never reads — the price of one static-shape
    program for any active set; only their writes are suppressed.
    ``active=None`` means all lanes write (the all-decoding batch)."""
    B = token.shape[0]
    x = params["wte"][token][:, None, :].astype(cfg.dtype)  # [B,1,E]
    cos_t, sin_t = rope if rope is not None else llama_mod.rope_table(
        cfg, cfg.block_size
    )
    lanes = jnp.arange(B)
    write_mask = (
        None if active is None else active[:, None, None]
    )

    def body(x, layer):
        lp, k_c, v_c = layer
        h = llama_mod._rms_norm(x, lp["rms1"], cfg.rms_eps)
        q, k, v = _llama_qkv(h, lp, cfg, B, 1)
        q = _apply_rope_gathered(q, cos_t, sin_t, pos)
        k = _apply_rope_gathered(k, cos_t, sin_t, pos)
        k_w, v_w = k[:, 0], v[:, 0]
        if write_mask is not None:
            k_w = jnp.where(write_mask, k_w, k_c[lanes, pos])
            v_w = jnp.where(write_mask, v_w, v_c[lanes, pos])
        k_c = k_c.at[lanes, pos].set(k_w)
        v_c = v_c.at[lanes, pos].set(v_w)
        att = _cached_attention_ragged(
            q, k_c, v_c, pos,
            window=getattr(cfg, "sliding_window", None),
        ).reshape(B, 1, cfg.n_embd)
        x = x + att @ lp["wo"]
        h = llama_mod._rms_norm(x, lp["rms2"], cfg.rms_eps)
        return _llama_mlp(x, h, lp, cfg), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = llama_mod._rms_norm(x, params["rmsf"], cfg.rms_eps)
    logits = llama_mod.head_logits(params, x)[:, 0]
    return logits, KVCache(k=k_new, v=v_new)


def _rect_attention_dense(q, k, v, start, window=None):
    """Rectangular causal attention for a lane prefill chunk: q
    [1,C,H,D] at absolute positions start..start+C against the lane's
    full key range [1,T,H_kv,D]; key j visible to chunk query i iff
    j <= start + i (band-clamped under a window). Dense masked einsum
    — the serving chunk is small, so the [C,T] score tile is cheap;
    the long-context path keeps ops/flash_attention_rect."""
    b, c, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, c, hkv, g, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k,
        preferred_element_type=jnp.float32,
    ) / np.sqrt(d)
    qi = start + jnp.arange(c)[None, None, None, :, None]
    ki = jnp.arange(t)[None, None, None, None, :]
    mask = ki <= qi
    if window is not None:
        mask &= (qi - ki) < window
    s = jnp.where(mask, s, -1e30)
    att = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", att, v)
    return o.reshape(b, c, hq, d)


def llama_lane_prefill_chunk(params, cache: KVCache, tokens, lane,
                             start, cfg, rope=None):
    """Prefill ``tokens`` [1, C] of ONE sequence into lane ``lane`` of
    the shared multi-lane cache at positions [start, start+C), leaving
    every other lane untouched — the bounded prefill admission step of
    the continuous-batching scheduler (decode latency is protected by
    capping C, not by pausing the whole batch for a monolithic
    prompt pass).

    ``lane`` and ``start`` are traced scalars, so one compiled program
    serves every lane/offset for a given chunk length C; the scheduler
    pads ragged final chunks up to C (padded positions write garbage
    that the next chunk or decode step overwrites BEFORE any mask can
    expose it, and padded queries' outputs are discarded host-side).

    Returns (chunk logits [1, C, vocab] f32, cache) — all chunk
    positions, so the caller samples the first token from the last
    REAL position of a padded final chunk."""
    B, C = tokens.shape
    if B != 1:
        raise ValueError(
            f"lane prefill takes one sequence, got batch {B}"
        )
    cos_t, sin_t = rope if rope is not None else llama_mod.rope_table(
        cfg, cfg.block_size
    )
    cos = jax.lax.dynamic_slice_in_dim(cos_t, start, C, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_t, start, C, 0)
    x = params["wte"][tokens].astype(cfg.dtype)  # [1,C,E]

    def body(x, layer):
        lp, k_c, v_c = layer
        h = llama_mod._rms_norm(x, lp["rms1"], cfg.rms_eps)
        q, k, v = _llama_qkv(h, lp, cfg, B, C)
        q = llama_mod.apply_rope(q, cos, sin)
        k = llama_mod.apply_rope(k, cos, sin)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (lane, start, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (lane, start, 0, 0))
        k_lane = jax.lax.dynamic_slice_in_dim(k_c, lane, 1, 0)
        v_lane = jax.lax.dynamic_slice_in_dim(v_c, lane, 1, 0)
        att = _rect_attention_dense(
            q, k_lane, v_lane, start,
            window=getattr(cfg, "sliding_window", None),
        ).reshape(B, C, cfg.n_embd)
        x = x + att @ lp["wo"]
        h = llama_mod._rms_norm(x, lp["rms2"], cfg.rms_eps)
        return _llama_mlp(x, h, lp, cfg), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v)
    )
    x = llama_mod._rms_norm(x, params["rmsf"], cfg.rms_eps)
    logits = llama_mod.head_logits(params, x)
    return logits, KVCache(k=k_new, v=v_new)


def _fns_for(cfg) -> tuple:
    """(prefill_fn, step_fn) with model-specific constants (rope
    tables) precomputed once, outside any scan."""
    if isinstance(cfg, llama_mod.LlamaConfig):
        rope = llama_mod.rope_table(cfg, cfg.block_size)
        return (
            functools.partial(
                llama_prefill, rope=rope,
                causal=not getattr(cfg, "prefix_lm", False),
            ),
            functools.partial(llama_decode_step, rope=rope),
        )
    if isinstance(cfg, gpt_mod.GPTConfig):
        return gpt_prefill, gpt_decode_step
    raise TypeError(f"unsupported config type {type(cfg).__name__}")


def _kv_heads(cfg) -> int:
    return getattr(cfg, "n_kv_head", cfg.n_head)


# ---------------------------------------------------------------------------
# Generation loop
# ---------------------------------------------------------------------------


def generate(
    params: Dict[str, Any],
    cfg,
    prompt: jax.Array,  # [B, T_prompt] int32
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Sample ``max_new_tokens`` continuations. Greedy when
    ``temperature == 0``. Returns [B, T_prompt + max_new_tokens].

    The prompt fills the cache in ONE batched forward (prefill); the
    decode loop is one ``lax.scan`` over positions; jit-compatible
    (wrap in jax.jit with static max_new_tokens for repeated use).
    """
    prefill_fn, step_fn = _fns_for(cfg)
    b, t_prompt = prompt.shape
    total = t_prompt + max_new_tokens
    if total > cfg.block_size:
        raise ValueError(
            f"prompt+new = {total} exceeds block_size {cfg.block_size}"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    cache = _cache_for(cfg, b, total, _kv_heads(cfg))
    logits, cache = prefill_fn(params, cache, prompt, cfg)

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None:
            kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        return jax.random.categorical(k, logits).astype(jnp.int32)

    def decode_body(carry, i):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        new_logits, cache = step_fn(
            params, cache, tok, t_prompt + i, cfg
        )
        return (cache, new_logits, key), tok

    (_, _, _), toks = jax.lax.scan(
        decode_body, (cache, logits, key), jnp.arange(max_new_tokens)
    )
    return jnp.concatenate([prompt, toks.T], axis=1)


def decode_logits_sequential(params, cfg, tokens: jax.Array):
    """Teacher-forcing consistency helper (used by tests): run the
    cached decode step over ``tokens`` [B, T] and return the logits at
    every position [B, T, vocab] — must match the training forward."""
    _, step_fn = _fns_for(cfg)
    b, t = tokens.shape
    cache = _cache_for(cfg, b, t, _kv_heads(cfg))

    def body(cache, i):
        logits, cache = step_fn(params, cache, tokens[:, i], i, cfg)
        return cache, logits

    _, logits = jax.lax.scan(body, cache, jnp.arange(t))
    return jnp.swapaxes(logits, 0, 1)


def llama_prefill_chunked(params, cache: KVCache, tokens, cfg,
                          chunk_size: int = 1024, rope=None):
    """Bounded-memory prefill for LONG prompts: query chunks of
    ``chunk_size`` run through all layers against the growing cache
    via the rectangular flash kernel (flash_attention_rect, q_offset
    = chunk start) — peak attention memory is O(chunk * T) with no
    [T, T] score tile, versus the one-shot prefill's full-prompt
    pass. Causal only (a bidirectional GLM prefix cannot be chunked:
    early chunks would need future prefix context — use
    ``llama_prefill(causal=False)``).

    Returns the same (last-position logits, filled cache) contract as
    :func:`llama_prefill`; parity is regression-tested chunk-by-chunk
    (tests/test_flash_rect.py).

    Compilation note: the Python chunk loop traces one program per
    distinct (chunk start, chunk length) pair per call — ceil(T0 /
    chunk_size) compiles on first use for a given prompt length.
    Amortized over a long prompt this is cheap (the final ragged chunk
    is the only shape that varies between prompt lengths), but latency-
    sensitive servers should bucket prompt lengths to multiples of
    ``chunk_size``.
    """
    from dlrover_tpu.ops.flash_attention import flash_attention_rect

    if getattr(cfg, "prefix_lm", False):
        raise ValueError(
            "prefix-LM prompts prefill bidirectionally and cannot "
            "be chunked (early chunks would need future prefix "
            "context); use llama_prefill(causal=False)"
        )
    B, T0 = tokens.shape
    if T0 < 1:
        raise ValueError(
            "llama_prefill_chunked needs at least one prompt token "
            f"(got tokens of shape {tokens.shape})"
        )
    Hkv, E = cfg.n_kv_head, cfg.n_embd
    cos_t, sin_t = rope if rope is not None else llama_mod.rope_table(
        cfg, cfg.block_size
    )
    k_cache, v_cache = cache.k, cache.v
    x_last = None
    for start in range(0, T0, chunk_size):
        end = min(start + chunk_size, T0)
        c = end - start
        cos, sin = cos_t[start:end], sin_t[start:end]
        x = params["wte"][tokens[:, start:end]].astype(cfg.dtype)

        def body(x, layer, start=start, end=end, c=c, cos=cos,
                 sin=sin):
            lp, k_c, v_c = layer
            h = llama_mod._rms_norm(x, lp["rms1"], cfg.rms_eps)
            q, k, v = _llama_qkv(h, lp, cfg, B, c)
            q = llama_mod.apply_rope(q, cos, sin)
            k = llama_mod.apply_rope(k, cos, sin)
            k_c = jax.lax.dynamic_update_slice(
                k_c, k, (0, start, 0, 0)
            )
            v_c = jax.lax.dynamic_update_slice(
                v_c, v, (0, start, 0, 0)
            )
            win = getattr(cfg, "sliding_window", None)
            # Under a band, clamp visible keys to it: per-chunk key
            # traffic is O(chunk * window), not O(chunk * T) — the
            # kernel's dead-block skip saves the MXU work but not
            # the K/V block fetches.
            lo = 0 if win is None else max(0, start - win + 1)
            k_vis, v_vis = k_c[:, lo:end], v_c[:, lo:end]
            off = start - lo
            g = cfg.q_per_kv
            if g == 1:
                att = flash_attention_rect(
                    q, k_vis, v_vis, causal=True, q_offset=off,
                    window=win,
                )
            else:
                # GQA without expanding the cache: q heads i*g+j use
                # kv head i, so group j's strided head slice attends
                # the raw cache — g kernel calls over a small q chunk
                # instead of a q_per_kv-times K/V copy (which would
                # peak at the one-shot prefill's footprint, defeating
                # the point of chunking).
                outs = [
                    flash_attention_rect(
                        q[:, :, j::g], k_vis, v_vis, causal=True,
                        q_offset=off, window=win,
                    )
                    for j in range(g)
                ]
                att = jnp.stack(outs, axis=3).reshape(
                    B, c, cfg.n_head, cfg.head_dim
                )
            att = att.reshape(B, c, E)
            x = x + att @ lp["wo"]
            h = llama_mod._rms_norm(x, lp["rms2"], cfg.rms_eps)
            return _llama_mlp(x, h, lp, cfg), (k_c, v_c)

        x, (k_cache, v_cache) = jax.lax.scan(
            body, x, (params["blocks"], k_cache, v_cache)
        )
        x_last = x[:, -1:]
    x = llama_mod._rms_norm(x_last, params["rmsf"], cfg.rms_eps)
    logits = llama_mod.head_logits(params, x)[:, 0]
    return logits, KVCache(k=k_cache, v=v_cache)
