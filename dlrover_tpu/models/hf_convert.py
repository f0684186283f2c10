"""HuggingFace checkpoint -> native pytree converters.

The reference consumes HF models directly (its Llama example builds
``AutoModelForCausalLM`` and wraps layers,
/root/reference/atorch/examples/llama2/fsdp_llama2.py:8-14); this
framework uses native JAX modules instead, so migration needs a weight
bridge. ``llama_params_from_hf`` maps an HF Llama ``state_dict`` (or
model) onto models/llama.py's stacked-layer pytree:

* torch ``Linear.weight`` is [out, in] — transposed to [in, out];
* per-layer tensors are stacked on a leading ``layers`` dim for the
  ``lax.scan`` backbone;
* rotary convention matches (HF ``rotate_half`` == our split-halves
  apply_rope), so no permutation of q/k rows is needed.

Torch stays host-side only: tensors convert through numpy and the
result is a plain numpy pytree the caller shards via
``jax.device_put`` / ``make_sharded_init``-style shardings.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np

from dlrover_tpu.models.llama import LlamaConfig


def _np(t) -> np.ndarray:
    """torch tensor | np array -> float32 numpy on host."""
    if hasattr(t, "detach"):
        t = t.detach().to("cpu").float().numpy()
    return np.asarray(t, np.float32)


def llama_config_from_hf(hf_config) -> LlamaConfig:
    """Map an HF Llama (or Mistral/Mixtral/OLMoE) config to ours —
    Mixtral configs carry num_local_experts/num_experts_per_tok and
    OLMoE ones num_experts/num_experts_per_tok/norm_topk_prob, which
    switch the native family into MoE mode (OLMoE also into
    normalised queries and keys); a Mistral ``sliding_window``
    carries through to the banded flash kernel."""
    olmoe = getattr(hf_config, "model_type", "") == "olmoe"
    n_experts = getattr(
        hf_config, "num_experts" if olmoe else "num_local_experts", 0
    )
    return LlamaConfig(
        qk_norm=olmoe,
        # Mixtral always renormalises its top-k weights.
        moe_renorm_top_k=getattr(hf_config, "norm_topk_prob", True),
        moe_aux_loss_weight=getattr(
            hf_config, "router_aux_loss_coef", 1e-2
        ),
        sliding_window=getattr(hf_config, "sliding_window", None),
        vocab_size=hf_config.vocab_size,
        block_size=hf_config.max_position_embeddings,
        n_layer=hf_config.num_hidden_layers,
        n_head=hf_config.num_attention_heads,
        n_kv_head=getattr(
            hf_config, "num_key_value_heads",
            hf_config.num_attention_heads,
        ),
        n_embd=hf_config.hidden_size,
        intermediate=hf_config.intermediate_size,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        rms_eps=hf_config.rms_norm_eps,
        n_experts=n_experts,
        moe_top_k=getattr(hf_config, "num_experts_per_tok", 2),
        # No-drop capacity (capacity == all tokens) for the one-hot
        # path an ``expert`` mesh axis selects: HF has no capacity
        # concept, so a converted model must never drop or it
        # diverges from the source. Lower it explicitly to fine-tune
        # with GShard-style dropping.
        moe_capacity_factor=(
            float(n_experts)
            / max(getattr(hf_config, "num_experts_per_tok", 2), 1)
            if n_experts
            else 1.25
        ),
    )


def llama_params_from_hf(
    state_dict: Mapping[str, Any],
    cfg: LlamaConfig,
    dtype: Any = np.float32,
) -> Dict[str, Any]:
    """HF Llama(ForCausalLM) state_dict -> our param pytree.

    Accepts either the ``model.``-prefixed CausalLM dict or a bare
    LlamaModel dict. Tied-embedding checkpoints (no lm_head.weight)
    fall back to wte for the head, matching HF's tie_word_embeddings
    at conversion time — but the returned pytree carries ``wte`` and
    ``lm_head`` as two *independent* leaves, so the tie does not
    survive training: gradients flow to each copy separately and they
    diverge from the first optimizer step. That is fine for inference
    and full-finetune-with-untied-head, but differs from HF's tied
    fine-tune semantics; callers who need the tie preserved should
    check ``"lm_head.weight" not in state_dict`` and alias the leaves
    in their own step function (e.g. overwrite lm_head from wte after
    each update, or compute logits against wte directly).
    """
    if hasattr(state_dict, "state_dict"):
        raise TypeError("pass model.state_dict(), not the model")
    sd = dict(state_dict)
    used = set()

    def get(name):
        for key in (name, f"model.{name}"):
            if key in sd:
                used.add(key)
                return _np(sd[key])
        raise KeyError(
            f"HF state_dict is missing {name!r} "
            f"(have e.g. {list(sd)[:4]})"
        )

    L = cfg.n_layer

    def stack(fmt, transpose=True):
        mats = []
        for i in range(L):
            w = get(fmt.format(i=i))
            mats.append(w.T if transpose else w)
        return np.stack(mats).astype(dtype)

    wte = get("embed_tokens.weight").astype(dtype)
    try:
        head = _np(sd["lm_head.weight"]).astype(dtype)
    except KeyError:
        head = wte  # tie_word_embeddings
    blocks = {
        "rms1": stack(
            "layers.{i}.input_layernorm.weight", transpose=False
        ).astype(np.float32),
        "wq": stack("layers.{i}.self_attn.q_proj.weight"),
        "wk": stack("layers.{i}.self_attn.k_proj.weight"),
        "wv": stack("layers.{i}.self_attn.v_proj.weight"),
        "wo": stack("layers.{i}.self_attn.o_proj.weight"),
        "rms2": stack(
            "layers.{i}.post_attention_layernorm.weight",
            transpose=False,
        ).astype(np.float32),
    }
    if cfg.qk_norm:
        blocks.update(
            q_norm=stack(
                "layers.{i}.self_attn.q_norm.weight", transpose=False
            ).astype(np.float32),
            k_norm=stack(
                "layers.{i}.self_attn.k_norm.weight", transpose=False
            ).astype(np.float32),
        )
    if cfg.n_experts > 0:
        # Mixtral block_sparse_moe: gate -> router, experts j:
        # w1 = SwiGLU gate, w3 = up, w2 = down. OLMoE: mlp.gate and
        # mlp.experts.j.{gate,up,down}_proj.
        if any(k.endswith("layers.0.mlp.gate.weight") for k in sd):
            moe, names = "mlp", ("gate_proj", "up_proj", "down_proj")
        else:
            moe, names = "block_sparse_moe", ("w1", "w3", "w2")

        def stack_experts(fmt):
            mats = []
            for i in range(L):
                mats.append(
                    np.stack(
                        [
                            get(fmt.format(i=i, j=j)).T
                            for j in range(cfg.n_experts)
                        ]
                    )
                )
            return np.stack(mats).astype(dtype)  # [L, E, in, out]

        blocks["moe"] = {
            "router": stack(
                "layers.{i}." + moe + ".gate.weight"
            ).astype(np.float32),
            **{
                leaf: stack_experts(
                    "layers.{i}." + moe + ".experts.{j}." + name
                    + ".weight"
                )
                for leaf, name in zip(("wg", "wi", "wo"), names)
            },
        }
    else:
        blocks.update(
            w_gate=stack("layers.{i}.mlp.gate_proj.weight"),
            w_up=stack("layers.{i}.mlp.up_proj.weight"),
            w_down=stack("layers.{i}.mlp.down_proj.weight"),
        )
    params = {
        "wte": wte,
        "blocks": blocks,
        "rmsf": get("norm.weight").astype(np.float32),
        "lm_head": head,
    }
    used.add("lm_head.weight")
    # Models with weights we don't map (e.g. attention_bias=True
    # checkpoints carry q_proj.bias) would silently convert into a
    # different function — refuse instead of degrading.
    leftover = {
        k for k in sd
        if k not in used
        and not k.endswith("rotary_emb.inv_freq")  # recomputed
    }
    if leftover:
        raise ValueError(
            "HF state_dict contains tensors this converter does not "
            f"map (unsupported architecture variant?): "
            f"{sorted(leftover)[:6]}"
        )
    return params


# ---------------------------------------------------------------------------
# ChatGLM2/3 (GLM family, models/glm.py)
# ---------------------------------------------------------------------------


def _interleaved_to_halves_perm(rot: int) -> np.ndarray:
    """Index permutation mapping ChatGLM's interleaved rotary layout
    (pairs (x_{2j}, x_{2j+1}) rotated together) onto our split-halves
    apply_rope layout (x_j with x_{j+rot/2}). perm[j] = source index
    in the interleaved layout for target position j."""
    half = rot // 2
    perm = np.empty(rot, np.int64)
    perm[:half] = 2 * np.arange(half)
    perm[half:] = 2 * np.arange(half) + 1
    return perm


def glm_config_from_hf(hf_config) -> LlamaConfig:
    """Map a ChatGLM2/3 HF config onto the native GLM shape
    (models/glm.py: Llama backbone + qkv bias + half-dim rotary).

    Long-context ChatGLM checkpoints (e.g. the 32k variants) scale the
    rotary base by ``rope_ratio`` — HF's modeling_chatglm computes
    ``base = 10000 * rope_ratio`` — so it is read into rope_theta here
    rather than silently defaulted.  ``original_rope`` flips the
    interleaved rotary convention; the permutation mapping assumes the
    standard (True) layout, so a False value is rejected rather than
    converted wrong."""
    if not getattr(hf_config, "original_rope", True):
        raise ValueError(
            "ChatGLM config has original_rope=False (non-standard "
            "rotary layout); the interleaved->split-halves rotary "
            "permutation in glm_params_from_hf assumes the standard "
            "layout and would convert this checkpoint incorrectly"
        )
    return LlamaConfig(
        vocab_size=hf_config.padded_vocab_size,
        block_size=hf_config.seq_length,
        n_layer=hf_config.num_layers,
        n_head=hf_config.num_attention_heads,
        n_kv_head=(
            hf_config.multi_query_group_num
            if getattr(hf_config, "multi_query_attention", False)
            else hf_config.num_attention_heads
        ),
        n_embd=hf_config.hidden_size,
        intermediate=hf_config.ffn_hidden_size,
        rms_eps=hf_config.layernorm_epsilon,
        qkv_bias=getattr(hf_config, "add_qkv_bias", True),
        rotary_pct=0.5,
        rope_theta=10000.0 * getattr(hf_config, "rope_ratio", 1.0),
        # Same generation semantics as the native presets: prompts
        # prefill bidirectionally (models/glm.py).
        prefix_lm=True,
    )


def glm_params_from_hf(
    state_dict, cfg: LlamaConfig, dtype: Any = np.float32
) -> Dict[str, Any]:
    """ChatGLM2/3 state_dict -> our param pytree.

    Three layout conversions on top of the Llama mapping:

    * the fused ``query_key_value`` weight/bias splits into wq/wk/wv
      rows ([E + 2*kv, E] row-major: q then k then v);
    * the fused SwiGLU ``dense_h_to_4h`` ([2I, E], silu(first half) *
      second half) splits into w_gate/w_up;
    * ChatGLM rotates interleaved pairs over the first half of each
      head; our apply_rope rotates split halves — the q/k columns of
      each head's rotary slice are permuted so the two conventions
      compute the same function (validated by
      tests/test_glm.py::test_rotary_permutation_equivalence).
    """
    if hasattr(state_dict, "state_dict"):
        raise TypeError("pass model.state_dict(), not the model")
    sd = dict(state_dict)
    used = set()

    def get(name):
        for key in (name, f"transformer.{name}"):
            if key in sd:
                used.add(key)
                return _np(sd[key])
        raise KeyError(f"ChatGLM state_dict is missing {name!r}")

    L, E, D = cfg.n_layer, cfg.n_embd, cfg.head_dim
    kv = cfg.n_kv_head * D
    rot = int(D * cfg.rotary_pct)
    perm = _interleaved_to_halves_perm(rot)

    def permute_heads(w, n_heads):
        """Permute each head's rotary slice of the OUTPUT dim.
        w: [..., n_heads*D] column-major heads."""
        shaped = w.reshape(w.shape[:-1] + (n_heads, D))
        fixed = np.concatenate(
            [shaped[..., perm], shaped[..., rot:]], axis=-1
        )
        return fixed.reshape(w.shape)

    wq_l, wk_l, wv_l, bq_l, bk_l, bv_l = [], [], [], [], [], []
    gate_l, up_l, down_l, wo_l, r1_l, r2_l = [], [], [], [], [], []
    for i in range(L):
        pre = f"encoder.layers.{i}"
        qkv_w = get(f"{pre}.self_attention.query_key_value.weight")
        wq_l.append(permute_heads(qkv_w[:E].T, cfg.n_head))
        wk_l.append(permute_heads(qkv_w[E:E + kv].T, cfg.n_kv_head))
        wv_l.append(qkv_w[E + kv:].T)
        if cfg.qkv_bias:
            qkv_b = get(f"{pre}.self_attention.query_key_value.bias")
            bq_l.append(permute_heads(qkv_b[:E], cfg.n_head))
            bk_l.append(
                permute_heads(qkv_b[E:E + kv], cfg.n_kv_head)
            )
            bv_l.append(qkv_b[E + kv:])
        wo_l.append(get(f"{pre}.self_attention.dense.weight").T)
        h4 = get(f"{pre}.mlp.dense_h_to_4h.weight")
        gate_l.append(h4[: cfg.intermediate].T)
        up_l.append(h4[cfg.intermediate:].T)
        down_l.append(get(f"{pre}.mlp.dense_4h_to_h.weight").T)
        r1_l.append(get(f"{pre}.input_layernorm.weight"))
        r2_l.append(get(f"{pre}.post_attention_layernorm.weight"))

    blocks = {
        "rms1": np.stack(r1_l).astype(np.float32),
        "wq": np.stack(wq_l).astype(dtype),
        "wk": np.stack(wk_l).astype(dtype),
        "wv": np.stack(wv_l).astype(dtype),
        "wo": np.stack(wo_l).astype(dtype),
        "rms2": np.stack(r2_l).astype(np.float32),
        "w_gate": np.stack(gate_l).astype(dtype),
        "w_up": np.stack(up_l).astype(dtype),
        "w_down": np.stack(down_l).astype(dtype),
    }
    if cfg.qkv_bias:
        blocks.update(
            bq=np.stack(bq_l).astype(dtype),
            bk=np.stack(bk_l).astype(dtype),
            bv=np.stack(bv_l).astype(dtype),
        )
    params = {
        "wte": get("embedding.word_embeddings.weight").astype(dtype),
        "blocks": blocks,
        "rmsf": get("encoder.final_layernorm.weight").astype(
            np.float32
        ),
        "lm_head": get("output_layer.weight").astype(dtype),
    }
    leftover = {
        k for k in sd
        if k not in used and "rotary_pos_emb" not in k
    }
    if leftover:
        raise ValueError(
            "ChatGLM state_dict contains tensors this converter "
            f"does not map: {sorted(leftover)[:6]}"
        )
    return params
