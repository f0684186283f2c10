"""HF Llama -> native pytree conversion: logits parity vs transformers."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.models.hf_convert import (  # noqa: E402
    llama_config_from_hf,
    llama_params_from_hf,
)


@pytest.fixture(scope="module")
def hf_model():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.eval()
    return model


def test_logits_match_transformers(hf_model):
    cfg = llama_config_from_hf(hf_model.config)
    assert cfg.n_kv_head == 2 and cfg.head_dim == 16
    params = llama_params_from_hf(hf_model.state_dict(), cfg)

    import dataclasses

    cfg = dataclasses.replace(
        cfg, dtype=np.float32, remat=False, use_flash_attention=False
    )
    tokens_np = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 16)
    )
    with torch.no_grad():
        want = hf_model(
            torch.from_numpy(tokens_np)
        ).logits.float().numpy()
    got = np.asarray(
        llama.forward(
            jax.tree.map(np.asarray, params),
            tokens_np.astype(np.int32),
            cfg,
        )
    )
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)


def test_mixtral_logits_match_transformers():
    """MoE family parity: tiny HF Mixtral vs native gated-expert
    Llama-MoE (capacity pinned high so neither path drops tokens —
    HF Mixtral has no capacity concept)."""
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
        router_aux_loss_coef=0.0,
    )
    torch.manual_seed(1)
    model = transformers.MixtralForCausalLM(hf_cfg)
    model.eval()

    cfg = llama_config_from_hf(hf_cfg)
    assert cfg.n_experts == 4 and cfg.moe_top_k == 2
    params = llama_params_from_hf(model.state_dict(), cfg)

    import dataclasses

    cfg = dataclasses.replace(
        cfg,
        dtype=np.float32,
        remat=False,
        use_flash_attention=False,
        # capacity >= all tokens so routing never drops (HF parity)
        moe_capacity_factor=float(cfg.n_experts) / cfg.moe_top_k,
    )
    tokens_np = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 16)
    )
    with torch.no_grad():
        want = model(
            torch.from_numpy(tokens_np)
        ).logits.float().numpy()
    got = np.asarray(
        llama.forward(
            jax.tree.map(np.asarray, params),
            tokens_np.astype(np.int32),
            cfg,
        )
    )
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-3)


def test_olmoe_round_trip_through_transformers():
    """A tiny HF OLMoE (normalised queries and keys, 8 experts, 3 a
    token, weights not renormalised) -> the ``olmoe`` key map -> the
    native block on the sorted, dropless path: the logits and the
    load-balancing loss are HF's. Gains are moved off 1 and the
    router scaled up so that both count and the loads are uneven."""
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=48,
        num_hidden_layers=1,
        num_attention_heads=4,
        num_key_value_heads=4,
        num_experts=8,
        num_experts_per_tok=3,
        norm_topk_prob=False,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
        router_aux_loss_coef=0.01,
        output_router_logits=True,
    )
    torch.manual_seed(2)
    model = transformers.OlmoeForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.add_(0.3 * torch.randn_like(p))
            if name.endswith("mlp.gate.weight"):
                p.mul_(40.0)
    model.eval()

    import dataclasses

    cfg = llama_config_from_hf(hf_cfg)
    assert (cfg.n_experts, cfg.moe_top_k) == (8, 3)
    assert cfg.qk_norm and not cfg.moe_renorm_top_k
    params = llama_params_from_hf(model.state_dict(), cfg)
    assert params["blocks"]["moe"]["wg"].shape == (1, 8, 64, 48)
    assert params["blocks"]["q_norm"].shape == (1, 64)
    cfg = dataclasses.replace(
        cfg, dtype=np.float32, remat=False, use_flash_attention=False,
        moe_z_loss_weight=0.0,
    )
    tokens_np = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 17)
    )
    inputs = torch.from_numpy(tokens_np[:, :-1])
    with torch.no_grad():
        out = model(inputs)
    params = jax.tree.map(np.asarray, params)
    got = np.asarray(
        llama.forward(params, tokens_np[:, :-1].astype(np.int32), cfg)
    )
    np.testing.assert_allclose(
        got, out.logits.float().numpy(), atol=5e-4, rtol=5e-3
    )
    _, aux = llama.backbone_with_aux(
        params, tokens_np[:, :-1].astype(np.int32), cfg
    )
    np.testing.assert_allclose(
        float(aux), 0.01 * float(out.aux_loss), rtol=1e-4
    )


def test_tied_embeddings_fallback(hf_model):
    cfg = llama_config_from_hf(hf_model.config)
    sd = {
        k: v for k, v in hf_model.state_dict().items()
        if k != "lm_head.weight"
    }
    params = llama_params_from_hf(sd, cfg)
    np.testing.assert_array_equal(params["lm_head"], params["wte"])


def test_mistral_config_carries_sliding_window():
    """A Mistral HF config (Llama arch + sliding_window) maps onto the
    native family with the band intact; sliding_window=8 is NARROWER
    than the 32-token probe, so the band actively masks and the logits
    parity vs HF eager Mistral proves both implementations agree on
    the (q - k < window) band convention."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        sliding_window=8,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    cfg = llama_config_from_hf(hf_cfg)
    assert cfg.sliding_window == 8
    assert cfg.n_kv_head == 2

    torch.manual_seed(1)
    model = transformers.MistralForCausalLM(hf_cfg)
    model.eval()
    params = llama_params_from_hf(model.state_dict(), cfg)

    import dataclasses

    cfg = dataclasses.replace(
        cfg, dtype=np.float32, remat=False, use_flash_attention=False
    )
    tokens_np = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 32)
    )
    with torch.no_grad():
        want = model(
            torch.from_numpy(tokens_np)
        ).logits.float().numpy()
    got = np.asarray(
        llama.forward(
            jax.tree.map(np.asarray, params),
            tokens_np.astype(np.int32),
            cfg,
        )
    )
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)
