"""Llama-3 family (beyond-reference): flag bundle, the "llama3" RoPE
frequency remap (HF ``rope_type: "llama3"``, Llama-3.1+), and HF config
round-tripping. The reference stops at CodeLlama's linear interpolation
(positional_embeddings.py:11); this family extends the same machinery."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models import make_config
from megatron_llm_tpu.ops.rope import llama3_scale_freqs, precompute_freqs

L3_SCALING = dict(factor=8.0, low_freq_factor=1.0, high_freq_factor=4.0,
                  original_max_position=8192)


def _base_freqs(dim=128, theta=500_000.0):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def test_family_bundle():
    cfg = make_config("llama3-8b")
    m = cfg.model
    assert m.rope_theta == 500_000.0
    assert m.num_attention_heads_kv == 8 and m.num_attention_heads == 32
    assert m.ffn_hidden_size == 14336
    assert m.use_rms_norm and m.glu_activation == "swiglu" and not m.use_bias
    from megatron_llm_tpu.models.language_model import padded_vocab_size
    assert padded_vocab_size(m.vocab_size, cfg) == 128256  # already 128-divisible


def test_family_invariants_enforced():
    with pytest.raises(ValueError, match="rotary"):
        make_config("llama3", num_layers=2, hidden_size=64,
                    num_attention_heads=4, vocab_size=256,
                    position_embedding_type="absolute")


def test_remap_piecewise():
    freqs = _base_freqs()
    out = np.asarray(llama3_scale_freqs(freqs, **L3_SCALING))
    base = np.asarray(freqs)
    wavelen = 2 * np.pi / base
    hi = wavelen < 8192 / 4.0   # well inside original context: untouched
    lo = wavelen > 8192 / 1.0   # beyond original context: pure interpolation
    assert hi.any() and lo.any()
    np.testing.assert_allclose(out[hi], base[hi], rtol=1e-6)
    np.testing.assert_allclose(out[lo], base[lo] / 8.0, rtol=1e-6)
    band = ~hi & ~lo
    assert ((out[band] >= base[band] / 8.0 - 1e-9)
            & (out[band] <= base[band] + 1e-9)).all()


def test_remap_matches_hf():
    """Cross-check against transformers' own llama3 rule when available."""
    try:
        from transformers import LlamaConfig
        from transformers.modeling_rope_utils import _compute_llama3_parameters
    except ImportError:
        pytest.skip("transformers rope utils not available")
    hf_cfg = LlamaConfig(
        hidden_size=512, num_attention_heads=4, rope_theta=500_000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8192},
    )
    try:
        hf_freqs, _ = _compute_llama3_parameters(hf_cfg, device="cpu")
    except Exception as e:  # signature drift across versions
        pytest.skip(f"HF helper signature mismatch: {e}")
    ours = np.asarray(llama3_scale_freqs(_base_freqs(), **L3_SCALING))
    np.testing.assert_allclose(ours, np.asarray(hf_freqs), rtol=1e-5)


def test_precompute_freqs_llama3_vs_linear():
    c3, s3 = precompute_freqs(64, 128, theta=500_000.0, scaling_factor=8.0,
                              scaling_type="llama3")
    cl, sl = precompute_freqs(64, 128, theta=500_000.0, scaling_factor=8.0,
                              scaling_type="linear")
    assert not np.allclose(np.asarray(c3), np.asarray(cl))
    # factor 1.0 under llama3 == unscaled (the remap is gated on factor)
    c1, _ = precompute_freqs(64, 128, theta=500_000.0, scaling_factor=1.0,
                             scaling_type="llama3")
    c0, _ = precompute_freqs(64, 128, theta=500_000.0)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c0))


def test_unknown_scaling_type_fails_loudly():
    with pytest.raises(ValueError, match="scaling_type"):
        precompute_freqs(64, 128, scaling_factor=8.0, scaling_type="ntk")


def test_hf_config_roundtrip():
    from weights_conversion.hf_to_native import config_from_hf
    from weights_conversion.native_to_hf import hf_config_from_native

    try:
        from transformers import LlamaConfig
    except ImportError:
        pytest.skip("transformers not available")
    src = LlamaConfig(
        num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=256, vocab_size=1024,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        rope_theta=500_000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8192},
    )
    cfg = config_from_hf(src, "llama3")
    m = cfg.model
    assert m.rope_scaling_type == "llama3"
    assert m.rope_scaling_factor == 8.0
    assert m.rope_llama3_high_freq_factor == 4.0
    back = hf_config_from_native(cfg, vocab_size=1024)
    rs = back.rope_scaling
    assert rs["rope_type"] == "llama3" and rs["factor"] == 8.0
    assert rs["original_max_position_embeddings"] == 8192


def test_forward_smoke():
    """Tiny llama3 model with the remap active: loss computes and is finite
    (drives make_rope_cache's scaling_type wiring end to end)."""
    from megatron_llm_tpu.models import init_model_params, loss_from_batch

    cfg = make_config("llama3", num_layers=2, hidden_size=128,
                      num_attention_heads=4, num_attention_heads_kv=2,
                      vocab_size=512, params_dtype="float32",
                      max_position_embeddings=128,
                      rope_scaling_type="llama3", rope_scaling_factor=8.0,
                      use_flash_attn=False)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 512)
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:],
             "loss_mask": jnp.ones((2, 64))}
    loss, _ = loss_from_batch(cfg, params, batch)
    assert np.isfinite(float(loss))
