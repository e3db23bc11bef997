"""Native -> HuggingFace weight conversion (inverse of hf_to_native).

Reference: weights_conversion/megatron_to_hf.py (un-permute qkv, write HF
safetensors/config). Loads an orbax checkpoint (any tp/pp it was trained
with — shardings are erased on host gather), rebuilds the HF state dict, and
saves with ``save_pretrained`` so ``AutoModelForCausalLM.from_pretrained``
loads it directly (tools/push_to_hub.py then uploads it).

    python -m weights_conversion.native_to_hf --load ckpts/run1 \
        --out /tmp/hf-export --model_name llama2 [--vocab_size 32000]
"""

from __future__ import annotations

import argparse
from typing import Any, Dict

import numpy as np

from weights_conversion.hf_to_native import pack_qkv, unpack_qkv
from weights_conversion.permute_qkv import interleaved_rows_to_hf


def to_hf_llama_state(params: Dict[str, Any], cfg, vocab_size: int) -> Dict[str, Any]:
    """Native params pytree -> HF Llama/Mistral state dict (numpy)."""
    m = cfg.model
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    L = m.num_layers
    layers = params["layers"]
    state: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight":
            np.asarray(params["embedding"]["word_embeddings"])[:vocab_size],
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    if "lm_head" in params:
        state["lm_head.weight"] = np.ascontiguousarray(
            np.asarray(params["lm_head"]["kernel"]).T[:vocab_size]
        )
    for i in range(L):
        pre = f"model.layers.{i}"
        get = lambda *ks: np.asarray(_walk(layers, ks)[i])
        q, k, v = unpack_qkv(get("attention", "qkv", "kernel"), n, nkv, d)
        state[f"{pre}.self_attn.q_proj.weight"] = interleaved_rows_to_hf(q, d)
        state[f"{pre}.self_attn.k_proj.weight"] = interleaved_rows_to_hf(k, d)
        state[f"{pre}.self_attn.v_proj.weight"] = v
        state[f"{pre}.self_attn.o_proj.weight"] = np.ascontiguousarray(
            get("attention", "dense", "kernel").T
        )
        if m.add_qkv_bias:
            # Qwen2: the fused bias vector is a 1-column kernel — same
            # unpack + de-interleave as the weights
            qb, kb, vb = unpack_qkv(
                get("attention", "qkv", "bias")[None, :], n, nkv, d)
            state[f"{pre}.self_attn.q_proj.bias"] = (
                interleaved_rows_to_hf(qb, d)[:, 0])
            state[f"{pre}.self_attn.k_proj.bias"] = (
                interleaved_rows_to_hf(kb, d)[:, 0])
            state[f"{pre}.self_attn.v_proj.bias"] = vb[:, 0]
        if m.num_experts is not None:
            # inverse of the mixtral branch in convert_llama_state
            state[f"{pre}.block_sparse_moe.gate.weight"] = (
                np.ascontiguousarray(get("moe", "router", "kernel").T)
            )
            fc1 = get("moe", "experts", "fc1", "kernel")  # [E, 2, h, ffn]
            fc2 = get("moe", "experts", "fc2", "kernel")  # [E, ffn, h]
            for e in range(m.num_experts):
                epre = f"{pre}.block_sparse_moe.experts.{e}"
                state[f"{epre}.w3.weight"] = np.ascontiguousarray(fc1[e, 0].T)
                state[f"{epre}.w1.weight"] = np.ascontiguousarray(fc1[e, 1].T)
                state[f"{epre}.w2.weight"] = np.ascontiguousarray(fc2[e].T)
        else:
            fc1 = get("mlp", "fc1", "kernel")  # [h, 2, ffn]
            state[f"{pre}.mlp.up_proj.weight"] = np.ascontiguousarray(fc1[:, 0, :].T)
            state[f"{pre}.mlp.gate_proj.weight"] = np.ascontiguousarray(fc1[:, 1, :].T)
            state[f"{pre}.mlp.down_proj.weight"] = np.ascontiguousarray(
                get("mlp", "fc2", "kernel").T
            )
        state[f"{pre}.input_layernorm.weight"] = get("input_norm", "scale")
        state[f"{pre}.post_attention_layernorm.weight"] = get("post_norm", "scale")
    return state


def to_hf_falcon_state(params: Dict[str, Any], cfg, vocab_size: int) -> Dict[str, Any]:
    """Native params pytree -> HF Falcon state dict (inverse of
    convert_falcon_state; reference megatron_to_hf.py falcon branch)."""
    m = cfg.model
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    layers = params["layers"]
    state: Dict[str, np.ndarray] = {
        "transformer.word_embeddings.weight":
            np.asarray(params["embedding"]["word_embeddings"])[:vocab_size],
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
        # falcon ties lm_head to the embedding
        "lm_head.weight":
            np.asarray(params["embedding"]["word_embeddings"])[:vocab_size],
    }
    ln_name = "ln_attn" if m.parallel_layernorm else "input_layernorm"
    for i in range(m.num_layers):
        pre = f"transformer.h.{i}"
        get = lambda *ks: np.asarray(_walk(layers, ks)[i])
        q, k, v = unpack_qkv(get("attention", "qkv", "kernel"), n, nkv, d)
        q = interleaved_rows_to_hf(q, d)
        k = interleaved_rows_to_hf(k, d)
        # HF falcon's fused qkv is the same group-major layout as native
        state[f"{pre}.self_attention.query_key_value.weight"] = (
            np.ascontiguousarray(pack_qkv(q, k, v, n, nkv, d).T)
        )
        state[f"{pre}.self_attention.dense.weight"] = np.ascontiguousarray(
            get("attention", "dense", "kernel").T
        )
        state[f"{pre}.mlp.dense_h_to_4h.weight"] = np.ascontiguousarray(
            get("mlp", "fc1", "kernel").T
        )
        state[f"{pre}.mlp.dense_4h_to_h.weight"] = np.ascontiguousarray(
            get("mlp", "fc2", "kernel").T
        )
        state[f"{pre}.{ln_name}.weight"] = get("input_norm", "scale")
        state[f"{pre}.{ln_name}.bias"] = get("input_norm", "bias")
        if m.parallel_layernorm:
            state[f"{pre}.ln_mlp.weight"] = get("mlp_norm", "scale")
            state[f"{pre}.ln_mlp.bias"] = get("mlp_norm", "bias")
    return state


def _walk(tree, keys):
    for k in keys:
        tree = tree[k]
    return tree


def hf_config_from_native(cfg, vocab_size: int):
    from transformers import FalconConfig, LlamaConfig, MistralConfig

    m = cfg.model
    if not m.rope_scaling_factor or m.rope_scaling_factor == 1.0:
        rope_scaling = None
    elif getattr(m, "rope_scaling_type", "linear") == "llama3":
        rope_scaling = {
            "rope_type": "llama3",
            "factor": float(m.rope_scaling_factor),
            "low_freq_factor": float(m.rope_llama3_low_freq_factor),
            "high_freq_factor": float(m.rope_llama3_high_freq_factor),
            "original_max_position_embeddings":
                int(m.rope_llama3_original_max_position),
        }
    else:
        rope_scaling = {"type": "linear", "factor": float(m.rope_scaling_factor)}
    if cfg.model_name == "falcon":
        return FalconConfig(
            vocab_size=vocab_size,
            hidden_size=m.hidden_size,
            num_hidden_layers=m.num_layers,
            num_attention_heads=m.num_attention_heads,
            num_kv_heads=m.num_attention_heads_kv,
            new_decoder_architecture=m.parallel_layernorm,
            parallel_attn=m.parallel_attn,
            # without new_decoder_architecture HF ignores num_kv_heads and
            # derives nkv from multi_query — keep them consistent
            multi_query=(m.num_attention_heads_kv == 1),
            bias=False,
            alibi=False,
            max_position_embeddings=m.max_position_embeddings,
            layer_norm_epsilon=m.layernorm_epsilon,
            rope_theta=m.rope_theta,
            rope_scaling=rope_scaling,
        )
    common = dict(
        vocab_size=vocab_size,
        hidden_size=m.hidden_size,
        intermediate_size=m.ffn_hidden_size,
        num_hidden_layers=m.num_layers,
        num_attention_heads=m.num_attention_heads,
        num_key_value_heads=m.num_attention_heads_kv,
        max_position_embeddings=m.max_position_embeddings,
        rms_norm_eps=m.layernorm_epsilon,
        rope_theta=m.rope_theta,
        tie_word_embeddings=m.tie_embed_logits,
    )
    if rope_scaling:
        common["rope_scaling"] = rope_scaling
    if cfg.model_name == "mistral":
        return MistralConfig(sliding_window=m.sliding_window_size, **common)
    if cfg.model_name == "qwen2":
        from transformers import Qwen2Config

        return Qwen2Config(**common)
    if cfg.model_name == "mixtral":
        from transformers import MixtralConfig

        return MixtralConfig(
            sliding_window=m.sliding_window_size,
            num_local_experts=m.num_experts,
            num_experts_per_tok=m.moe_router_topk,
            router_aux_loss_coef=m.moe_aux_loss_coeff,
            **common,
        )
    return LlamaConfig(**common)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--load", required=True, help="native checkpoint dir")
    ap.add_argument("--out", required=True, help="HF output dir")
    ap.add_argument("--model_name", default="llama2")
    ap.add_argument("--vocab_size", type=int, default=None,
                    help="unpadded vocab size (default: from checkpoint meta)")
    args = ap.parse_args()

    import json
    import os

    import torch
    from transformers import AutoModelForCausalLM

    from megatron_llm_tpu.checkpointing import (
        checkpoint_dir,
        load_checkpoint,
        read_tracker,
    )
    from megatron_llm_tpu.models import make_config

    iteration, release = read_tracker(args.load)
    meta_path = os.path.join(
        checkpoint_dir(args.load, iteration or 0, release), "meta.json"
    )
    with open(meta_path) as f:
        saved = json.load(f)["config"]
    cfg = make_config(args.model_name or saved.get("model_name", "llama2"),
                      **{k: v for k, v in saved["model"].items() if v is not None})

    import orbax.checkpoint as ocp

    path = checkpoint_dir(os.path.abspath(args.load), iteration or 0, release)
    params = ocp.StandardCheckpointer().restore(os.path.join(path, "params"))

    vocab = args.vocab_size or saved["model"].get("vocab_size")
    if cfg.model_name == "falcon":
        state = to_hf_falcon_state(params, cfg, vocab)
    else:
        state = to_hf_llama_state(params, cfg, vocab)
    hf_cfg = hf_config_from_native(cfg, vocab)
    model = AutoModelForCausalLM.from_config(hf_cfg)
    model.load_state_dict(
        {k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in state.items()},
        strict=not cfg.model.tie_embed_logits,
    )
    model.save_pretrained(args.out, safe_serialization=True)
    print(f"saved HF model to {args.out}")


if __name__ == "__main__":
    main()
