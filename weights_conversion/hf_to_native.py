"""HuggingFace -> native weight conversion (Llama/Llama-2/CodeLlama/Mistral/
Mixtral/Falcon).

Reference: weights_conversion/hf_to_megatron.py (llama_to_megatron:116,
falcon_to_megatron:59). Differences by design: output is ONE tp/pp-agnostic
orbax checkpoint tagged ``release`` (sharding happens at load time via
NamedSharding — no mp_rank_XX files), and the QKV layout is the group-major
fused kernel documented in models/transformer.py.

Run as a script:
    python -m weights_conversion.hf_to_native --model <hf-path-or-name> \
        --out ckpts/llama2-7b [--model_name llama2]
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Dict

import numpy as np

from megatron_llm_tpu.models.language_model import padded_vocab_size
from weights_conversion.permute_qkv import hf_rows_to_interleaved


def _np(t) -> np.ndarray:
    return t.detach().to("cpu").float().numpy()


def pack_qkv(q: np.ndarray, k: np.ndarray, v: np.ndarray,
             n: int, nkv: int, d: int) -> np.ndarray:
    """[n*d, h], [nkv*d, h], [nkv*d, h] (out-major, torch layout) ->
    fused group-major kernel [h, (n+2nkv)*d]."""
    h = q.shape[1]
    g = n // nkv
    qg = q.reshape(nkv, g, d, h)
    kg = k.reshape(nkv, 1, d, h)
    vg = v.reshape(nkv, 1, d, h)
    fused = np.concatenate([qg, kg, vg], axis=1)  # [nkv, g+2, d, h]
    return np.ascontiguousarray(
        fused.reshape(nkv * (g + 2) * d, h).T
    )  # [h, (n+2nkv)d]


def unpack_qkv(kernel: np.ndarray, n: int, nkv: int, d: int):
    """Inverse of pack_qkv: [h, (n+2nkv)d] -> (q, k, v) torch-layout arrays."""
    h = kernel.shape[0]
    g = n // nkv
    fused = kernel.T.reshape(nkv, g + 2, d, h)
    q = fused[:, :g].reshape(n * d, h)
    k = fused[:, g].reshape(nkv * d, h)
    v = fused[:, g + 1].reshape(nkv * d, h)
    return q, k, v


def convert_llama_state(state: Dict[str, Any], cfg) -> Dict[str, Any]:
    """HF Llama/Mistral/Mixtral state_dict -> native params pytree (numpy,
    fp32); Mixtral swaps the dense MLP subtree for router + expert stacks."""
    m = cfg.model
    n, nkv, d, h = (m.num_attention_heads, m.num_attention_heads_kv,
                    m.kv_channels, m.hidden_size)
    L = m.num_layers
    vpad = padded_vocab_size(m.vocab_size, cfg)

    def emb_pad(w):
        out = np.zeros((vpad, h), np.float32)
        out[: w.shape[0]] = w
        return out

    def stack(key_fn):
        return np.stack([key_fn(i) for i in range(L)])

    def W(name, i):
        return _np(state[f"model.layers.{i}.{name}.weight"])

    def qkv_kernel(i):
        q = hf_rows_to_interleaved(W("self_attn.q_proj", i), d)
        k = hf_rows_to_interleaved(W("self_attn.k_proj", i), d)
        v = W("self_attn.v_proj", i)
        return pack_qkv(q, k, v, n, nkv, d)

    def qkv_bias(i):
        # Qwen2: per-projection bias vectors ride the same interleave +
        # group-major fuse as the kernels (a column-vector is just a
        # kernel with h=1)
        B = lambda name: _np(  # noqa: E731
            state[f"model.layers.{i}.{name}.bias"])[:, None]
        qb = hf_rows_to_interleaved(B("self_attn.q_proj"), d)
        kb = hf_rows_to_interleaved(B("self_attn.k_proj"), d)
        return pack_qkv(qb, kb, B("self_attn.v_proj"), n, nkv, d)[0]

    attention = {
        "qkv": {"kernel": stack(qkv_kernel)},
        "dense": {
            "kernel": stack(lambda i: W("self_attn.o_proj", i).T)
        },
    }
    if m.add_qkv_bias:
        attention["qkv"]["bias"] = stack(qkv_bias)

    params = {
        "embedding": {
            "word_embeddings": emb_pad(_np(state["model.embed_tokens.weight"]))
        },
        "layers": {
            "input_norm": {"scale": stack(lambda i: W("input_layernorm", i))},
            "post_norm": {
                "scale": stack(lambda i: W("post_attention_layernorm", i))
            },
            "attention": attention,
        },
        "final_norm": {"scale": _np(state["model.norm.weight"])},
    }
    if m.num_experts is not None:
        # HF Mixtral block_sparse_moe: w2(silu(w1(x)) * w3(x)) per expert —
        # w3 (up) is our value half (slot 0), w1 (gate) our gated half
        # (slot 1), w2 (down) our fc2; gate.weight [E, h] -> router [h, E]
        E = m.num_experts

        def EW(i, e, wname):
            return _np(state[
                f"model.layers.{i}.block_sparse_moe.experts.{e}.{wname}.weight"
            ])

        params["layers"]["moe"] = {
            "router": {
                "kernel": stack(
                    lambda i: _np(
                        state[f"model.layers.{i}.block_sparse_moe.gate.weight"]
                    ).T
                )
            },
            "experts": {
                "fc1": {
                    "kernel": stack(
                        lambda i: np.stack([
                            np.stack([EW(i, e, "w3").T, EW(i, e, "w1").T])
                            for e in range(E)
                        ])
                    )
                },
                "fc2": {
                    "kernel": stack(
                        lambda i: np.stack(
                            [EW(i, e, "w2").T for e in range(E)]
                        )
                    )
                },
            },
        }
    else:
        params["layers"]["mlp"] = {
            # fc1 [h, 2, ffn]: slot 0 = value (up_proj), slot 1 = gated
            # half (gate_proj) — mlp computes x1 * silu(x2)
            "fc1": {
                "kernel": stack(
                    lambda i: np.stack(
                        [W("mlp.up_proj", i).T, W("mlp.gate_proj", i).T],
                        axis=1,
                    )
                )
            },
            "fc2": {"kernel": stack(lambda i: W("mlp.down_proj", i).T)},
        }
    if not m.tie_embed_logits:
        params["lm_head"] = {
            "kernel": np.ascontiguousarray(emb_pad(_np(state["lm_head.weight"])).T)
        }
    return params


def convert_falcon_state(state: Dict[str, Any], cfg) -> Dict[str, Any]:
    """HF Falcon state_dict -> native params (parallel-attn block)."""
    m = cfg.model
    n, nkv, d, h = (m.num_attention_heads, m.num_attention_heads_kv,
                    m.kv_channels, m.hidden_size)
    L = m.num_layers
    vpad = padded_vocab_size(m.vocab_size, cfg)

    def emb_pad(w):
        out = np.zeros((vpad, h), np.float32)
        out[: w.shape[0]] = w
        return out

    def W(name, i):
        return _np(state[f"transformer.h.{i}.{name}.weight"])

    def B(name, i):
        key = f"transformer.h.{i}.{name}.bias"
        return _np(state[key]) if key in state else None

    def qkv_kernel(i):
        # HF falcon fused qkv is already [nkv, g+2, d, h]-ordered
        w = W("self_attention.query_key_value", i)  # [(n+2nkv)d, h]
        g = n // nkv
        grouped = w.reshape(nkv, g + 2, d, h)
        q = grouped[:, :g].reshape(n * d, h)
        k = grouped[:, g].reshape(nkv * d, h)
        v = grouped[:, g + 1].reshape(nkv * d, h)
        q = hf_rows_to_interleaved(q, d)
        k = hf_rows_to_interleaved(k, d)
        return pack_qkv(q, k, v, n, nkv, d)

    def stack(fn):
        return np.stack([fn(i) for i in range(L)])

    ln_name = "ln_attn" if m.parallel_layernorm else "input_layernorm"
    layers = {
        "input_norm": {
            "scale": stack(lambda i: W(ln_name, i)),
            "bias": stack(lambda i: B(ln_name, i)),
        },
        "attention": {
            "qkv": {"kernel": stack(qkv_kernel)},
            "dense": {"kernel": stack(lambda i: W("self_attention.dense", i).T)},
        },
        "mlp": {
            "fc1": {"kernel": stack(lambda i: W("mlp.dense_h_to_4h", i).T)},
            "fc2": {"kernel": stack(lambda i: W("mlp.dense_4h_to_h", i).T)},
        },
    }
    if m.parallel_layernorm:
        layers["mlp_norm"] = {
            "scale": stack(lambda i: W("ln_mlp", i)),
            "bias": stack(lambda i: B("ln_mlp", i)),
        }
    return {
        "embedding": {
            "word_embeddings": emb_pad(_np(state["transformer.word_embeddings.weight"]))
        },
        "layers": layers,
        "final_norm": {
            "scale": _np(state["transformer.ln_f.weight"]),
            "bias": _np(state["transformer.ln_f.bias"]),
        },
    }


def convert_hf_model(hf_model, cfg) -> Dict[str, Any]:
    state = hf_model.state_dict()
    if cfg.model_name == "falcon":
        return convert_falcon_state(state, cfg)
    return convert_llama_state(state, cfg)


def config_from_hf(hf_config, model_name: str):
    """Derive a native Config from an HF config object."""
    from megatron_llm_tpu.models import make_config

    kw = dict(
        num_layers=hf_config.num_hidden_layers,
        hidden_size=hf_config.hidden_size,
        num_attention_heads=hf_config.num_attention_heads,
        vocab_size=hf_config.vocab_size,
        max_position_embeddings=getattr(hf_config, "max_position_embeddings", 2048),
    )
    # HF rope scaling -> native: "linear" maps to --rope_scaling_factor
    # (the reference's position-interpolation path,
    # positional_embeddings.py:11); "llama3" maps to the native frequency
    # remap (ops/rope.py:llama3_scale_freqs). Anything else (yarn /
    # dynamic) must fail loudly: silently dropping it would convert to a
    # model with wrong RoPE frequencies.
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling:
        stype = scaling.get("type") or scaling.get("rope_type")
        if stype == "linear":
            kw["rope_scaling_factor"] = float(scaling["factor"])
        elif stype == "llama3":
            kw["rope_scaling_type"] = "llama3"
            kw["rope_scaling_factor"] = float(scaling["factor"])
            kw["rope_llama3_low_freq_factor"] = float(
                scaling.get("low_freq_factor", 1.0))
            kw["rope_llama3_high_freq_factor"] = float(
                scaling.get("high_freq_factor", 4.0))
            kw["rope_llama3_original_max_position"] = int(
                scaling.get("original_max_position_embeddings", 8192))
        else:
            raise ValueError(
                f"unsupported rope_scaling type {stype!r}; only linear "
                "interpolation and the llama3 remap have native equivalents"
            )

    if model_name == "falcon":
        # same fail-loudly posture as rope_scaling above: a config feature we
        # cannot represent must not silently convert to garbage logits
        if getattr(hf_config, "alibi", False):
            raise ValueError("alibi falcon models are not supported "
                             "(native falcon uses RoPE)")
        if not getattr(hf_config, "parallel_attn", True):
            raise ValueError("sequential-attention falcon (parallel_attn="
                             "False) is not supported")
        kw["num_attention_heads_kv"] = getattr(hf_config, "num_kv_heads", None) or (
            1 if getattr(hf_config, "multi_query", False)
            else hf_config.num_attention_heads
        )
        kw["parallel_layernorm"] = getattr(hf_config, "new_decoder_architecture", False)
        kw["tie_embed_logits"] = True
        kw["rope_theta"] = getattr(hf_config, "rope_theta", 10000.0)
    else:
        kw["num_attention_heads_kv"] = getattr(
            hf_config, "num_key_value_heads", hf_config.num_attention_heads
        )
        kw["ffn_hidden_size"] = hf_config.intermediate_size
        kw["layernorm_epsilon"] = hf_config.rms_norm_eps
        kw["rope_theta"] = getattr(hf_config, "rope_theta", 10000.0)
        # pass the checkpoint's tying through (Llama-3.2 ties; most others
        # don't) — validate_family still rejects combinations the family
        # contract forbids, rather than silently untying
        kw["tie_embed_logits"] = bool(
            getattr(hf_config, "tie_word_embeddings", False))
        if model_name == "ouro":
            # a LoopLM's config (no weight converter yet): the passes, and
            # the one exit threshold the looped forward reads logits at
            from megatron_llm_tpu.models.language_model import EXIT_BELOW_ONE

            threshold = float(getattr(hf_config, "early_exit_threshold", 1.0))
            if threshold < 1.0:
                raise ValueError(EXIT_BELOW_ONE.format(threshold=threshold))
            kw["loop_steps"] = hf_config.total_ut_steps
            kw["kv_channels"] = hf_config.head_dim
        if model_name == "mistral":
            kw["sliding_window_size"] = getattr(hf_config, "sliding_window", 4096)
        if model_name == "qwen2":
            # Qwen2 SWA is layer-banded (full attention below
            # max_window_layers); native sliding_window_size is uniform, so
            # only the all-layers case maps — anything else must fail
            # loudly (same posture as rope_scaling above)
            if getattr(hf_config, "use_sliding_window", False):
                mwl = getattr(hf_config, "max_window_layers",
                              hf_config.num_hidden_layers)
                if mwl < hf_config.num_hidden_layers:
                    raise ValueError(
                        "qwen2 with max_window_layers < num_hidden_layers "
                        "(mixed full/sliding attention) has no native "
                        "equivalent")
                kw["sliding_window_size"] = hf_config.sliding_window
        if model_name == "mixtral":
            kw["num_experts"] = hf_config.num_local_experts
            kw["moe_router_topk"] = hf_config.num_experts_per_tok
            kw["sliding_window_size"] = getattr(hf_config, "sliding_window", None)
            # keep the checkpoint's aux-loss weight, not our default
            kw["moe_aux_loss_coeff"] = float(
                getattr(hf_config, "router_aux_loss_coef", 0.01)
            )
            # HF Mixtral routes DROPLESSLY; the default capacity_factor
            # 1.25 would silently drop tokens relative to the source model
            # during finetune/inference. num_experts/topk guarantees every
            # token a slot at either expert it routes to (ADVICE round 2).
            kw["moe_capacity_factor"] = (
                hf_config.num_local_experts / hf_config.num_experts_per_tok
            )
    return make_config(model_name, **kw)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", required=True, help="HF model path or name")
    ap.add_argument("--out", required=True, help="output checkpoint dir")
    ap.add_argument("--model_name", default="llama2",
                    choices=["llama", "llama2", "codellama", "llama3",
                             "mistral", "mixtral", "falcon", "qwen2"])
    args = ap.parse_args()

    import orbax.checkpoint as ocp
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(args.model)
    cfg = config_from_hf(hf_cfg, args.model_name)
    model = AutoModelForCausalLM.from_pretrained(args.model)
    params = convert_hf_model(model, cfg)

    out = os.path.abspath(os.path.join(args.out, "release"))
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(out, "params"), params)
    ckptr.wait_until_finished()  # the save is async; don't exit half-written
    with open(os.path.join(args.out, "latest_checkpointed_iteration.txt"), "w") as f:
        f.write("release")
    print(f"saved release checkpoint to {out}")


if __name__ == "__main__":
    main()
