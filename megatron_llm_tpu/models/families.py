"""Model families — flag-bundle wrappers over the shared language model.

The reference implements these as thin subclasses of GPTModel that assert the
architecture's flag bundle (model/llama_model.py:22-30, falcon_model.py:18-29,
mistral_model.py:30). Here a family is a validated Config plus the shared
functional model; construction helpers below mirror those assertions.
"""

from __future__ import annotations

from megatron_llm_tpu.config.arguments import Config, apply_architecture


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def validate_family(cfg: Config) -> Config:
    m = cfg.model
    name = cfg.model_name
    if name in ("llama", "llama2", "codellama", "llama3"):
        # llama_model.py:22-30
        _check(m.position_embedding_type == "rotary", "llama requires rotary embeddings")
        _check(m.glu_activation == "swiglu", "llama requires swiglu")
        _check(m.use_rms_norm, "llama requires RMSNorm")
        _check(not m.use_bias, "llama has no biases")
        if name != "llama3":  # Llama-3.2 small models tie embeddings
            _check(not m.tie_embed_logits, "llama uses untied embeddings")
    elif name == "falcon":
        # falcon_model.py:18-29
        _check(m.parallel_attn, "falcon requires parallel_attn")
        _check(m.position_embedding_type == "rotary", "falcon requires rotary embeddings")
        _check(not m.use_rms_norm, "falcon uses LayerNorm, not RMSNorm")
    elif name == "mistral":
        # mistral_model.py:30 pins 4096; we only require a window to be set so
        # HF checkpoints with other window sizes convert cleanly
        _check(m.sliding_window_size is not None,
               "mistral requires sliding_window_size")
        _check(m.use_rms_norm and m.glu_activation == "swiglu", "mistral uses llama block")
    elif name == "mixtral":
        _check(m.num_experts is not None and m.num_experts > 1,
               "mixtral requires num_experts > 1")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "mixtral uses the llama block")
        _check(not m.use_bias, "mixtral has no biases")
    elif name == "joyai":
        _check(m.mla, "joyai requires attention_type 'mla'")
        _check(m.num_experts is not None and m.num_experts > 1,
               "joyai requires num_experts > 1")
        _check(m.moe_score_func == "sigmoid" and m.moe_selection_bias,
               "joyai routes by bias-corrected sigmoid scores")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "joyai uses RMSNorm and SwiGLU")
        _check(not m.use_bias and not m.parallel_attn,
               "joyai is a sequential block without biases")
        _check(m.position_embedding_type == "rotary",
               "joyai requires rotary embeddings")
    elif name == "smallthinker":
        _check(m.layer_period > 1 and 0 in m.sliding_window_layout
               and 1 in m.sliding_window_layout,
               "smallthinker mixes full and window layers: give "
               "sliding_window_layout and rope_layout")
        _check(m.num_experts is not None and m.num_experts > 1
               and not m.moe_shared_experts,
               "smallthinker requires num_experts > 1 and no shared expert")
        _check(m.moe_router_input == "layer_input"
               and m.moe_score_func == "softmax" and m.moe_normalize_gates,
               "smallthinker's router reads the layer input and weighs the "
               "chosen experts by a softmax over them")
        _check(m.use_rms_norm and m.glu_activation == "reglu",
               "smallthinker uses RMSNorm and ReGLU")
        _check(not m.use_bias and not m.parallel_attn,
               "smallthinker is a sequential block without biases")
    elif name == "commanda":
        _check(m.layer_period > 1 and 0 in m.sliding_window_layout
               and 1 in m.sliding_window_layout,
               "commanda mixes window and full layers: give "
               "sliding_window_layout and rope_layout")
        _check(m.parallel_attn and not m.parallel_layernorm,
               "commanda is a parallel block behind ONE norm")
        _check(not m.use_rms_norm and not m.norm_bias and not m.use_bias,
               "commanda uses bias-free LayerNorm and no biases")
        _check(m.glu_activation == "swiglu", "commanda uses SwiGLU experts")
        _check(m.num_experts is not None and m.num_experts > 1,
               "commanda requires num_experts > 1")
        _check(m.moe_score_func == "sigmoid" and not m.moe_selection_bias
               and m.moe_normalize_gates
               and m.moe_routed_scaling_factor == 1.0,
               "commanda routes by plain sigmoid scores, normalised over "
               "the chosen, unscaled")
        _check(m.moe_shared_experts > 0
               and m.moe_shared_combination == "average",
               "commanda averages its shared experts")
        _check(m.tie_embed_logits, "commanda ties its head to the embedding")
    elif name == "brumby":
        _check(m.retention, "brumby requires attention_type 'retention'")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "brumby uses RMSNorm and SwiGLU")
        _check(not m.use_bias and not m.add_qkv_bias and not m.parallel_attn,
               "brumby is a sequential block without biases (its gate's "
               "bias is the layer's own)")
        _check(m.position_embedding_type == "rotary",
               "brumby keeps RoPE on q and k")
        _check(m.num_experts is None and not m.tie_embed_logits,
               "brumby is dense with an untied head")
        _check(m.kv_channels % 8 == 0,
               "brumby's feature map tiles a head in blocks of 8")
    elif name == "gigachat35":
        _check(m.mla and m.attention_output_gate,
               "gigachat35 requires attention_type 'mla' with its output "
               "gate")
        _check(m.delta and m.linear_layout and 0 in m.linear_layout
               and 1 in m.linear_layout,
               "gigachat35 mixes linear and latent-attention layers: give "
               "linear_layout")
        _check(m.post_sublayer_norms and m.zero_centered_gated_norm
               and m.use_rms_norm,
               "gigachat35 norms before and after each sublayer with a "
               "sigmoid-gained RMSNorm")
        _check(m.glu_activation == "swiglu" and m.swiglu_limit,
               "gigachat35 uses SwiGLU under swiglu_limit")
        _check(m.num_experts is not None and m.num_experts > 1
               and m.moe_score_func == "sigmoid" and m.moe_selection_bias,
               "gigachat35 routes by bias-corrected sigmoid scores")
        _check(not m.use_bias and not m.parallel_attn
               and not m.tie_embed_logits,
               "gigachat35 is a sequential block without biases and an "
               "untied head")
        _check(m.position_embedding_type == "rotary"
               and m.rope_scaling_type == "yarn",
               "gigachat35 rotates under YaRN")
    elif name == "nemotron_h":
        _check(bool(m.sublayer_pattern)
               and set(m.sublayer_pattern) <= set("ME*"),
               "nemotron_h is a stack of one-sublayer layers: give "
               "sublayer_pattern (the published hybrid_override_pattern), "
               "its letters M, E and *")
        _check(m.use_rms_norm and m.glu_activation is None
               and m.activation == "squared_relu",
               "nemotron_h uses RMSNorm and non-gated relu^2 experts")
        _check(m.num_experts is None or (
            m.moe_score_func == "sigmoid" and m.moe_selection_bias
            and m.moe_normalize_gates),
               "nemotron_h routes by bias-corrected sigmoid scores, "
               "normalised over the chosen")
        _check(not m.use_bias and not m.tie_embed_logits,
               "nemotron_h has no biases but the conv's and an untied head")
        _check(m.position_embedding_type != "rotary",
               "nemotron_h's attention carries no position signal")
    elif name == "lfm2":
        pattern = m.sublayer_pattern or ""
        _check(bool(pattern) and set(pattern) <= set("C*DE")
               and len(pattern) % 2 == 0
               and set(pattern[0::2]) <= set("C*")
               and set(pattern[1::2]) <= set("DE"),
               "lfm2 is a stack of mixer-then-feed-forward layers: give "
               "sublayer_pattern two letters a layer, C or * then D or E "
               "(config/arguments.py lfm2_sublayers writes layer_types out)")
        _check(m.qk_head_norm and m.position_embedding_type == "rotary",
               "lfm2's attention norms q and k a head, then rotates them")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "lfm2 uses RMSNorm and SwiGLU")
        _check(m.num_experts is None or (
            m.moe_score_func == "sigmoid" and m.moe_selection_bias
            and m.moe_normalize_gates and not m.moe_shared_experts),
               "lfm2 routes by bias-corrected sigmoid scores, normalised "
               "over the chosen, with no shared expert")
        _check(not m.use_bias and m.tie_embed_logits,
               "lfm2 has no biases and ties its head to the embedding")
    elif name == "sdar_moe":
        _check(m.diffusion_block_length and m.mask_token_id is not None,
               "sdar_moe generates by diffusion over blocks: give "
               "diffusion_block_length and mask_token_id")
        _check(m.qk_head_norm and m.position_embedding_type == "rotary",
               "sdar_moe's attention norms q and k a head, then rotates them")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "sdar_moe uses RMSNorm and SwiGLU")
        _check(m.num_experts is not None and m.num_experts > 1
               and m.moe_score_func == "softmax" and m.moe_normalize_gates
               and not m.moe_selection_bias and not m.moe_shared_experts,
               "sdar_moe routes by a softmax over all experts, normalised "
               "over the chosen, with no shared expert")
        _check(not m.use_bias and not m.tie_embed_logits,
               "sdar_moe has no biases and an untied head")
    elif name == "ouro":
        _check(m.post_sublayer_norms and not m.zero_centered_gated_norm
               and m.use_rms_norm,
               "ouro norms before and after each sublayer with a plain "
               "RMSNorm")
        _check(m.glu_activation == "swiglu"
               and m.position_embedding_type == "rotary"
               and not m.qk_head_norm,
               "ouro uses SwiGLU and rotates q and k with no norm a head")
        _check(m.num_experts is None and not m.parallel_attn,
               "ouro is a dense sequential block")
        _check(not m.use_bias and not m.tie_embed_logits,
               "ouro has no biases and an untied head")
    elif name == "axk2":
        _check(m.mla and m.index_topk and m.index_n_heads
               and m.index_head_dim,
               "axk2 requires attention_type 'mla' under an indexer: give "
               "index_topk, index_n_heads and index_head_dim")
        _check(m.index_head_dim >= m.qk_rope_head_dim,
               "axk2's index vectors rotate their first qk_rope_head_dim "
               "values")
        _check(m.attention_output_gate and m.attention_gate_headwise
               and m.gated_norm and m.gated_norm_rank > 0,
               "axk2 gates its attention's output a head and its norms "
               "through a low-rank pair")
        _check(m.num_experts is not None and m.num_experts > 1
               and m.moe_score_func == "sigmoid" and m.moe_selection_bias
               and m.moe_normalize_gates,
               "axk2 routes by bias-corrected sigmoid scores, normalised "
               "over the chosen")
        _check(m.num_experts % m.moe_n_group == 0
               and 0 < m.moe_topk_group <= m.moe_n_group
               and m.moe_router_topk
               <= m.moe_topk_group * (m.num_experts // m.moe_n_group),
               "axk2's router picks among moe_topk_group of moe_n_group "
               "equal groups of experts")
        _check(m.use_rms_norm and m.glu_activation == "swiglu"
               and not m.use_bias and not m.parallel_attn
               and not m.tie_embed_logits,
               "axk2 is a sequential RMSNorm / SwiGLU block without "
               "biases and an untied head")
        _check(m.position_embedding_type == "rotary"
               and m.rope_scaling_type == "yarn",
               "axk2 rotates under YaRN")
    elif name == "qwen2":
        # beyond-reference: llama block + QKV-only bias
        _check(m.position_embedding_type == "rotary",
               "qwen2 requires rotary embeddings")
        _check(m.use_rms_norm and m.glu_activation == "swiglu",
               "qwen2 uses the llama block")
        _check(not m.use_bias, "qwen2 has no global biases")
        _check(m.add_qkv_bias, "qwen2 requires add_qkv_bias")
    return cfg


def make_config(model_name: str, **overrides) -> Config:
    """Build a finalized family Config; overrides are flat flag names."""
    from megatron_llm_tpu.config.arguments import _set_flag

    cfg = Config()
    apply_architecture(cfg, model_name)
    for k, v in overrides.items():
        _set_flag(cfg, k, v)
    cfg.finalize()
    return validate_family(cfg)
