"""Configuration system for the TPU-native Megatron-LLM rebuild.

Replaces the reference's argparse flag system (``megatron/arguments.py`` — ~180
underscore-style flags in 16 groups) with typed dataclass groups plus a CLI
parser generated from the dataclass fields.  Flag names are kept identical to
the reference wherever the concept survives the TPU redesign, so launch
scripts translate one-to-one.

Reference: /root/reference/megatron/arguments.py:15-1106.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from dataclasses import dataclass, field, fields
from typing import Any, List, Optional, Tuple


# ---------------------------------------------------------------------------
# Group dataclasses
# ---------------------------------------------------------------------------


@dataclass
class ModelConfig:
    """Network architecture (reference ``_add_network_size_args``)."""

    num_layers: int = 2
    hidden_size: int = 128
    ffn_hidden_size: Optional[int] = None  # default 4*h (or derived for GLU)
    num_attention_heads: int = 4
    # GQA / MQA: number of KV heads.  None => MHA (== num_attention_heads).
    num_attention_heads_kv: Optional[int] = None
    kv_channels: Optional[int] = None  # default hidden_size // num_heads
    max_position_embeddings: int = 2048
    # 'rotary' | 'absolute' | 'none'
    position_embedding_type: str = "rotary"
    rope_theta: float = 10000.0
    # Linear position-interpolation scaling (CodeLlama 32K path):
    # positions are divided by this factor (reference positional_embeddings.py:11).
    rope_scaling_factor: float = 1.0
    # 'linear' | 'llama3' (HF rope_type "llama3" frequency remap — Llama-3.1+;
    # beyond-reference, see ops/rope.py:llama3_scale_freqs)
    rope_scaling_type: str = "linear"
    rope_llama3_low_freq_factor: float = 1.0
    rope_llama3_high_freq_factor: float = 4.0
    rope_llama3_original_max_position: int = 8192
    # 'yarn' (ops/rope.py yarn_scale_freqs): the band of frequencies between
    # `beta_fast` and `beta_slow` turns over the original context is blended
    # from extrapolation to interpolation by `rope_scaling_factor`;
    # `rope_yarn_mscale_all_dim` > 0 scales the softmax by m^2, m = 0.1 x
    # mscale_all_dim x ln(factor) + 1 (the DeepSeek-V3 convention; the cos /
    # sin table stays unscaled: mscale / mscale_all_dim = 1)
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_original_max_position: int = 4096
    rope_yarn_mscale_all_dim: float = 0.0
    vocab_size: Optional[int] = None  # set from tokenizer
    make_vocab_size_divisible_by: int = 128
    layernorm_epsilon: float = 1e-5
    use_rms_norm: bool = True
    # LayerNorm's additive bias (use_rms_norm false); false = scale only,
    # the Cohere block's norm
    norm_bias: bool = True
    # GLU activation: None | 'swiglu' | 'geglu' | 'reglu' | 'liglu'
    glu_activation: Optional[str] = "swiglu"
    # plain activation when glu_activation is None: 'gelu' | 'relu' | 'squared_relu'
    activation: str = "gelu"
    use_bias: bool = False  # reference --no_bias inverted
    # Qwen2-style: bias on the fused QKV projection ONLY (dense/mlp stay
    # bias-free); independent of use_bias (beyond-reference family)
    add_qkv_bias: bool = False
    # Falcon-style: attention and MLP computed in parallel from the same LN.
    parallel_attn: bool = False
    # Falcon-40B style: separate LN for the parallel MLP branch.
    parallel_layernorm: bool = False
    # Mistral sliding-window attention size (None = full causal).
    sliding_window_size: Optional[int] = None
    # The layer PATTERN, one period of it (models/transformer.py owns what
    # a kind of layer does): entry j says whether the layers l with
    # l % period == j attend inside `sliding_window_size` (1) or to the whole
    # causal prefix (0), and whether they rotate q and k (1) or carry no
    # position signal (0).  The published `sliding_window_layout` /
    # `rope_layout` of the SmallThinker family, cut to their first period.
    # None = every layer alike (window where sliding_window_size is set,
    # rotation where position_embedding_type is 'rotary'): a period of one
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    tie_embed_logits: bool = False  # share input embedding and output head
    apply_query_key_layer_scaling: bool = False
    attention_softmax_in_fp32: bool = True
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    init_method_std: float = 0.02
    # scale output-layer init by 1/sqrt(2*num_layers) (reference use_scaled_init_method)
    use_scaled_init_method: bool = True
    # LIMA per-layer dropout: linearly ramp hidden_dropout from 0 to value.
    lima_dropout: bool = False
    # FP8 matmuls (TransformerEngine-path analog, ops/fp8.py):
    # None | 'e4m3' (reference --fp8_e4m3) | 'hybrid' (--fp8_hybrid:
    # e4m3 forward, e5m2 gradients). Functional on any backend; a
    # throughput win only on fp8-capable TPU generations.
    fp8: Optional[str] = None
    fp8_margin: int = 0  # back off scales by 2^-margin (reference --fp8_margin)
    # Fuse the LM-head matmul with cross entropy, scanned over this many
    # vocab chunks, so the full [b, s, vocab] fp32 logits are never
    # materialized in the training loss (ops/cross_entropy.py:
    # chunked_softmax_cross_entropy_from_hidden). None = off.
    ce_vocab_chunks: Optional[int] = None
    # BERT next-sentence/sentence-order binary head (bert_model.py:125)
    bert_binary_head: bool = False
    # bidirectional (non-causal) self-attention — BERT / T5 encoder
    bidirectional: bool = False
    # number of token-type (segment) embeddings; 0 disables (BERT uses 2)
    num_tokentypes: int = 0
    # T5: decoder depth (None = num_layers); decoder layers get cross-attention
    decoder_num_layers: Optional[int] = None
    # --- Mixture of Experts (beyond-reference: the reference has no MoE) ---
    # number of experts per MoE layer; None = dense model
    num_experts: Optional[int] = None
    # 'topk' (token-choice, GShard/Mixtral) | 'expert_choice' (Zhou et al.
    # 2022: experts pick tokens — balanced by construction; a research/
    # training configuration: it leaks future tokens within a routing
    # group, see docs/guide/moe.md)
    moe_router_type: str = "topk"
    moe_router_topk: int = 2
    # expert capacity = ceil(topk * tokens * capacity_factor / num_experts)
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    # tokens are routed in fixed-size groups of (at most) this many tokens so
    # the one-hot dispatch/combine tensors stay O(group * capacity) instead
    # of O(seq^2) at long context (GShard grouping); seq_length must be a
    # multiple of the group size when longer than it
    moe_group_size: int = 4096
    # renormalize the selected top-k gates to sum to 1 (Mixtral convention)
    moe_normalize_gates: bool = True
    # Switch-style load-balance aux loss and ST-MoE router z-loss weights
    moe_aux_loss_coeff: float = 0.01
    moe_z_loss_coeff: float = 0.0
    # the router's options, data of the family (models/moe.py `route`):
    # how a token's scores are made ('softmax' over the experts, Mixtral;
    # 'sigmoid' of each logit, the DeepSeek-V3 lineage) ...
    moe_score_func: str = "softmax"
    # ... whether a learned per-expert bias is added to the scores for the
    # top-k SELECTION only, never to the weights (`e_score_correction_bias`
    # of `topk_method: noaux_tc`; the leaf `moe/router/bias`) ...
    moe_selection_bias: bool = False
    # ... and what multiplies the (renormalised) weights of the chosen
    moe_routed_scaling_factor: float = 1.0
    # group-limited selection (DeepSeek-V3's `n_group` / `topk_group`): the
    # experts stand in `moe_n_group` equal groups, a group's score is the
    # sum of its two largest selection scores, and the top-k is taken among
    # the experts of the `moe_topk_group` best groups.  1 / 1: no groups
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # what `moe_normalize_gates` adds to the chosen scores' sum (DeepSeek-V3's
    # modelling code: 1e-20; LFM2's: 1e-6)
    moe_gate_eps: float = 1e-20
    # width of one expert's FFN; None = ffn_hidden_size (Mixtral).  With a
    # value, ffn_hidden_size stays the width of the dense layers' MLP
    moe_ffn_hidden_size: Optional[int] = None
    # shared experts: a dense MLP of moe_shared_experts x the expert width
    # that every token takes, added to the routed sum
    moe_shared_experts: int = 0
    # how the shared experts' outputs join: 'sum' (the DeepSeek lineage) or
    # 'average' (their sum over their number: Cohere's
    # shared_expert_combination_strategy)
    moe_shared_combination: str = "sum"
    # what the router reads: 'post_attention' (the norm before the expert
    # layer, Mixtral and the DeepSeek lineage) or 'layer_input' (the normed
    # input of the layer, what the attention reads too: SmallThinker's
    # router placed before attention)
    moe_router_input: str = "post_attention"
    # the chip's share of an expert-parallel layer: the router keeps its
    # `num_experts` outputs and its top-k, this program HOLDS
    # `moe_experts_held` of the experts, from `moe_first_held_expert` on,
    # and computes the part of the layer's output those give; what the
    # absent experts would add is left out (no code stands in for the
    # other chips).  None = all of them
    moe_experts_held: Optional[int] = None
    moe_first_held_expert: int = 0
    # dense layers that run BEFORE the `num_layers` layers of the scanned
    # stack (DeepSeek's `first_k_dense_replace`: a published
    # `num_hidden_layers` is dense_prefix_layers + num_layers).  They are a
    # stack of their own, `params["dense_layers"]`, so that the scanned
    # stack keeps one parameter shape; 0 = a uniform model, unchanged
    dense_prefix_layers: int = 0
    # --- latent attention (MLA, the DeepSeek-V2/V3 lineage) ---
    # 'mha' (MHA/GQA/MQA over kv_channels) | 'mla': low-rank queries
    # (q_lora_rank, with a norm), a joint latent + rope-key down-projection
    # (kv_lora_rank + qk_rope_head_dim values a token: ALL the cache holds),
    # decoupled RoPE on qk_rope_head_dim of the qk_nope_head_dim +
    # qk_rope_head_dim query dims, values of v_head_dim
    # | 'retention': gated degree-2 power retention over the same q / k / v
    # heads (ops/retention.py): no softmax, no key or value kept, a
    # sequence's past is a float32 state of constant size a KV head; a
    # per-head RMSNorm on q and k before RoPE and a log-sigmoid gate a KV
    # head from the layer's normed input come with it (the degree, the
    # feature layout and the state's dtype are the mechanism's, not flags)
    attention_type: str = "mha"
    # 'mha' only: one RMSNorm a head on q and on k before the rotation
    # (leaves `attention/q_norm`, `attention/k_norm` of kv_channels)
    qk_head_norm: bool = False
    # generation by diffusion over blocks (SDAR): the attention mask is
    # BLOCK-causal (a query sees every key up to the end of its own block of
    # `diffusion_block_length` positions, cut from position 0), and the
    # engine unmasks a block's positions over several denoising steps from
    # the row `mask_token_id` of the vocabulary (generation/blocks.py).
    # None: a causal model, which decodes one token a step
    diffusion_block_length: Optional[int] = None
    mask_token_id: Optional[int] = None
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # an elementwise sigmoid gate on the attention's output, read from the
    # layer's normed input (`gated_attention`; the leaf `attention/g_proj`)
    attention_output_gate: bool = False
    # ... or ONE value a head (the "headwise" form of the same paper; the
    # leaf is then [h, heads]): A.X-K2's head-specific gate
    attention_gate_headwise: bool = False
    # --- learned sparse attention (the DeepSeek-V3.2 lightning indexer,
    # models/sparse_mla.py): `index_n_heads` index queries of
    # `index_head_dim` a token score every earlier token's ONE cached index
    # key, and a query attends the `index_topk` best-scored latent rows
    # alone (all of them up to that context).  None: every key is read
    index_topk: Optional[int] = None
    index_n_heads: Optional[int] = None
    index_head_dim: Optional[int] = None
    # a rank-`gated_norm_rank` sigmoid gate on the output of a layer's two
    # norms and of the final norm (ops/norms.py `gated_norm`)
    gated_norm: bool = False
    gated_norm_rank: int = 16
    # --- linear layers: the gated delta rule (ops/gated_delta.py) ---
    # one period of the scanned stack: entry j says whether the layers l
    # with l % period == j are LINEAR layers (1: a gated-delta mixer on a
    # per-sequence recurrent state) or `attention_type` layers (0).  The
    # dense prefix is counted apart: `dense_prefix_linear` says its layers
    # are linear ones.  The state's and the conv tail's dtype (float32), the
    # chunk and the kernel's block are the mechanism's, not flags
    linear_layout: Optional[Tuple[int, ...]] = None
    dense_prefix_linear: bool = False
    linear_num_key_heads: Optional[int] = None
    linear_num_value_heads: Optional[int] = None
    linear_key_head_dim: Optional[int] = None
    linear_value_head_dim: Optional[int] = None
    linear_conv_kernel_dim: int = 4
    # --- layers of ONE sublayer (the Nemotron-H family) ---
    # the published `hybrid_override_pattern`, a letter a layer: `M` a
    # Mamba-2 mixer (ops/mamba2.py) on a per-sequence recurrent state, `E`
    # an expert block, `*` an attention block on K/V pages; each layer is
    # x + Mixer(Norm(x)), with no MLP behind a mixer.  Any order: the
    # stack scans its repeated stretches (models/sublayers.py
    # `stretches`).  `num_layers` follows its length.  The state's and the conv
    # tail's dtype (float32), the chunk and the kernel's block are the
    # mechanism's, not flags
    sublayer_pattern: Optional[str] = None
    # two further letters, for a family whose layer is TWO such sublayers
    # (LiquidAI's LFM2: `lfm2_sublayers` writes its `layer_types` out):
    # `C` a gated short convolution (a causal depthwise conv of
    # `short_conv_kernel` taps between two elementwise gates, on a
    # per-sequence tail of its last inputs and NO recurrent state), `D` a
    # dense MLP of `ffn_hidden_size`.  With them `*` may rotate
    # (`position_embedding_type` 'rotary').  The tail's dtype is the
    # activations' (the family's code keeps its conv cache in the model's)
    short_conv_kernel: int = 3
    # the Mamba-2 mixer: `mamba_num_heads` heads of `mamba_head_dim` on a
    # state of `ssm_state_size`, B and C shared by the heads of each of
    # `mamba_n_groups` groups, a causal depthwise conv of `mamba_conv_kernel`
    # taps (with a bias) over x, B and C
    mamba_num_heads: Optional[int] = None
    mamba_head_dim: Optional[int] = None
    mamba_n_groups: int = 1
    ssm_state_size: Optional[int] = None
    mamba_conv_kernel: int = 4
    # --- the GigaChat3.5 block ---
    # `layernorm_type: pre_post`: a norm after each sublayer too, inside
    # the residual branch (leaves `attn_out_norm`, `mlp_out_norm`)
    post_sublayer_norms: bool = False
    # `norm_type: ZeroCenteredGatedNorm`: the RMSNorm's gain is 2 sigmoid(w)
    # (1 at w = 0; the leaf is `gate`, not `scale`: ops/norms.py)
    zero_centered_gated_norm: bool = False
    # gpt-oss's clamp inside a SwiGLU: SiLU(min(gate, limit)) x clip(value,
    # -limit, limit), dense MLP and experts alike
    swiglu_limit: Optional[float] = None
    # --- a LOOPED stack (Ouro's LoopLM: `total_ut_steps`) ---
    # the whole stack runs `loop_steps` times over the SAME weights, the
    # final norm after every pass and feeding the next; a pass's keys and
    # values are its own (a cache holds `cache_layer_slots` rows a token).
    # After every norm an exit gate (a hidden -> 1 linear with a bias, leaf
    # `exit_gate`) gives the pass's exit probability; the logits are the
    # LAST pass's, where the exit distribution's cumulative mass reaches the
    # published `early_exit_threshold` of 1.0 (a checkpoint's config that
    # says less is refused where it enters: weights_conversion/
    # hf_to_native.py).  1: every other model, no loop and no gate
    loop_steps: int = 1

    @property
    def depth(self) -> int:
        """Layers of weights a token passes: the dense prefix and the
        scanned stack."""
        return self.dense_prefix_layers + self.num_layers

    @property
    def cache_layer_slots(self) -> int:
        """The KV pool's layer axis: a slot a layer and PASS, layer l of
        pass t (from 0) at ``t * depth + l`` (``depth`` where nothing
        loops)."""
        return self.loop_steps * self.depth

    @property
    def mla(self) -> bool:
        return self.attention_type == "mla"

    @property
    def retention(self) -> bool:
        return self.attention_type == "retention"

    @property
    def layer_period(self) -> int:
        """Layers in one period of the layer pattern (1 = uniform)."""
        return len(self.sliding_window_layout or self.rope_layout
                   or self.linear_layout or self.sublayer_pattern or (0,))

    @property
    def mamba(self) -> bool:
        """Whether some layer is a Mamba-2 layer."""
        return bool(self.sublayer_pattern and "M" in self.sublayer_pattern)

    @property
    def short_conv(self) -> bool:
        """Whether some sublayer is a gated short convolution."""
        return bool(self.sublayer_pattern and "C" in self.sublayer_pattern)

    @property
    def mamba_conv_channels(self) -> int:
        """Channels of a Mamba-2 layer's convolution: x, B and C."""
        return (self.mamba_num_heads * self.mamba_head_dim
                + 2 * self.mamba_n_groups * self.ssm_state_size)

    @property
    def delta(self) -> bool:
        """Whether some layer is a gated-delta (linear) layer."""
        return bool(self.linear_layout and any(self.linear_layout)) or (
            self.dense_prefix_linear and self.dense_prefix_layers > 0)

    @property
    def norm_gain(self) -> str:
        """How an RMSNorm's leaf becomes its gain (ops/norms.py)."""
        return "sigmoid2" if self.zero_centered_gated_norm else "scale"

    @property
    def experts_held(self) -> int:
        """Experts whose weights this program holds (all, unless told)."""
        return self.moe_experts_held or self.num_experts

    @property
    def latent_cache_width(self) -> int:
        """Values one cached token of an MLA layer holds: the normed
        latent and the rotated shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def _finalize_sublayers(self) -> None:
        """A stack of one-sublayer layers (`sublayer_pattern`)."""
        pattern = self.sublayer_pattern
        assert "-" not in pattern, (
            f"sublayer_pattern {pattern!r}: '-' is the Nemotron-H family's "
            "dense MLP layer, which no published pattern served here holds "
            "and this stack does not build; its letters are M (Mamba-2), "
            "E (experts) and * (attention), and C (short convolution) and "
            "D (dense MLP) for a layer of two sublayers")
        assert pattern and set(pattern) <= set("ME*CD"), (
            f"sublayer_pattern {pattern!r}: a letter a layer, M (Mamba-2), "
            "E (experts), * (attention), C (gated short convolution) or D "
            "(dense MLP)")
        # the pattern IS the depth (a cut gives a shorter string)
        self.num_layers = len(pattern)
        assert ("E" in pattern) == (self.num_experts is not None), (
            "an E layer is an expert block: set num_experts (and give no "
            "experts to a pattern without one)")
        if self.mamba:
            missing = [k for k in ("mamba_num_heads", "mamba_head_dim",
                                   "ssm_state_size")
                       if not getattr(self, k)]
            assert not missing, f"Mamba-2 layers need {missing}"
            assert self.mamba_num_heads % self.mamba_n_groups == 0, (
                "a group of B and C serves a whole number of heads")
        assert not (self.mamba and self.short_conv), (
            "a state class holds Mamba-2 states or conv tails, not both: "
            "no published pattern mixes M and C")
        assert self.short_conv_kernel >= 2, (
            "a short convolution has at least two taps")
        assert (self.attention_type == "mha" and not self.linear_layout
                and not self.sliding_window_layout and not self.rope_layout
                and not self.dense_prefix_layers and not self.parallel_attn
                and not self.bidirectional
                and self.position_embedding_type != "absolute"), (
            "a stack of one-sublayer layers: causal K/V-head attention, "
            "rotated or with no position signal, no second layer pattern, "
            "no dense prefix and no parallel block")

    def finalize(self) -> None:
        assert self.attention_type in ("mha", "mla", "retention"), (
            f"unknown attention_type {self.attention_type!r}")
        assert not self.qk_head_norm or self.attention_type == "mha", (
            "qk_head_norm is the 'mha' path's: latent attention norms its "
            "latents and power retention has head norms of its own")
        if self.diffusion_block_length is not None:
            assert (self.diffusion_block_length > 0
                    and self.mask_token_id is not None
                    and 0 <= self.mask_token_id < (self.vocab_size or 1 << 31)), (
                "generation by diffusion over blocks needs a block length "
                "and mask_token_id, a row of the vocabulary")
            assert (self.attention_type == "mha" and not self.bidirectional
                    and self.sliding_window_size is None
                    and not self.sliding_window_layout
                    and not self.sublayer_pattern and not self.linear_layout), (
                "the block-causal mask is written for one class of K/V "
                "pages: 'mha' layers with no window, no pattern and no "
                "state class")
        assert self.loop_steps >= 1, "loop_steps counts the passes: >= 1"
        if self.loop_steps > 1:
            assert (self.attention_type == "mha" and not self.bidirectional
                    and not self.sliding_window_layout
                    and not self.sublayer_pattern and not self.linear_layout
                    and not self.dense_prefix_layers
                    and self.diffusion_block_length is None), (
                "a looped stack is written for one class of K/V pages over "
                "ONE scanned stack: 'mha' layers, no pattern, no state "
                "class, no dense prefix")
        if self.retention:
            assert (self.sliding_window_size is None
                    and not self.sliding_window_layout
                    and not self.bidirectional), (
                "power retention is causal and has no window or pattern: "
                "its state is the whole past")
        if self.mla:
            missing = [k for k in ("q_lora_rank", "kv_lora_rank",
                                   "qk_nope_head_dim", "qk_rope_head_dim",
                                   "v_head_dim") if not getattr(self, k)]
            assert not missing, f"attention_type 'mla' needs {missing}"
            assert self.qk_rope_head_dim % 2 == 0, (
                "qk_rope_head_dim must be even (RoPE rotates pairs)")
            assert self.sliding_window_size is None, (
                "latent attention has no sliding window")
            # the rotary table is made for this width; K/V heads do not
            # exist (one latent row serves every query head)
            self.kv_channels = self.qk_rope_head_dim
            self.num_attention_heads_kv = 1
        for name in ("sliding_window_layout", "rope_layout", "linear_layout"):
            layout = getattr(self, name)
            if layout is not None:
                layout = tuple(int(v) for v in layout)
                assert layout and set(layout) <= {0, 1}, (
                    f"{name} is one period of 0/1 entries, got {layout}")
                setattr(self, name, layout)
        if self.sliding_window_layout or self.rope_layout:
            assert (self.sliding_window_layout and self.rope_layout
                    and len(self.sliding_window_layout)
                    == len(self.rope_layout)), (
                "a layer pattern gives sliding_window_layout and "
                "rope_layout, one period each, of the same length")
            assert self.num_layers % self.layer_period == 0, (
                f"num_layers {self.num_layers} is not a whole number of "
                f"periods of the layer pattern ({self.layer_period} layers)")
            assert not any(self.sliding_window_layout) or (
                self.sliding_window_size), (
                "sliding_window_layout marks window layers: set "
                "sliding_window_size")
            assert not any(self.rope_layout) or (
                self.position_embedding_type == "rotary"), (
                "rope_layout marks rotating layers: position_embedding_type "
                "must be 'rotary'")
            assert not self.mla and not self.bidirectional, (
                "a layer pattern is written for causal K/V-head attention")
        if self.delta:
            missing = [k for k in ("linear_num_key_heads",
                                   "linear_num_value_heads",
                                   "linear_key_head_dim",
                                   "linear_value_head_dim")
                       if not getattr(self, k)]
            assert not missing, f"linear layers need {missing}"
            assert self.linear_num_value_heads % self.linear_num_key_heads \
                == 0, "a key head serves a whole number of value heads"
            assert (self.mla and not self.sliding_window_layout
                    and not self.rope_layout), (
                "linear layers stand beside latent attention "
                "(attention_type 'mla') in a stack with no window pattern")
            assert self.num_layers % self.layer_period == 0, (
                f"num_layers {self.num_layers} is not a whole number of "
                f"periods of linear_layout ({self.layer_period} layers)")
        if self.sublayer_pattern is not None:
            self._finalize_sublayers()
        # periods of the scanned stack: what a hybrid's mixer stacks are
        # sized by (models/transformer.py init_mixers)
        self.scanned_periods = self.num_layers // self.layer_period
        if self.kv_channels is None:
            assert self.hidden_size % self.num_attention_heads == 0, (
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_attention_heads {self.num_attention_heads}"
            )
            self.kv_channels = self.hidden_size // self.num_attention_heads
        if self.num_attention_heads_kv is None:
            self.num_attention_heads_kv = self.num_attention_heads
        if self.ffn_hidden_size is None:
            if self.glu_activation is not None:
                # Llama convention: 2/3 * 4h rounded up to a multiple of 256.
                ffn = int(4 * self.hidden_size * 2 / 3)
                self.ffn_hidden_size = 256 * ((ffn + 255) // 256)
            else:
                self.ffn_hidden_size = 4 * self.hidden_size


@dataclass
class ParallelConfig:
    """Device-mesh layout (reference TP/PP/DP world carving, parallel_state.py:51-205).

    TPU-native: one JAX process sees all devices; parallelism is expressed as a
    ``jax.sharding.Mesh`` over axes (dp, pp, tp) instead of NCCL subgroups.
    """

    tensor_model_parallel_size: int = 1
    pipeline_model_parallel_size: int = 1
    # data parallel size; None = infer from device count / (tp*pp)
    data_parallel_size: Optional[int] = None
    # Megatron-style sequence parallelism: shard seq dim over tp in LN/dropout
    # regions (activation memory / TP).
    sequence_parallel: bool = False
    # Fine-grained compute/collective overlap (parallel/overlap.py, ROADMAP
    # item 3): 'ring' decomposes the row-parallel all-reduce/reduce-scatter
    # (and the column-parallel all-gather under SP) into a chunked
    # collective matmul — tp GEMM chunks pipelined against ppermute hops —
    # inside a full-manual shard_map region.  'off' (default) keeps
    # today's XLA-inserted collectives byte for byte.  Silently inert at
    # tp == 1 and on pp/cp layouts (those own their manual regions).
    tp_overlap: str = "off"
    # int8-quantize the ring's wire chunks (per-chunk f32 scales, compute-
    # dtype accumulate; straight-through backward) — the forward-collective
    # member of the --quantized_* family, closing the PR 13 follow-on.
    # Only meaningful with --tp_overlap ring; error bound documented in
    # docs/guide/quantization.md.
    quantized_tp_collectives: bool = False
    # Vocab-parallel head ring (parallel/overlap.py:vocab_parallel, ISSUE
    # 20): decompose the serving head GEMM's logits all-gather into an
    # all-gather matmul ring — each rank GEMMs one column sub-chunk of
    # its vocab shard while previously computed sub-chunks ppermute
    # around the ring, so the wire hides behind the MXU work that decode
    # pays EVERY tick.  Runs outside the pp stage region, so it composes
    # with pipeline-parallel serving.  Silently inert at tp == 1.
    vocab_ring: bool = False
    # declares that cp batches follow the STANDARD zigzag layout
    # (parallel/ring.py:apply_zigzag) — lets causal ring attention use the
    # striped Pallas kernels instead of the jnp fallback; set it alongside
    # the data-side apply_zigzag transform
    cp_zigzag: bool = False
    # Context parallelism (ring attention) size — extension beyond reference.
    context_parallel_size: int = 1
    # Expert parallelism for MoE — extension beyond reference.
    expert_parallel_size: int = 1
    num_micro_batches: Optional[int] = None  # derived from batch sizes
    virtual_pipeline_model_parallel_size: Optional[int] = None
    # 'gpipe' (all-fwd-then-all-bwd, differentiable scan) or '1f1b'
    pipeline_schedule: str = "1f1b"
    # 1F1B lockstep-SPMD head: shard the LM-head vocab over the pp axis so
    # every stage computes a USEFUL 1/pp of the head each tick instead of a
    # masked-out full head (parallel/pipeline.py pp-vocab head). Applies to
    # the default GPT head under the 1F1B schedules when the padded vocab
    # divides pp; custom family hooks keep the replicated head.
    pp_vocab_parallel_head: bool = True
    # activation recompute: None | 'full' | 'selective'
    recompute_granularity: Optional[str] = "selective"
    # shard stacked-layer scan carries over tp when sequence_parallel
    distribute_saved_activations: bool = False

    def finalize(self, n_devices: Optional[int] = None) -> None:
        assert self.tp_overlap in ("off", "ring"), (
            f"--tp_overlap must be 'off' or 'ring', got {self.tp_overlap!r}")
        if self.data_parallel_size is None and n_devices is not None:
            mp = (
                self.tensor_model_parallel_size
                * self.pipeline_model_parallel_size
                * self.context_parallel_size
            )
            assert n_devices % mp == 0, (
                f"device count {n_devices} not divisible by model-parallel size {mp}"
            )
            self.data_parallel_size = n_devices // mp


@dataclass
class TrainingConfig:
    """Training driver knobs (reference ``_add_training_args``)."""

    micro_batch_size: int = 1
    global_batch_size: Optional[int] = None
    rampup_batch_size: Optional[Tuple[int, int, int]] = None  # start, incr, samples
    train_iters: Optional[int] = None
    train_samples: Optional[int] = None
    eval_iters: int = 10
    eval_interval: int = 1000
    exit_interval: Optional[int] = None
    exit_duration_in_mins: Optional[int] = None
    exit_signal_handler: bool = False
    seed: int = 1234
    data_parallel_random_init: bool = False
    # numerics
    params_dtype: str = "bfloat16"  # 'float32' | 'bfloat16' | 'float16'
    fp32_residual_connection: bool = False
    accumulate_allreduce_grads_in_fp32: bool = True
    # loss scaling (fp16 only)
    loss_scale: Optional[float] = None  # None => dynamic
    initial_loss_scale: float = 2.0 ** 32
    min_loss_scale: float = 1.0
    loss_scale_window: int = 1000
    hysteresis: int = 2
    # perf switches
    use_flash_attn: bool = True
    scan_layers: bool = True  # lax.scan over stacked layers (compile time)
    remat_policy: str = "save_dots_except_logits"
    skip_train: bool = False
    skip_iters: List[int] = field(default_factory=list)
    # --- host/device overlap (training.py async loop) ---
    # How many dispatched-but-unfetched steps may be in flight before the
    # host blocks on the oldest (bounds device memory for queued programs
    # and error latency). 0 = the fully synchronous legacy loop; metrics
    # are fetched in one batched device_get at log_interval boundaries
    # either way.
    async_dispatch_depth: int = 2
    # Background data pipeline stage (data/prefetch.py): batches are pulled
    # from the loader, collated (incl. ramp-up chunk concatenation) and
    # placed on device up to this many steps ahead of the consuming step.
    # 0 = pull + place inline on the critical path (legacy behavior).
    prefetch_depth: int = 2
    # EQuARX-style int8 chunk-quantized DP gradient all-reduce
    # (parallel/quantized.py, ISSUE 13): explicit
    # quantize -> reduce-scatter -> dequant-accumulate -> all-gather sync
    # replacing the implicit bf16 all-reduce on dp-pure meshes (dp > 1,
    # tp == pp == cp == ep == 1).  OFF by default — the bf16 sync path is
    # untouched; the loss-delta gate vs bf16 sync lives in
    # tests/test_kv_quant.py and docs/guide/quantization.md documents the
    # accepted delta and when NOT to enable this.
    quantized_grad_allreduce: bool = False


@dataclass
class OptimizerConfig:
    """Reference ``_add_learning_rate_args`` + ``_add_regularization_args``."""

    optimizer: str = "adam"  # 'adam' | 'sgd'
    lr: float = 3e-4
    min_lr: float = 0.0
    lr_decay_style: str = "cosine"  # constant|linear|cosine|inverse-square-root
    lr_decay_iters: Optional[int] = None
    lr_decay_samples: Optional[int] = None
    lr_warmup_iters: int = 0
    lr_warmup_samples: int = 0
    lr_warmup_fraction: Optional[float] = None
    override_opt_param_scheduler: bool = False
    use_checkpoint_opt_param_scheduler: bool = False
    weight_decay: float = 0.01
    start_weight_decay: Optional[float] = None
    end_weight_decay: Optional[float] = None
    weight_decay_incr_style: str = "constant"  # constant|linear|cosine
    clip_grad: float = 1.0
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    sgd_momentum: float = 0.9
    # memory-bounded per-layer-slice Adam update (same math as the optax
    # chain; the TPU analog of apex multi-tensor FusedAdam's bounded
    # working set — see optimizer.scanned_adam)
    scanned_update: bool = True
    # ZeRO-1: shard fp32 optimizer state over dp (reference distrib_optimizer.py)
    use_distributed_optimizer: bool = False


@dataclass
class DataConfig:
    """Reference ``_add_data_args``."""

    data_path: List[str] = field(default_factory=list)  # weight path pairs ok
    split: str = "969, 30, 1"
    train_data_path: List[str] = field(default_factory=list)
    valid_data_path: List[str] = field(default_factory=list)
    test_data_path: List[str] = field(default_factory=list)
    seq_length: int = 2048
    decoder_seq_length: Optional[int] = None  # T5 decoder length
    num_workers: int = 2
    tokenizer_type: str = "SentencePieceTokenizer"
    vocab_file: Optional[str] = None
    merge_file: Optional[str] = None
    tokenizer_model: Optional[str] = None  # sentencepiece model path
    vocab_extra_ids: int = 0
    vocab_extra_ids_list: Optional[str] = None
    no_new_tokens: bool = False
    data_impl: str = "mmap"  # 'mmap' | 'infer'
    mmap_warmup: bool = False
    dataloader_type: str = "single"  # 'single' | 'cyclic'
    reset_position_ids: bool = False
    reset_attention_mask: bool = False
    eod_mask_loss: bool = False
    # instruction tuning
    data_type: str = "gpt"  # 'gpt' | 'instruction'
    variable_seq_lengths: bool = False
    scalar_loss_mask: float = 0.0
    loss_role: str = "assistant"  # 'assistant' | 'user' | 'all'


@dataclass
class CheckpointConfig:
    """Reference ``_add_checkpointing_args`` + checkpointing.py behavior."""

    save: Optional[str] = None
    save_interval: Optional[int] = None
    load: Optional[str] = None
    no_load_optim: bool = False
    no_load_rng: bool = False
    no_save_optim: bool = False
    no_save_rng: bool = False
    finetune: bool = False
    use_checkpoint_args: bool = False
    exit_on_missing_checkpoint: bool = False
    async_save: bool = False
    keep_last_n_checkpoints: Optional[int] = None
    # Verify the manifest (per-file size + sha256) of the checkpoint being
    # loaded; a corrupt one is quarantined to *.corrupt and load falls back
    # to the newest checkpoint that verifies (resilience/integrity.py).
    verify_on_load: bool = True


@dataclass
class ResilienceConfig:
    """Fault tolerance (megatron_llm_tpu/resilience/): hang watchdog,
    supervised restarts, goodput accounting — docs/guide/resilience.md."""

    # step-deadline watchdog (resilience/watchdog.py): on a silent hang,
    # dump all thread stacks, attempt a bounded emergency save, and exit
    # with code 43 so the supervisor restarts the run
    watchdog: bool = False
    # deadline = watchdog_multiplier x EMA(step time), floored
    watchdog_multiplier: float = 10.0
    watchdog_min_deadline: float = 60.0
    # the first armed window covers JIT compilation — generous by design
    watchdog_first_deadline: float = 1800.0
    # how long the expiry path waits for the emergency host-snapshot save
    # before exiting anyway (the snapshot may hang on a wedged device)
    emergency_save_timeout: float = 120.0
    # supervisor (tools/run_resilient.py) restart budget + backoff
    max_restarts: int = 10
    restart_backoff: float = 2.0
    restart_backoff_max: float = 300.0
    restart_reset_after: float = 3600.0


@dataclass
class LoggingConfig:
    """Reference ``_add_logging_args`` + wandb shim."""

    log_interval: int = 100
    timing_log_level: int = 0
    timing_log_option: str = "minmax"  # max|minmax|all
    # jax.profiler xplane tracing (SURVEY §5: the TPU analog of the
    # reference's named-span timer discipline, megatron/timers.py). Traces
    # iterations [profile_step_start, profile_step_end) into profile_dir
    # (viewable with tensorboard / xprof).
    profile: bool = False
    profile_step_start: int = 10
    profile_step_end: int = 12
    profile_dir: Optional[str] = None  # default: <tensorboard_dir or .>/profile
    # --- observability subsystem (megatron_llm_tpu/observability/,
    # docs/guide/observability.md) ---
    # host-side span tracing of the async loop's phases (data-wait,
    # dispatch, metric-drain, ckpt-flush): Chrome-trace/Perfetto JSON
    # windows written here; None disables tracing entirely
    trace_dir: Optional[str] = None
    # dump one trace file per this many steps (0 = only a final dump)
    trace_steps: int = 50
    # span ring-buffer capacity (oldest events drop beyond it)
    trace_buffer_events: int = 65536
    # serve Prometheus /metrics (+ /profile on-demand capture trigger)
    # on this port; 0 binds an ephemeral port; None disables
    metrics_port: Optional[int] = None
    # bound on on-demand jax.profiler windows per process (SIGUSR2 or
    # GET /profile?steps=N; output under <profile_dir>/ondemand/)
    profile_max_captures: int = 8
    tensorboard_dir: Optional[str] = None
    tensorboard_log_interval: int = 1
    tensorboard_queue_size: int = 1000
    log_timers_to_tensorboard: bool = False
    log_learning_rate_to_tensorboard: bool = True
    log_loss_scale_to_tensorboard: bool = True
    log_memory_to_tensorboard: bool = False
    log_params_norm: bool = False
    log_num_zeros_in_grad: bool = False
    wandb_logger: bool = False
    wandb_project: str = ""
    wandb_entity: str = ""
    wandb_name: Optional[str] = None
    wandb_id: Optional[str] = None
    wandb_resume: bool = False
    wandb_api_key: Optional[str] = None
    metrics: List[str] = field(default_factory=list)


def check_prefill_chunk(prefill_chunk: int, page_size: int) -> None:
    """The one check of ``--prefill_chunk``, where the value enters
    (:meth:`Config.finalize` and the engine's constructor): prompt rows
    join the tick in chunks on a grid of whole pages, so there is no
    unchunked mode."""
    if (not isinstance(prefill_chunk, int) or prefill_chunk <= 0
            or prefill_chunk % page_size):
        raise ValueError(
            f"prefill_chunk must be a positive whole number of pages "
            f"(page_size {page_size}), got {prefill_chunk!r}")


@dataclass
class InferenceConfig:
    """Text-generation server/sampling defaults."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0
    max_tokens_to_oom: int = 12000
    port: int = 5000
    # weight-only int8 for decode (ops/quant.py): transformer-layer linears
    # stored int8 in HBM, dequantized inside the GEMM — inference only
    int8_weights: bool = False
    # continuous-batching engine (generation/engine.py): decode slots per
    # tick, KV page granularity, pool size (None = slots * pages_per_seq
    # + 1 null page), and the per-sequence length cap (None = seq_length)
    max_batch_slots: int = 8
    page_size: int = 16
    kv_pool_pages: Optional[int] = None
    # pages of the WINDOW page class of a patterned model's pool (the
    # layers that see `sliding_window_size` keys; kv_pool_pages then sizes
    # the full-attention layers' class).  None = what every slot at its
    # cap needs
    kv_window_pool_pages: Optional[int] = None
    engine_max_seq: Optional[int] = None
    # quantized paged KV cache (ISSUE 13, ops/kv_quant.py): --kv_dtype
    # bf16|int8|fp8 picks the pool storage.  bf16 (default) is today's
    # engine byte for byte; int8/fp8 store pages with per-page, per-head
    # symmetric absmax scales for ~2x concurrent slots / prefix-cache
    # capacity / speculative headroom at fixed pool bytes — target AND
    # draft caches together (docs/guide/quantization.md "KV cache")
    kv_dtype: str = "bf16"
    # prefix cache + chunked prefill (ISSUE 5): shared refcounted prompt
    # pages with copy-on-write, prefill split into --prefill_chunk-token
    # chunks packed into the decode tick (a positive whole number of
    # pages, check_prefill_chunk); --page_watermark is extra
    # free+evictable slack admission keeps beyond the worst-case
    # commitment of in-flight requests;
    # --max_queued_requests bounds the submit queue (overflow -> 503 with
    # Retry-After on the server, 0 = unbounded)
    prefix_cache: bool = True
    prefill_chunk: int = 64
    page_watermark: int = 0
    max_queued_requests: int = 256
    # scheduling control plane (generation/scheduling/, ISSUE 7):
    # --sched_policy fcfs|priority|slo picks the admission/preemption
    # policy (fcfs = the pre-policy engine, bitwise); --sched_aging_s is
    # the priority policy's anti-starvation horizon (a queued request
    # climbs one class per aging_s seconds); --sched_quota bounds queue
    # depth per priority class ("0:64,2:16", overflow -> 503);
    # --sched_preemption gates preemption-by-page-release
    sched_policy: str = "fcfs"
    sched_aging_s: float = 5.0
    sched_quota: Optional[str] = None
    sched_preemption: bool = True
    # speculative decoding (generation/speculative/, ISSUE 9): --spec_k is
    # the speculation-depth cap (0 = off, today's one-token tick);
    # --spec_draft names the draft model — "family:key=val,..." builds a
    # random-init config (smoke), "...@/ckpt/dir" loads params from a
    # checkpoint; --spec_adaptive shrinks the per-slot depth on a low
    # acceptance EMA.  Greedy speculative decode is bitwise-identical to
    # spec_k=0; sampled decode matches the target distribution exactly
    # (docs/guide/serving.md "Speculative decoding")
    spec_k: int = 0
    spec_draft: Optional[str] = None
    spec_adaptive: bool = True
    # ragged paged attention (generation/ragged.py, ISSUE 11): every
    # tick's decode slots, speculative-verify blocks and prefill-chunk
    # rows are ONE compiled launch over a ragged row batch.
    # --prefill_budget is the prompt TOKENS a tick may prefill: the
    # compiled prefill-row capacity of the tick and the cap on the
    # SchedulerPolicy's token-level prefill_budget.
    # 0 = --max_batch_slots rounded up to whole chunks, which for an
    # engine of at most one chunk of slots is one chunk a tick.
    prefill_budget: int = 0
    # per-request flight recorder (observability/flight.py, ISSUE 12):
    # --flight_records bounds how many retired request records the
    # engine keeps for /debug/requests and the watchdog's emergency dump
    # (0 disables recording entirely); --flight_events bounds each
    # record's event log (oldest events drop, with an honest count)
    flight_records: int = 256
    flight_events: int = 64


@dataclass
class RetrieverConfig:
    """Biencoder/ICT/REALM retrieval (reference ``_add_biencoder_args``:
    biencoder_model.py, pretrain_ict.py, indexer.py, tasks/orqa)."""

    biencoder_projection_dim: int = 0
    biencoder_shared_query_context_model: bool = False
    retriever_score_scaling: bool = False
    retriever_report_topk_accuracies: List[int] = field(
        default_factory=lambda: [1, 5, 20]
    )
    retriever_seq_length: int = 256
    titles_data_path: Optional[str] = None
    query_in_block_prob: float = 0.1
    use_one_sent_docs: bool = False
    bert_load: Optional[str] = None     # init towers from a BERT checkpoint
    embedding_path: Optional[str] = None  # block-embedding store
    indexer_batch_size: int = 128
    indexer_log_interval: int = 1000


@dataclass
class Config:
    """Aggregate configuration (analog of the reference's global ``args``)."""

    model: ModelConfig = field(default_factory=ModelConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    data: DataConfig = field(default_factory=DataConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    inference: InferenceConfig = field(default_factory=InferenceConfig)
    retriever: RetrieverConfig = field(default_factory=RetrieverConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    # architecture family: 'gpt' | 'llama' | 'llama2' | 'codellama' | 'falcon' | 'mistral'
    model_name: str = "llama2"

    def finalize(self, n_devices: Optional[int] = None) -> "Config":
        """Derive defaults and enforce cross-flag invariants.

        Mirrors the reference's ``validate_args`` (arguments.py:53-350).
        """
        self.model.finalize()
        self.parallel.finalize(n_devices)
        check_prefill_chunk(self.inference.prefill_chunk,
                            self.inference.page_size)
        t = self.training
        if t.global_batch_size is None:
            dp = self.parallel.data_parallel_size or 1
            t.global_batch_size = t.micro_batch_size * dp
        if self.parallel.num_micro_batches is None:
            dp = self.parallel.data_parallel_size or 1
            denom = t.micro_batch_size * dp
            assert t.global_batch_size % denom == 0, (
                f"global_batch_size {t.global_batch_size} not divisible by "
                f"micro_batch_size*dp {denom}"
            )
            self.parallel.num_micro_batches = t.global_batch_size // denom
        # sequence parallelism requires TP>1 to do anything
        if self.parallel.tensor_model_parallel_size == 1:
            self.parallel.sequence_parallel = False
        # bf16 training accumulates grads in fp32 by DEFAULT (reference
        # validate_args:139-148 forces it; for bfloat16 an explicit False
        # is honored — halving the accumulator is what fits Llama-7B TP=8
        # on 16-GiB v5e chips, tools/aot_scale_check.py). float16 keeps
        # the force: its grads carry the dynamic loss scale, and summing
        # scaled fp16 microbatch grads overflows the accumulator at
        # scales the backoff can never escape.
        if t.params_dtype == "float16":
            t.accumulate_allreduce_grads_in_fp32 = True
        if self.model.num_attention_heads_kv is not None:
            assert (
                self.model.num_attention_heads % self.model.num_attention_heads_kv == 0
            ), "num_attention_heads must be divisible by num_attention_heads_kv"
        if self.parallel.pipeline_model_parallel_size > 1:
            assert (
                self.model.num_layers % self.parallel.pipeline_model_parallel_size == 0
            ), "num_layers must be divisible by pipeline_model_parallel_size"
        if self.model.num_experts is not None:
            ep = self.parallel.expert_parallel_size
            assert self.model.num_experts % ep == 0, (
                f"num_experts {self.model.num_experts} not divisible by "
                f"expert_parallel_size {ep}"
            )
            if self.parallel.pipeline_model_parallel_size > 1:
                # All schedules carry the router aux-loss gradient: GPipe
                # through the tick-scan transpose, the 1F1B schedules by
                # seeding the stage vjp's aux output with the loss scale at
                # each stage's own backward tick (the aux term is
                # stage-local, so no cross-stage aux gradient exists —
                # parallel/pipeline.py:_1f1b_setup).
                assert self.parallel.context_parallel_size == 1, (
                    "MoE with pipeline parallelism requires "
                    "context_parallel_size == 1"
                )
            assert self.model.moe_router_topk <= self.model.num_experts
            assert self.model.moe_router_type in ("topk", "expert_choice"), (
                f"unknown moe_router_type {self.model.moe_router_type!r}"
            )
            assert self.model.moe_score_func in ("softmax", "sigmoid"), (
                f"unknown moe_score_func {self.model.moe_score_func!r}"
            )
            assert self.model.moe_shared_combination in ("sum", "average"), (
                "unknown moe_shared_combination "
                f"{self.model.moe_shared_combination!r}")
            assert self.model.moe_router_input in (
                "post_attention", "layer_input"), (
                f"unknown moe_router_input {self.model.moe_router_input!r}")
            assert not (self.model.moe_router_input == "layer_input"
                        and self.model.parallel_attn), (
                "moe_router_input 'layer_input' is for the sequential "
                "block: a parallel block's experts read the layer input "
                "already")
            if self.model.moe_experts_held is not None:
                m = self.model
                assert (0 < m.moe_experts_held and 0 <= m.moe_first_held_expert
                        and m.moe_first_held_expert + m.moe_experts_held
                        <= m.num_experts), (
                    f"held experts {m.moe_first_held_expert} .. "
                    f"{m.moe_first_held_expert + m.moe_experts_held} lie "
                    f"outside the router's {m.num_experts}")
                assert (m.moe_router_type == "topk" and ep == 1), (
                    "moe_experts_held is one chip's share of an "
                    "expert-parallel layer on the dropless dispatch: "
                    "expert_parallel_size 1 and moe_router_type 'topk'")
            if self.model.moe_router_type == "expert_choice":
                # EC routing compares tokens across positions within a
                # routing group, leaking future-token information into the
                # selection — unsound for causal-LM TRAINING (the only
                # families MoE attaches to here). Loud warning rather than
                # an error: fine for encoders-to-come and research runs.
                import warnings

                warnings.warn(
                    "moe_router_type='expert_choice' leaks future-token "
                    "information within each routing group; a causal LM "
                    "trained with it can exploit the leak. Use the default "
                    "'topk' token-choice routing for production causal-LM "
                    "training (models/moe.py:route_expert_choice).",
                    stacklevel=2,
                )
            if self.parallel.data_parallel_size is not None:
                # auto-inferred dp (None) is validated later by build_mesh
                assert self.parallel.data_parallel_size % ep == 0, (
                    f"data_parallel_size {self.parallel.data_parallel_size} "
                    f"not divisible by expert_parallel_size {ep} (ep is "
                    f"carved out of dp)"
                )
            assert self.model_name in (
                "gpt", "llama", "llama2", "codellama", "llama3", "falcon",
                "mistral", "mixtral", "joyai", "smallthinker", "commanda",
                "gigachat35", "nemotron_h", "lfm2", "sdar_moe", "axk2",
            ), (
                "MoE is supported for the GPT/Llama-family decoder models "
                "only — the BERT/T5/biencoder loss paths do not consume the "
                "router aux losses"
            )
            assert not (self.inference.int8_weights
                        and self.model.glu_activation is None), (
                "int8_weights with experts that are not gated is not "
                "written: such an expert's fc1 lies [ffn, h] (models/moe.py) "
                "and ops/quant.py takes a kernel's second-last axis for its "
                "input")
        else:
            assert self.parallel.expert_parallel_size == 1, (
                "expert_parallel_size > 1 requires num_experts (MoE)"
            )
            assert not self.model.moe_shared_experts, (
                "moe_shared_experts requires num_experts (MoE)")
        if self.model.dense_prefix_layers:
            assert self.model.num_experts is not None, (
                "dense_prefix_layers precede expert layers: set num_experts")
            assert self.parallel.pipeline_model_parallel_size == 1, (
                "a dense prefix is a stack of its own; pipeline stages "
                "cut one uniform stack")
        period = self.model.layer_period
        if period > 1:
            pp = self.parallel.pipeline_model_parallel_size
            vpp = self.parallel.virtual_pipeline_model_parallel_size or 1
            assert self.model.num_layers % (pp * vpp * period) == 0, (
                f"a pipeline stage holds whole periods of the layer "
                f"pattern: num_layers {self.model.num_layers} over "
                f"{pp * vpp} stages is not a multiple of {period}")
            assert self.parallel.context_parallel_size == 1, (
                "a layer pattern with context parallelism is not written: "
                "the ring has one window for every layer")
            assert (not self.model.dense_prefix_layers
                    or self.model.linear_layout), (
                "a window pattern counts its periods from layer 0: no "
                "dense prefix (linear_layout counts them from the scanned "
                "stack's first layer)")
        return self


# ---------------------------------------------------------------------------
# Architecture presets (reference model/llama_model.py, falcon_model.py,
# mistral_model.py flag bundles)
# ---------------------------------------------------------------------------

ARCH_DEFAULTS = {
    "gpt": dict(
        use_rms_norm=False,
        glu_activation=None,
        use_bias=True,
        tie_embed_logits=True,
        position_embedding_type="absolute",
    ),
    # llama_model.py:22-30: rotary + swiglu + RMSNorm + no bias + untied embeddings
    "llama": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
    ),
    "llama2": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
    ),
    # CodeLlama: llama2 + rope_theta=1e6 (arguments.py:467-468)
    "codellama": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        rope_theta=1_000_000.0,
    ),
    # Llama-3 (beyond-reference): llama2 block + GQA everywhere,
    # rope_theta 5e5, 128k vocab; 3.1+ checkpoints add the "llama3" rope
    # frequency remap via rope_scaling_type
    "llama3": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        rope_theta=500_000.0,
    ),
    # falcon_model.py:18-29: MQA/GQA + parallel attention (+ parallel layernorm for 40B)
    "falcon": dict(
        use_rms_norm=False,
        glu_activation=None,
        use_bias=False,
        tie_embed_logits=True,
        position_embedding_type="rotary",
        parallel_attn=True,
    ),
    # bert_model.py: bidirectional, learned positions, tokentypes, binary head
    "bert": dict(
        use_rms_norm=False,
        glu_activation=None,
        use_bias=True,
        tie_embed_logits=True,
        position_embedding_type="absolute",
        bidirectional=True,
        num_tokentypes=2,
        bert_binary_head=True,
    ),
    # t5_model.py: encoder-decoder, learned positions, tied embeddings
    "t5": dict(
        use_rms_norm=False,
        glu_activation=None,
        use_bias=True,
        tie_embed_logits=True,
        position_embedding_type="absolute",
    ),
    # mistral_model.py:30: llama2 bundle + sliding window 4096
    "mistral": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        sliding_window_size=4096,
    ),
    # Mixtral: mistral block with a top-2 8-expert MoE FFN (beyond-reference —
    # the reference has no MoE family; see models/moe.py)
    "mixtral": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        num_experts=8,
        moe_router_topk=2,
        rope_theta=1_000_000.0,
    ),
    # JoyAI-LLM-Flash (beyond-reference; the DeepSeek-V3 block): latent
    # attention, one leading dense layer, then layers of 256 routed experts
    # top-8 by a bias-corrected sigmoid router plus one shared expert
    "joyai": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=32_000_000.0,
        attention_type="mla",
        moe_score_func="sigmoid",
        moe_selection_bias=True,
        moe_normalize_gates=True,
        moe_routed_scaling_factor=2.5,
        moe_shared_experts=1,
        dense_prefix_layers=1,
    ),
    # SmallThinker (beyond-reference): RMSNorm blocks of GQA attention and
    # ReGLU experts with no shared expert and no dense layer; one layer in
    # four attends to everything and carries no position signal, the other
    # three rotate and see 4096 keys; the router reads the layer's normed
    # input (placed before attention), softmax over the chosen six
    "smallthinker": dict(
        use_rms_norm=True,
        glu_activation="reglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_500_000.0,
        sliding_window_size=4096,
        sliding_window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1),
        moe_router_input="layer_input",
        moe_score_func="softmax",
        moe_normalize_gates=True,
    ),
    # Command A+ (beyond-reference; Cohere's cohere2_moe): a PARALLEL block
    # (attention and experts both read one bias-free LayerNorm of the
    # layer's input) of GQA attention and SwiGLU experts; three layers in
    # four rotate (interleaved pairs) and see 4096 keys, the fourth sees
    # all and carries no position signal; a sigmoid router with no
    # selection bias whose chosen weights are normalised; four shared
    # experts whose AVERAGE joins the routed sum; tied head
    "commanda": dict(
        use_rms_norm=False,
        norm_bias=False,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=True,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        rope_theta=50_000.0,
        parallel_attn=True,
        sliding_window_size=4096,
        sliding_window_layout=(1, 1, 1, 0),
        rope_layout=(1, 1, 1, 0),
        moe_score_func="sigmoid",
        moe_selection_bias=False,
        moe_normalize_gates=True,
        moe_routed_scaling_factor=1.0,
        moe_shared_experts=4,
        moe_shared_combination="average",
    ),
    # Brumby (beyond-reference; manifestai's `brumby`): the Qwen3 block
    # (RMSNorm, SwiGLU, GQA widths, per-head RMSNorm on q and k, RoPE at
    # theta 1e6, untied head) whose every layer replaces softmax attention
    # by gated degree-2 power retention (ops/retention.py)
    "brumby": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_000_000.0,
        attention_type="retention",
    ),
    # GigaChat3.5 (beyond-reference; ai-sage's `gigachat3_5`): a hybrid
    # stack, three gated-delta (linear) layers to one latent-attention
    # layer, under the DeepSeek-V3 expert layer.  The scanned stack starts
    # at the model's first attention layer (its layer 3), so a period reads
    # latent, linear, linear, linear; the dense prefix (the published 3
    # layers) is linear layers with a dense SwiGLU.  pre_post norms whose
    # gain is 2 sigmoid(w), a sigmoid gate on the attention's output, the
    # clamp of swiglu_limit, YaRN (factor 8 over 32,768) on the 64 rope dims
    "gigachat35": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=100_000.0,
        rope_scaling_type="yarn",
        rope_scaling_factor=8.0,
        rope_yarn_beta_fast=32.0,
        rope_yarn_beta_slow=1.0,
        rope_yarn_original_max_position=32768,
        rope_yarn_mscale_all_dim=1.0,
        attention_type="mla",
        attention_output_gate=True,
        linear_layout=(0, 1, 1, 1),
        dense_prefix_linear=True,
        dense_prefix_layers=3,
        post_sublayer_norms=True,
        zero_centered_gated_norm=True,
        swiglu_limit=10.0,
        moe_score_func="sigmoid",
        moe_selection_bias=True,
        moe_normalize_gates=True,
        moe_routed_scaling_factor=2.5,
        moe_shared_experts=1,
    ),
    # NVIDIA Nemotron-H / Nemotron 3 (`nemotron_h`): layers of ONE sublayer
    # under one RMSNorm, in the order the published string gives: Mamba-2
    # mixers, non-gated relu^2 experts behind a sigmoid + bias router with
    # one shared expert, GQA attention with no position signal; untied head
    "nemotron_h": dict(
        use_rms_norm=True,
        glu_activation=None,
        activation="squared_relu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="none",
        layernorm_epsilon=1e-5,
        moe_score_func="sigmoid",
        moe_selection_bias=True,
        moe_normalize_gates=True,
    ),
    # LiquidAI LFM2 (`lfm2_moe`): a layer is a mixer then a feed-forward, each
    # behind its own RMSNorm, written as TWO letters of `sublayer_pattern`
    # (`lfm2_sublayers`): gated short convolutions and QK-normed rotated GQA;
    # dense SwiGLU in the first layers, SwiGLU experts behind a sigmoid +
    # bias router after them; head tied to the embedding
    "lfm2": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=True,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-5,
        rope_theta=1_000_000.0,
        qk_head_norm=True,
        moe_score_func="sigmoid",
        moe_selection_bias=True,
        moe_normalize_gates=True,
        moe_gate_eps=1e-6,
    ),
    # JetLM SDAR (`sdar_moe`): the Qwen3-MoE block (QK-normed rotated GQA,
    # SwiGLU experts behind a softmax router normalised over the chosen, no
    # shared expert, untied head) under a BLOCK-causal mask, generated from
    # by diffusion over blocks of `diffusion_block_length`
    "sdar_moe": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_000_000.0,
        qk_head_norm=True,
        moe_score_func="softmax",
        moe_normalize_gates=True,
        diffusion_block_length=4,
    ),
    # ByteDance Ouro (`ouro`, a LoopLM): a dense llama-like stack with FOUR
    # norms a layer (before and after each sublayer), run `loop_steps`
    # times over the same weights with the final norm and an exit gate
    # after every pass; multi-head attention (a K/V head a query head)
    "ouro": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_000_000.0,
        post_sublayer_norms=True,
        loop_steps=4,
    ),
    # A.X-K2 (beyond-reference; skt's `axk2`): the DeepSeek-V3.2 block
    # (latent attention whose every query attends the `index_topk` rows a
    # learned indexer scores best; one leading dense layer; 256 routed
    # experts top-8 by a bias-corrected sigmoid router limited to the best
    # groups, one shared expert) under the model's own two sublayers: a
    # sigmoid gate a head on the attention's output and a low-rank sigmoid
    # gate on the layer's two norms and the final norm; YaRN (factor 2 over
    # 131,072) on the 64 rope dims
    "axk2": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_000_000.0,
        rope_scaling_type="yarn",
        rope_scaling_factor=2.0,
        rope_yarn_beta_fast=32.0,
        rope_yarn_beta_slow=1.0,
        rope_yarn_original_max_position=131072,
        rope_yarn_mscale_all_dim=1.0,
        attention_type="mla",
        attention_output_gate=True,
        attention_gate_headwise=True,
        gated_norm=True,
        gated_norm_rank=16,
        moe_score_func="sigmoid",
        moe_selection_bias=True,
        moe_normalize_gates=True,
        moe_routed_scaling_factor=2.5,
        moe_shared_experts=1,
        dense_prefix_layers=1,
    ),
    # Qwen2/2.5 (beyond-reference): llama2 block + bias on the QKV
    # projection only + rope_theta 1e6; small checkpoints (<=1.5B) tie
    # embeddings, which config_from_hf passes through
    "qwen2": dict(
        use_rms_norm=True,
        glu_activation="swiglu",
        use_bias=False,
        add_qkv_bias=True,
        tie_embed_logits=False,
        position_embedding_type="rotary",
        layernorm_epsilon=1e-6,
        rope_theta=1_000_000.0,
    ),
}

def lfm2_sublayers(layer_types: str, num_dense_layers: int) -> str:
    """An LFM2 `layer_types` (a letter a layer: `c` conv, `*` full
    attention) as a `sublayer_pattern`, two letters a layer: the mixer
    (`C` or `*`), then the feed-forward (`D` in the first
    `num_dense_layers` layers, `E` after them)."""
    assert set(layer_types) <= set("c*"), layer_types
    return "".join(
        ("C" if t == "c" else "*") + ("D" if i < num_dense_layers else "E")
        for i, t in enumerate(layer_types))


# Canonical model sizes (hidden/layers/heads/kv-heads/ffn) for convenience.
MODEL_SIZES = {
    "llama2-7b": dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
                      num_attention_heads_kv=32, ffn_hidden_size=11008,
                      max_position_embeddings=4096),
    "llama2-13b": dict(num_layers=40, hidden_size=5120, num_attention_heads=40,
                       num_attention_heads_kv=40, ffn_hidden_size=13824,
                       max_position_embeddings=4096),
    "llama2-70b": dict(num_layers=80, hidden_size=8192, num_attention_heads=64,
                       num_attention_heads_kv=8, ffn_hidden_size=28672,
                       max_position_embeddings=4096),
    "llama3-8b": dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
                      num_attention_heads_kv=8, ffn_hidden_size=14336,
                      max_position_embeddings=8192, vocab_size=128256),
    "llama3-70b": dict(num_layers=80, hidden_size=8192, num_attention_heads=64,
                       num_attention_heads_kv=8, ffn_hidden_size=28672,
                       max_position_embeddings=8192, vocab_size=128256),
    "codellama-34b": dict(num_layers=48, hidden_size=8192, num_attention_heads=64,
                          num_attention_heads_kv=8, ffn_hidden_size=22016,
                          max_position_embeddings=16384),
    "falcon-7b": dict(num_layers=32, hidden_size=4544, num_attention_heads=71,
                      num_attention_heads_kv=1, max_position_embeddings=2048),
    "falcon-40b": dict(num_layers=60, hidden_size=8192, num_attention_heads=128,
                       num_attention_heads_kv=8, max_position_embeddings=2048,
                       parallel_layernorm=True),
    "mistral-7b": dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
                       num_attention_heads_kv=8, ffn_hidden_size=14336,
                       max_position_embeddings=32768),
    "mixtral-8x7b": dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
                         num_attention_heads_kv=8, ffn_hidden_size=14336,
                         max_position_embeddings=32768, num_experts=8,
                         moe_router_topk=2),
    # 40 published layers = 1 dense + 39 of experts (dense_prefix_layers
    # comes from the family); no MTP layer on the serving path
    "joyai-llm-flash": dict(num_layers=39, hidden_size=2048,
                            num_attention_heads=32, ffn_hidden_size=7168,
                            max_position_embeddings=131072,
                            q_lora_rank=1536, kv_lora_rank=512,
                            qk_nope_head_dim=128, qk_rope_head_dim=64,
                            v_head_dim=128, num_experts=256,
                            moe_router_topk=8, moe_ffn_hidden_size=768,
                            vocab_size=129280),
    # 52 layers = 13 periods of (full NoPE, window, window, window)
    "smallthinker-21b-a3b": dict(num_layers=52, hidden_size=2560,
                                 num_attention_heads=28,
                                 num_attention_heads_kv=4, kv_channels=128,
                                 max_position_embeddings=16384,
                                 num_experts=64, moe_router_topk=6,
                                 moe_ffn_hidden_size=768,
                                 ffn_hidden_size=768, vocab_size=151936),
    "brumby-14b": dict(num_layers=40, hidden_size=5120,
                       num_attention_heads=40, num_attention_heads_kv=8,
                       kv_channels=128, ffn_hidden_size=17408,
                       max_position_embeddings=32768, vocab_size=151936),
    # 40 published layers = 3 dense linear layers + 37 of experts; the
    # scanned stack is whole periods of (latent, linear, linear, linear)
    # from the model's layer 3 on: 36 of them, the published layer 39 (a
    # latent layer that would open a tenth period) left to a cut's arithmetic
    "gigachat35-432b-a28b": dict(num_layers=36, hidden_size=7168,
                                 num_attention_heads=64,
                                 ffn_hidden_size=18432,
                                 max_position_embeddings=262144,
                                 q_lora_rank=1536, kv_lora_rank=512,
                                 qk_nope_head_dim=128, qk_rope_head_dim=64,
                                 v_head_dim=128,
                                 linear_num_key_heads=32,
                                 linear_num_value_heads=64,
                                 linear_key_head_dim=128,
                                 linear_value_head_dim=128,
                                 linear_conv_kernel_dim=4,
                                 num_experts=256, moe_router_topk=8,
                                 moe_ffn_hidden_size=2048,
                                 vocab_size=128256),
    # NVIDIA-Nemotron-3-Nano-30B-A3B: 23 M, 23 E, 6 * in the published
    # order (6 + 4 x 7 + 9 + 9); the shared expert's 3,712 is two expert
    # widths (one ungated MLP of twice the width is the same function)
    "nemotron_h-3-nano-30b-a3b": dict(
        sublayer_pattern=(
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
        num_layers=52, hidden_size=2688,
        num_attention_heads=32, num_attention_heads_kv=2, kv_channels=128,
        max_position_embeddings=262144,
        mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
        ssm_state_size=128, mamba_conv_kernel=4,
        num_experts=128, moe_router_topk=6, moe_ffn_hidden_size=1856,
        ffn_hidden_size=1856, moe_shared_experts=2,
        moe_routed_scaling_factor=2.5, vocab_size=131072),
    # LFM2-24B-A2B: 40 layers, `conv conv full_attention conv` ten times
    # over, the first two with a dense SwiGLU of 11,776 and 38 with 64
    # experts of 1,536, top-4: 80 sublayers (`num_layers` counts those)
    "lfm2-24b-a2b": dict(
        sublayer_pattern=lfm2_sublayers("cc*c" * 10, 2),
        num_layers=80, hidden_size=2048,
        num_attention_heads=32, num_attention_heads_kv=8, kv_channels=64,
        max_position_embeddings=128000, short_conv_kernel=3,
        ffn_hidden_size=11776, num_experts=64, moe_router_topk=4,
        moe_ffn_hidden_size=1536, moe_routed_scaling_factor=1.0,
        vocab_size=65536),
    # SDAR-30B-A3B-Chat: 48 layers, every one 128 experts of 768, top-8;
    # `intermediate_size` 6144 is read by no layer; blocks of 4 (the
    # report's training block), the mask id the published tokenizer's
    "sdar-30b-a3b-chat": dict(
        num_layers=48, hidden_size=2048,
        num_attention_heads=32, num_attention_heads_kv=4, kv_channels=128,
        max_position_embeddings=32768, ffn_hidden_size=6144,
        num_experts=128, moe_router_topk=8, moe_ffn_hidden_size=768,
        vocab_size=151936, diffusion_block_length=4, mask_token_id=151669),
    # Ouro-2.6B: 48 layers x 4 passes (`total_ut_steps`), 16 heads of 128
    # each with a K/V head of its own, every published size
    "ouro-2.6b": dict(
        num_layers=48, hidden_size=2048,
        num_attention_heads=16, num_attention_heads_kv=16, kv_channels=128,
        max_position_embeddings=65536, ffn_hidden_size=5632,
        vocab_size=49152, loop_steps=4),
    # A.X-K2 688B-A33B: 61 published layers = 1 dense + 60 of experts
    "a.x-k2": dict(num_layers=60, hidden_size=7168, num_attention_heads=64,
                   ffn_hidden_size=18432, max_position_embeddings=262144,
                   q_lora_rank=1536, kv_lora_rank=512,
                   qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                   index_n_heads=64, index_head_dim=128, index_topk=2048,
                   num_experts=256, moe_router_topk=8,
                   moe_ffn_hidden_size=2048, moe_n_group=8, moe_topk_group=4,
                   vocab_size=163840),
    # 32 layers = 8 periods of (window, window, window, full NoPE)
    "commanda-plus": dict(num_layers=32, hidden_size=4096,
                          num_attention_heads=128, num_attention_heads_kv=8,
                          kv_channels=128, max_position_embeddings=200000,
                          num_experts=128, moe_router_topk=8,
                          moe_ffn_hidden_size=4096, ffn_hidden_size=4096,
                          vocab_size=262144),
}


# a canonical size whose family is not the name's first word
SIZE_FAMILIES = {"sdar-30b-a3b-chat": "sdar_moe", "a.x-k2": "axk2"}


def apply_architecture(cfg: Config, model_name: str, size: Optional[str] = None) -> Config:
    """Apply an architecture flag bundle (and optionally a canonical size)."""
    family = model_name.split("-")[0] if model_name not in ARCH_DEFAULTS else model_name
    family = SIZE_FAMILIES.get(model_name, family)
    if model_name in MODEL_SIZES and size is None:
        size = model_name
    assert family in ARCH_DEFAULTS, f"unknown model family {family}"
    cfg.model_name = family
    for k, v in ARCH_DEFAULTS[family].items():
        setattr(cfg.model, k, v)
    if size is not None:
        assert size in MODEL_SIZES, f"unknown model size {size}"
        for k, v in MODEL_SIZES[size].items():
            setattr(cfg.model, k, v)
    return cfg


# ---------------------------------------------------------------------------
# CLI generation
# ---------------------------------------------------------------------------

_GROUPS = {
    "model": ModelConfig,
    "parallel": ParallelConfig,
    "training": TrainingConfig,
    "optimizer": OptimizerConfig,
    "data": DataConfig,
    "checkpoint": CheckpointConfig,
    "logging": LoggingConfig,
    "inference": InferenceConfig,
    "retriever": RetrieverConfig,
    "resilience": ResilienceConfig,
}


def _add_field_arg(parser: argparse.ArgumentParser, f: dataclasses.Field) -> None:
    # Note: `from __future__ import annotations` makes f.type a *string*
    # (e.g. "Optional[Tuple[int, int, int]]"), so dispatch is textual.
    name = "--" + f.name
    tstr = f.type if isinstance(f.type, str) else str(f.type)
    if "bool" in tstr:
        parser.add_argument(name, type=lambda s: s.lower() in ("1", "true", "yes"),
                            nargs="?", const=True, default=None)
    elif "List[int]" in tstr or "Tuple" in tstr:
        parser.add_argument(name, nargs="*", type=int, default=None)
    elif "List" in tstr or "list" in tstr:
        parser.add_argument(name, nargs="*", default=None)
    else:
        # int/float/str and Optional[...] thereof: coerced at assign time
        parser.add_argument(name, type=str, default=None)


def _coerce(value: Any, default: Any) -> Any:
    if value is None:
        return None
    if isinstance(value, list):
        return tuple(value) if isinstance(default, tuple) else value
    if isinstance(value, (tuple, bool)):
        return value
    if value == "None":
        return None
    if isinstance(default, bool):
        return value.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    # defaults of None: try int, float, then str
    if default is None:
        for cast in (int, float):
            try:
                return cast(value)
            except (TypeError, ValueError):
                pass
    return value


# Short spellings for the mesh-layout flags (the Megatron-style names the
# paper and ROADMAP use): --tp/--pp/--dp/--cp expand to the long dataclass
# field flags before parsing, so both forms work everywhere.
_PARALLEL_ALIASES = {
    "--tp": "--tensor_model_parallel_size",
    "--pp": "--pipeline_model_parallel_size",
    "--dp": "--data_parallel_size",
    "--cp": "--context_parallel_size",
    "--ep": "--expert_parallel_size",
}


def _expand_parallel_aliases(argv: List[str]) -> List[str]:
    out = []
    for a in argv:
        head, eq, tail = a.partition("=")
        if head in _PARALLEL_ALIASES:
            out.append(_PARALLEL_ALIASES[head] + (eq + tail if eq else ""))
        else:
            out.append(a)
    return out


def build_parser(extra_args_provider=None) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="TPU-native Megatron-LLM", allow_abbrev=False
    )
    parser.add_argument("--model_name", type=str, default=None,
                        help="gpt|llama|llama2|codellama|llama3|falcon|"
                             "mistral|mixtral|qwen2|joyai|smallthinker|gigachat35|ouro|axk2|"
                             "bert|t5 "
                             "or a canonical size like llama2-7b / "
                             "llama3-8b")
    seen = set()
    for group_name, group_cls in _GROUPS.items():
        group = parser.add_argument_group(group_name)
        for f in fields(group_cls):
            if f.name in seen:
                continue
            seen.add(f.name)
            _add_field_arg(group, f)
    if extra_args_provider is not None:
        extra_args_provider(parser)
    return parser


def parse_args(argv: Optional[List[str]] = None, extra_args_provider=None,
               args_defaults: Optional[dict] = None,
               n_devices: Optional[int] = None, finalize: bool = True) -> Config:
    """Parse CLI flags into a finalized :class:`Config`.

    ``args_defaults`` mirrors the reference's programmatic defaults injection
    (initialize.py:39): values applied before CLI overrides.
    """
    parser = build_parser(extra_args_provider)
    raw = sys.argv[1:] if argv is None else list(argv)
    raw = _expand_parallel_aliases(raw)
    ns, _unknown = parser.parse_known_args(raw)
    cfg = Config()
    if ns.model_name:
        apply_architecture(cfg, ns.model_name)
    if args_defaults:
        for k, v in args_defaults.items():
            _set_flag(cfg, k, v)
    for group_name, group_cls in _GROUPS.items():
        sub = getattr(cfg, group_name)
        for f in fields(group_cls):
            val = getattr(ns, f.name, None)
            if val is not None:
                default = getattr(sub, f.name)
                setattr(sub, f.name, _coerce(val, default))
    if finalize:
        cfg.finalize(n_devices=n_devices)
    return cfg


def _set_flag(cfg: Config, name: str, value: Any) -> None:
    """Set a flat flag name on whichever group owns it."""
    for group_name, group_cls in _GROUPS.items():
        if name in {f.name for f in fields(group_cls)}:
            setattr(getattr(cfg, group_name), name, value)
            return
    if hasattr(cfg, name):
        setattr(cfg, name, value)
        return
    raise KeyError(f"unknown flag {name}")
