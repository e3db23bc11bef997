"""Model-config flops accounting: tokens/sec -> TFLOP/s -> MFU.

Megatron-LM's scaling methodology (Narayanan et al., PAPERS.md) treats
per-step time/flops as a first-class training signal; this module is the
single home for that arithmetic — the driver's log line, the ``pretrain``
result dict (``steady_mfu`` / ``tokens_per_sec``), the metrics registry
gauges, and bench.py's measured-MFU line all divide by the same numbers.

Everything here is pure host math over the static model config — no
device contact (lint-enforced for this package).
"""

from __future__ import annotations

from typing import Optional

__all__ = [
    "PEAK_BF16_FLOPS_BY_KIND",
    "device_peak_flops",
    "flops_per_step",
    "flops_per_token",
    "mfu",
    "param_count",
]

PEAK_BF16_FLOPS_BY_KIND = {
    # per-chip peak dense bf16 FLOP/s, by EXACT device_kind string — the
    # single source of truth (bench.py re-exports; tools/aot_scale_check.py
    # estimates divide by the same numbers the measured MFU divides by)
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,     # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,  # Trillium
    "TPU v6e": 918e12,
}


def device_peak_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s for an exact ``device_kind`` string.

    None on an explicit CPU run (an 'MFU' over a nominal CPU peak is not a
    measurement — callers report 0/None instead).  Any other kind missing
    from the table is an error: a measuring path does not divide by a
    guess."""
    if device_kind == "cpu":
        return None
    if device_kind not in PEAK_BF16_FLOPS_BY_KIND:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {device_kind!r}: add it "
            "to PEAK_BF16_FLOPS_BY_KIND with its source")
    return PEAK_BF16_FLOPS_BY_KIND[device_kind]


def param_count(cfg) -> int:
    """Approximate parameter count from the model config (attention +
    MLP + embeddings; the reference FLOP-estimate family,
    language_model.py:370-384)."""
    m = cfg.model
    h, L = m.hidden_size, m.num_layers
    d = m.kv_channels or h // m.num_attention_heads
    n, nkv = m.num_attention_heads, m.num_attention_heads_kv or n
    ffn = m.ffn_hidden_size
    glu = 2 if m.glu_activation else 1
    per_layer = h * (n + 2 * nkv) * d + n * d * h + h * ffn * glu + ffn * h
    v = m.vocab_size or 32000
    emb = v * h * (1 if m.tie_embed_logits else 2)
    return per_layer * L + emb


def flops_per_token(cfg) -> float:
    """Matmul FLOPs per token, fwd+bwd: ``6*N`` dense plus the causal
    attention matmuls (QK^T and AV: 4*s^2*h per layer per sequence
    non-causal fwd, /2 causal, x3 fwd+bwd => 6*L*s*h per token)."""
    m = cfg.model
    attn = 6.0 * m.num_layers * m.hidden_size * cfg.data.seq_length
    return 6.0 * param_count(cfg) + attn


def flops_per_step(cfg, global_batch_size: Optional[int] = None) -> float:
    """Whole-step (all microbatches) matmul FLOPs from the config."""
    gbs = global_batch_size or cfg.training.global_batch_size or 1
    return flops_per_token(cfg) * gbs * cfg.data.seq_length


def mfu(cfg, tokens_per_sec: float,
        peak: Optional[float] = None,
        device_kind: Optional[str] = None,
        n_devices: int = 1) -> Optional[float]:
    """Model flops utilization (fraction) at a measured token rate.

    ``peak`` (per device) wins when given; otherwise it is looked up from
    ``device_kind``.  ``n_devices`` is every device the token rate was
    produced on.  Returns None on a CPU run — the callers publish 0.0 /
    omit the field rather than a made-up number."""
    if peak is None and device_kind is not None:
        peak = device_peak_flops(device_kind)
    if not peak or tokens_per_sec <= 0:
        return None
    return flops_per_token(cfg) * tokens_per_sec / (peak * max(n_devices, 1))
