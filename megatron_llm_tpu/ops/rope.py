"""Rotary position embeddings (RoPE) with linear position interpolation.

Reference: megatron/model/positional_embeddings.py — complex-number RoPE with
*interleaved-pair* convention (Meta/Llama native layout: dims (0,1), (2,3), ...
form the rotated pairs), ``precompute_freqs_cis`` at :7 with the 32K-context
linear scaling ``t /= scaling_factor`` at :11, and non-monotonic position_ids
support for packed sequences at :38-47.

We compute in real arithmetic (TPU has no complex MXU path): for each pair
(x_even, x_odd) rotate by angle theta_i * pos. cos/sin are precomputed in
fp32 and applied in fp32 for accuracy, output cast back to input dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def llama3_scale_freqs(
    freqs: jax.Array,
    factor: float,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_position: int = 8192,
) -> jax.Array:
    """Llama-3.1 frequency remap (HF ``rope_type: "llama3"``).

    Published piecewise rule: frequencies whose wavelength fits well inside
    the original context (wavelen < orig/high_freq_factor) are kept;
    frequencies whose wavelength exceeds it (wavelen > orig/low_freq_factor)
    are divided by ``factor`` (pure position interpolation); the band in
    between is smoothly interpolated. Beyond-reference: the reference's
    positional_embeddings.py:11 only implements the linear rule.
    """
    wavelen = 2.0 * jnp.pi / freqs
    low_wavelen = original_max_position / low_freq_factor
    high_wavelen = original_max_position / high_freq_factor
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    interp = (1.0 - smooth) * freqs / factor + smooth * freqs
    out = jnp.where(wavelen > low_wavelen, freqs / factor, interp)
    return jnp.where(wavelen < high_wavelen, freqs, out)


def yarn_scale_freqs(
    freqs: jax.Array,
    factor: float,
    theta: float,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    original_max_position: int = 4096,
) -> jax.Array:
    """YaRN (arXiv:2309.00071; HF ``rope_type: "yarn"``, the DeepSeek-V3
    modelling code's form): a frequency that turns more than ``beta_fast``
    times over the original context is kept (extrapolation), one that
    turns fewer than ``beta_slow`` times is divided by ``factor``
    (interpolation), and the pairs between are blended linearly by their
    index.  The attention's ``mscale`` is the caller's."""
    half = freqs.shape[0]
    dim = 2 * half

    def pair_of(turns):     # the pair whose wavelength makes ``turns`` turns
        return dim * math.log(original_max_position / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 x mscale x ln(factor) + 1`` (1 where nothing is scaled)."""
    return 1.0 if factor <= 1.0 or not mscale else (
        0.1 * mscale * math.log(factor) + 1.0)


def precompute_freqs(
    dim: int,
    max_len: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
    scaling_type: str = "linear",
    llama3_params: dict | None = None,
    dtype=jnp.float32,
    yarn_params: dict | None = None,
):
    """Return (cos, sin), each [max_len, dim//2], fp32.

    positional_embeddings.py:7-21 semantics incl. position interpolation
    (positions divided by scaling_factor). ``scaling_type="llama3"``
    instead remaps the frequencies per :func:`llama3_scale_freqs`
    (positions undivided), matching HF Llama-3.1+ checkpoints.
    """
    if scaling_type not in ("linear", "llama3", "yarn"):
        # fail-loudly posture (same as hf_to_native's rope_scaling check):
        # an unknown type silently falling back to linear would produce
        # wrong frequencies with no diagnostic
        raise ValueError(f"unknown rope scaling_type {scaling_type!r}; "
                         "expected 'linear', 'llama3' or 'yarn'")
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling_type == "llama3" and scaling_factor != 1.0:
        freqs = llama3_scale_freqs(freqs, scaling_factor,
                                   **(llama3_params or {}))
        t = jnp.arange(max_len, dtype=jnp.float32)
    elif scaling_type == "yarn" and scaling_factor != 1.0:
        freqs = yarn_scale_freqs(freqs, scaling_factor, theta,
                                 **(yarn_params or {}))
        t = jnp.arange(max_len, dtype=jnp.float32)
    else:
        t = jnp.arange(max_len, dtype=jnp.float32) / scaling_factor
    angles = jnp.outer(t, freqs)  # [max_len, dim//2]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary_emb(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    position_ids: jax.Array | None = None,
) -> jax.Array:
    """Rotate ``x`` [batch, seq, heads, head_dim] (interleaved-pair convention).

    ``position_ids`` [batch, seq] gathers rows of cos/sin — supports packed
    sequences with restarting positions (positional_embeddings.py:38-47).
    Without it, positions 0..seq-1 are used.
    """
    b, s, h, d = x.shape
    if position_ids is None:
        c = cos[:s][None, :, None, :]  # [1, s, 1, d/2]
        sn = sin[:s][None, :, None, :]
    else:
        c = cos[position_ids][:, :, None, :]  # [b, s, 1, d/2]
        sn = sin[position_ids][:, :, None, :]
    xf = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x_even, x_odd = xf[..., 0], xf[..., 1]
    out_even = x_even * c - x_odd * sn
    out_odd = x_odd * c + x_even * sn
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(b, s, h, d)
    return out.astype(x.dtype)


def apply_rotary_emb_half(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    position_ids: jax.Array | None = None,
) -> jax.Array:
    """HF-convention RoPE (rotate_half: first/second half are the pairs).

    Provided for logit-parity testing against HuggingFace checkpoints without
    re-permuting weights; the two conventions are related by a fixed head-dim
    permutation (reference weights_conversion/utils/permute_qkv.py).
    """
    b, s, h, d = x.shape
    if position_ids is None:
        idx = jnp.arange(s)
        c, sn = cos[idx], sin[idx]
        c = c[None, :, None, :]
        sn = sn[None, :, None, :]
    else:
        c = cos[position_ids][:, :, None, :]
        sn = sin[position_ids][:, :, None, :]
    c = jnp.concatenate([c, c], axis=-1)  # [.., d]
    sn = jnp.concatenate([sn, sn], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * c + rotated * sn).astype(x.dtype)
