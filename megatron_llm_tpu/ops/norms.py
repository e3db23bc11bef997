"""Normalization layers.

Replaces the reference's fused CUDA LayerNorm (model/fused_layer_norm.py:26-61,
layer_norm_cuda_kernel.cu) and pure-torch RMSNorm (fused_layer_norm.py:125-139).
On TPU, XLA fuses these elementwise chains well, and this jnp form is what the
model path runs.  A Pallas fused RMSNorm kernel exists (ops/pallas/rmsnorm.py)
but is NOT wired in: only tools/tpu_kernel_check.py and tools/mfu_sweep.py
call it, the latter to decide whether it should be.

Math matches the reference: internal computation in fp32, cast back to the
input dtype (RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm (fused_layer_norm.py:125-139 semantics: fp32 internal math)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array | None, eps: float = 1e-5
) -> jax.Array:
    """Affine LayerNorm with fp32 internal math (MixedFusedLayerNorm semantics)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)


def norm(x, params: dict, eps: float, use_rms: bool) -> jax.Array:
    """Dispatch on norm family given a params dict {'scale': ..., 'bias': ...?}."""
    if use_rms:
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params.get("bias"), eps)


def init_norm_params(hidden_size: int, use_rms: bool, dtype=jnp.float32,
                     bias: bool = True) -> dict:
    """``bias``: a LayerNorm's additive bias (``norm_bias``; RMSNorm has none)."""
    p = {"scale": jnp.ones((hidden_size,), dtype=dtype)}
    if not use_rms and bias:
        p["bias"] = jnp.zeros((hidden_size,), dtype=dtype)
    return p
