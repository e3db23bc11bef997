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


def sigmoid2_gain(w: jax.Array) -> jax.Array:
    """``2 sigmoid(w)``: the gain of a ZeroCenteredGatedNorm (GigaChat3.5's
    ``norm_type`` with ``layernorm_gating_weight`` 2), worth 1 at ``w = 0``
    and never negative or above 2."""
    return 2.0 * jax.nn.sigmoid(w.astype(jnp.float32))


def gated_head_norm(x: jax.Array, z: jax.Array, weight: jax.Array,
                    eps: float = 1e-6, gate_scale: float = 2.0) -> jax.Array:
    """The gated-delta layers' output norm a head
    (``gated_rmsnorm_sigmoid_zero_centered``): ``x / rms(x) * (1 + w) *
    gate_scale * sigmoid(z)``, float32 in and out."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out * (gate_scale * jax.nn.sigmoid(z.astype(jnp.float32)))


def norm(x, params: dict, eps: float, use_rms: bool) -> jax.Array:
    """Dispatch on norm family given a params dict {'scale': ..., 'bias': ...?}
    (or ``{'gate': w}``: an RMSNorm whose gain is :func:`sigmoid2_gain`)."""
    if "gate" in params:
        return rms_norm(x, sigmoid2_gain(params["gate"]), eps)
    if use_rms:
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params.get("bias"), eps)


def init_norm_params(hidden_size: int, use_rms: bool, dtype=jnp.float32,
                     bias: bool = True, gain: str = "scale") -> dict:
    """``bias``: a LayerNorm's additive bias (``norm_bias``; RMSNorm has none).
    ``gain`` 'sigmoid2': the leaf is ``gate``, zeros (a gain of 1)."""
    if gain == "sigmoid2":
        assert use_rms
        return {"gate": jnp.zeros((hidden_size,), dtype=dtype)}
    p = {"scale": jnp.ones((hidden_size,), dtype=dtype)}
    if not use_rms and bias:
        p["bias"] = jnp.zeros((hidden_size,), dtype=dtype)
    return p
