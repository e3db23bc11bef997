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


def gated_norm(x: jax.Array, params: dict, eps: float = 1e-6) -> jax.Array:
    """A.X-K2's GatedNorm: ``n * sigmoid((n W_down) W_up)`` with ``n =
    RMSNorm(x)``; the gate reads the normalised vector through a rank-r
    pair with no activation between (``gate_down`` [h, r], ``gate_up`` [r,
    h]).  The pair multiplies in the input's dtype into float32, the gate
    and the product are float32."""
    n = rms_norm(x, params["scale"], eps)
    low = jnp.dot(n, params["gate_down"].astype(n.dtype),
                  preferred_element_type=jnp.float32)
    gate = jnp.dot(low.astype(n.dtype), params["gate_up"].astype(n.dtype),
                   preferred_element_type=jnp.float32)
    return (n.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(x.dtype)


def norm(x, params: dict, eps: float, use_rms: bool) -> jax.Array:
    """Dispatch on norm family given a params dict {'scale': ..., 'bias': ...?}
    (or ``{'gate': w}``: an RMSNorm whose gain is :func:`sigmoid2_gain`; or
    with ``gate_down`` / ``gate_up``: :func:`gated_norm`)."""
    if "gate_down" in params:
        return gated_norm(x, params, eps)
    if "gate" in params:
        return rms_norm(x, sigmoid2_gain(params["gate"]), eps)
    if use_rms:
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params.get("bias"), eps)


def init_norm_params(hidden_size: int, use_rms: bool, dtype=jnp.float32,
                     bias: bool = True, gain: str = "scale",
                     gated_rank: int = 0, key=None) -> dict:
    """``bias``: a LayerNorm's additive bias (``norm_bias``; RMSNorm has none).
    ``gain`` 'sigmoid2': the leaf is ``gate``, zeros (a gain of 1).
    ``gated_rank`` > 0 (with ``key``): :func:`gated_norm`'s pair, DRAWN at
    1 / sqrt(fan-in) so that a gate's logit has unit variance (at the
    weights' 0.02 every gate would sit at a half and a program that
    dropped the gate would read as one that scaled its norms)."""
    if gated_rank:
        assert use_rms and gain == "scale" and key is not None
        k_down, k_up = jax.random.split(key)
        return {
            "scale": jnp.ones((hidden_size,), dtype=dtype),
            "gate_down": jax.random.normal(
                k_down, (hidden_size, gated_rank), dtype) / hidden_size ** 0.5,
            "gate_up": jax.random.normal(
                k_up, (gated_rank, hidden_size), dtype) / gated_rank ** 0.5}
    if gain == "sigmoid2":
        assert use_rms
        return {"gate": jnp.zeros((hidden_size,), dtype=dtype)}
    p = {"scale": jnp.ones((hidden_size,), dtype=dtype)}
    if not use_rms and bias:
        p["bias"] = jnp.zeros((hidden_size,), dtype=dtype)
    return p
