"""Activation functions: GLU family + gated variants.

Reference: megatron/model/glu_activations.py:8-48 (LiGLU/GEGLU/ReGLU/SwiGLU as
chunk-2 gating over the doubled fc1 output) and fused_bias_gelu.py (tanh-approx
gelu). XLA fuses these into the surrounding matmuls, so no custom kernels.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp


def gelu_tanh(x: jax.Array) -> jax.Array:
    """Tanh-approximated GeLU (fused_bias_gelu.py:10-17 formula)."""
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * x * (1.0 + 0.044715 * x * x)))


def squared_relu(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


ACTIVATIONS: Dict[str, Callable] = {
    "gelu": jax.nn.gelu,
    "gelu_tanh": gelu_tanh,
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
    "silu": jax.nn.silu,
}


def _glu(x: jax.Array, act: Callable) -> jax.Array:
    """Chunk-2 gating on the last dim: x1 * act(x2).

    Convention matches the reference (glu_activations.py:14-16: the activation
    applies to the *second* half of fc1's doubled output) so that fc1 weight
    layouts from converted checkpoints load without reshuffling.
    """
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x1 * act(x2)


def liglu(x):
    return _glu(x, lambda a: a)


def geglu(x):
    return _glu(x, jax.nn.gelu)


def reglu(x):
    return _glu(x, jax.nn.relu)


def swiglu(x):
    return _glu(x, jax.nn.silu)


GLU_ACTIVATIONS: Dict[str, Callable] = {
    "liglu": liglu,
    "geglu": geglu,
    "reglu": reglu,
    "swiglu": swiglu,
}

# Base (non-gated) activation for each GLU variant, for the [h, 2, ffn]
# fc1 layout where the gate applies as x[..., 0, :] * act(x[..., 1, :]).
GLU_BASE_ACTIVATIONS: Dict[str, Callable] = {
    "liglu": lambda a: a,
    "geglu": jax.nn.gelu,
    "reglu": jax.nn.relu,
    "swiglu": jax.nn.silu,
}


def glu_product(name: str, value: jax.Array, gate: jax.Array,
                limit: Optional[float] = None) -> jax.Array:
    """``value * act(gate)`` of the ``[.., 2, ffn]`` fc1 layout; with
    ``limit`` (gpt-oss's clamp, GigaChat3.5's ``swiglu_limit``) the gate is
    cut from above and the value on both sides first."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        value = jnp.clip(value, -limit, limit)
    return value * GLU_BASE_ACTIVATIONS[name](gate)


def get_mlp_activation(glu_activation: Optional[str], activation: str = "gelu") -> Callable:
    """Resolve the MLP activation; GLU variants expect a doubled fc1 output."""
    if glu_activation is not None:
        return GLU_ACTIVATIONS[glu_activation]
    return ACTIVATIONS[activation]
