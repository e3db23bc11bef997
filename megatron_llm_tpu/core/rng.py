"""RNG policy — functional replacement of CudaRNGStatesTracker.

The reference keeps named CUDA RNG streams so TP ranks draw *distinct*
dropout/init randomness inside model-parallel regions but *identical*
randomness elsewhere, and snapshots all streams around activation recompute
(megatron/core/tensor_parallel/random.py:64-245, seeding at :144-172:
``tensor_model_parallel_seed = seed + 2718 + tp_rank``).

With JAX's splittable PRNG none of that stateful machinery is needed:

* recompute-identical randomness is automatic — the same key produces the
  same bits whenever the (pure) function is replayed under ``jax.checkpoint``;
* per-TP-rank divergence is ``fold_in(key, axis_index('tp'))`` inside
  shard_map regions, or simply letting XLA shard a per-position key grid;
* the reference's seed schedule (initialize.py:179: ``seed + 100*pp_rank``,
  optionally ``+ 10*dp_rank``) becomes explicit fold_in constants below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Fold-in tags (arbitrary distinct constants; the 2718 matches the reference's
# model-parallel seed offset for archeological charm, random.py:161).
_MODEL_PARALLEL_TAG = 2718
_DATA_TAG = 1
_DROPOUT_TAG = 2
_INIT_TAG = 3
_PP_STRIDE = 100
_DP_STRIDE = 10


def base_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def init_key(seed: int) -> jax.Array:
    """Key for parameter initialization (identical on all ranks; sharded init
    draws are made consistent by initializing with jit + NamedSharding)."""
    return jax.random.fold_in(base_key(seed), _INIT_TAG)


def data_key(seed: int, iteration: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(base_key(seed), _DATA_TAG), iteration)


def dropout_key(seed: int, iteration: int) -> jax.Array:
    k = jax.random.fold_in(base_key(seed), _DROPOUT_TAG)
    return jax.random.fold_in(k, iteration)


def fold_layer(key: jax.Array, layer_index) -> jax.Array:
    return jax.random.fold_in(key, layer_index)


def fold_model_parallel(key: jax.Array, axis_name: str = "tp") -> jax.Array:
    """Diverge randomness across TP ranks inside a shard_map region
    (semantics of get_cuda_rng_tracker().fork(), random.py:121-141)."""
    return jax.random.fold_in(
        jax.random.fold_in(key, _MODEL_PARALLEL_TAG),
        jax.lax.axis_index(axis_name),
    )


def fold_pipeline_stage(key: jax.Array, pp_rank) -> jax.Array:
    """seed + 100 * pp_rank semantics (initialize.py:186-189)."""
    return jax.random.fold_in(key, _PP_STRIDE * pp_rank)


def fold_data_parallel(key: jax.Array, dp_rank) -> jax.Array:
    """Optional per-DP-rank init divergence (--data_parallel_random_init)."""
    return jax.random.fold_in(key, _DP_STRIDE * dp_rank)


def dropout(key: jax.Array, rate, x: jax.Array, deterministic: bool = False):
    """Plain inverted dropout; no-op when rate == 0 or deterministic.

    ``rate`` may be a traced scalar (LIMA per-layer ramp inside lax.scan), in
    which case the zero-rate short-circuit is skipped and the math handles it.
    """
    if deterministic or (isinstance(rate, (int, float)) and rate == 0.0):
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))
