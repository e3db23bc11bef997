"""Long-context smoke (VERDICT round-3 item 5): the 32K path's pieces —
RoPE position-interpolation scaling, long-seq masking, full remat, and the
ring-attention row-blocked online softmax — exercised end to end in a
train step at a CPU-tractable scaled-down width/seq. The full 32K e2e run
is bench.py --seq 32768 --rope_scaling 8 (on a TPU host); the AOT proof
at real width is
tools/aot_scale_check.py::codellama_34b_32k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.training_step import make_jitted_train_step


def test_long_seq_rope_scaled_train_step():
    seq = 8192
    cfg = make_config(
        "codellama",  # theta=1e6 family bundle
        num_layers=2, hidden_size=128, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=256, vocab_size=512,
        seq_length=seq, max_position_embeddings=seq,
        rope_scaling_factor=4.0, params_dtype="float32",
        micro_batch_size=1, global_batch_size=1, train_iters=10,
        use_flash_attn=False,
        context_parallel_size=2,  # ring attention carries the long seq
    )
    cfg.parallel.recompute_granularity = "full"
    cfg.finalize()
    mesh = build_mesh(context_parallel_size=2, devices=jax.devices()[:2])
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        step, _o, sh = make_jitted_train_step(cfg, mesh, params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (1, seq + 1), 0, 512)
        batch = sh["place_batch"]({
            "tokens": tok[:, :-1], "labels": tok[:, 1:],
            "loss_mask": jnp.ones((1, seq), jnp.float32),
        })
        _p, _o2, m = step(params, sh["opt_state_value"], batch, 0)
        loss = float(m["lm loss"])
    assert np.isfinite(loss) and loss > 0, loss


def test_rope_scaling_changes_long_range_attention():
    """Position interpolation actually rescales positions: the rope cache
    for scaled positions at seq 8192 equals the unscaled cache at 2048
    stretched 4x (codellama 16K-native doubling semantics,
    reference positional_embeddings.py:11 scaling)."""
    from megatron_llm_tpu.models.language_model import make_rope_cache

    base = make_config(
        "codellama", num_layers=1, hidden_size=64, num_attention_heads=1,
        num_attention_heads_kv=1, vocab_size=64, seq_length=8192,
        max_position_embeddings=8192, params_dtype="float32",
        micro_batch_size=1, global_batch_size=1, train_iters=1)
    scaled = make_config(
        "codellama", num_layers=1, hidden_size=64, num_attention_heads=1,
        num_attention_heads_kv=1, vocab_size=64, seq_length=8192,
        max_position_embeddings=8192, rope_scaling_factor=4.0,
        params_dtype="float32",
        micro_batch_size=1, global_batch_size=1, train_iters=1)
    cb = make_rope_cache(base)
    cs = make_rope_cache(scaled)
    # scaled position p behaves like unscaled position p/4
    cb_f = jax.tree_util.tree_leaves(cb)[0]
    cs_f = jax.tree_util.tree_leaves(cs)[0]
    np.testing.assert_allclose(
        np.asarray(cs_f[4000]), np.asarray(cb_f[1000]), atol=1e-5)