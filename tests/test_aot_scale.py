"""AOT compile-for-topology path (tools/aot_scale_check.py).

Validates on small shapes what the tool proves at 7B-70B scale: the full
jitted train step lowers and compiles for a VIRTUAL TPU topology from a CPU
host, with abstract (never materialized) params/optimizer state, the Pallas
flash kernel in the compiled program (kernel dispatch keys on the mesh
target platform, core/parallel_state.target_platform), and the 1F1B
schedule's nested shard_map composing with the manual pp axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

topologies = pytest.importorskip("jax.experimental.topologies")


@functools.lru_cache(maxsize=None)
def _topology(name):
    """The compile-only PJRT client for a virtual TPU topology (libtpu, no
    chips), or the reason there is none — asked once per topology."""
    try:
        return topologies.get_topology_desc(name, "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu here: skip, below
        return f"{type(e).__name__}: {e}"


def _topo_devices(name):
    topo = _topology(name)
    if isinstance(topo, str):
        pytest.skip(f"TPU topology unavailable: {topo}")
    return list(np.array(topo.devices).ravel())


def _lower_and_compile(cfg, mesh, gbs, seq, extra_batch=None):
    from megatron_llm_tpu.core.parallel_state import global_mesh
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.optimizer.optimizer import get_optimizer
    from megatron_llm_tpu.training_step import make_jitted_train_step

    with global_mesh(mesh):
        params_abs = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        opt = get_optimizer(cfg, params_abs)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        step, _o, _sh = make_jitted_train_step(
            cfg, mesh, params_abs, optimizer=opt, opt_state=opt_abs)
        batch = {
            "tokens": jax.ShapeDtypeStruct((gbs, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((gbs, seq), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((gbs, seq), jnp.float32),
            **(extra_batch or {}),
        }
        lowered = step.lower(params_abs, opt_abs, batch,
                             jax.ShapeDtypeStruct((), jnp.int32))
        return lowered, lowered.compile()


def test_aot_dense_tp8_includes_flash_kernel():
    from megatron_llm_tpu.core.parallel_state import build_mesh, target_platform, global_mesh
    from megatron_llm_tpu.models import make_config

    devices = _topo_devices("v5e:2x4")
    mesh = build_mesh(tensor_model_parallel_size=8, devices=devices)
    with global_mesh(mesh):
        assert target_platform() == "tpu"  # CPU host, TPU compile target
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=512, num_attention_heads=8,
        num_attention_heads_kv=8, vocab_size=2048, seq_length=256,
        max_position_embeddings=256, params_dtype="bfloat16",
        tensor_model_parallel_size=8, sequence_parallel=True,
        use_distributed_optimizer=True, micro_batch_size=1,
        global_batch_size=2, train_iters=10)
    cfg.parallel.num_micro_batches = 2
    lowered, compiled = _lower_and_compile(cfg, mesh, 2, 256)
    hlo = lowered.as_text()
    assert "tpu_custom_call" in hlo or "mosaic" in hlo.lower(), (
        "AOT lowering must contain the Pallas flash kernel")
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 0


def test_aot_1f1b_vpp_nested_shard_map_composes():
    """Regression: _flash_sharded inside the pipeline's manual (pp) context
    must bind the context abstract mesh (ops/attention.py)."""
    from megatron_llm_tpu.core.parallel_state import build_mesh
    from megatron_llm_tpu.models import make_config

    devices = _topo_devices("v5p:2x4x4")
    mesh = build_mesh(tensor_model_parallel_size=8,
                      pipeline_model_parallel_size=4, devices=devices)
    cfg = make_config(
        "falcon", num_layers=8, hidden_size=512, num_attention_heads=8,
        num_attention_heads_kv=8, ffn_hidden_size=2048, vocab_size=2048,
        seq_length=256, max_position_embeddings=256,
        params_dtype="bfloat16",
        tensor_model_parallel_size=8, pipeline_model_parallel_size=4,
        sequence_parallel=True, use_distributed_optimizer=True,
        micro_batch_size=1, global_batch_size=8, train_iters=10)
    cfg.parallel.num_micro_batches = 8
    cfg.parallel.pipeline_schedule = "1f1b"
    cfg.parallel.virtual_pipeline_model_parallel_size = 2
    cfg.parallel.recompute_granularity = "full"
    cfg.finalize()
    _lowered, compiled = _lower_and_compile(cfg, mesh, 8, 256)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_aot_striped_zigzag_ring_compiles():
    """The striped (zigzag) flash ring composes with the FULL jitted train
    step for a TPU target: cp2 + cp_zigzag + a token_idx batch must lower
    the half-chunk Mosaic kernels (the CPU dryrun can only exercise the
    jnp fallback — dispatch is TPU-target-only)."""
    from megatron_llm_tpu.core.parallel_state import build_mesh
    from megatron_llm_tpu.models import make_config
    from megatron_llm_tpu.parallel.ring import zigzag_permutation

    devices = _topo_devices("v5e:2x4")
    mesh = build_mesh(tensor_model_parallel_size=2, context_parallel_size=2,
                      data_parallel_size=2, devices=devices)
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=512, num_attention_heads=8,
        num_attention_heads_kv=8, ffn_hidden_size=1024, vocab_size=4096,
        seq_length=1024, max_position_embeddings=1024,
        params_dtype="bfloat16",
        tensor_model_parallel_size=2, context_parallel_size=2,
        sequence_parallel=True, use_distributed_optimizer=True,
        micro_batch_size=1, global_batch_size=2, train_iters=10)
    cfg.parallel.data_parallel_size = 2
    cfg.parallel.num_micro_batches = 1
    cfg.parallel.cp_zigzag = True
    cfg.finalize()
    gbs, s = 2, 1024
    lowered, compiled = _lower_and_compile(cfg, mesh, gbs, s, extra_batch={
        "position_ids": jax.ShapeDtypeStruct((gbs, s), jnp.int32),
        "token_idx": jax.ShapeDtypeStruct(
            zigzag_permutation(s, 2).shape, jnp.int32),
    })
    assert lowered.as_text().count("tpu_custom_call") > 0, (
        "striped ring must lower Mosaic kernels, not the jnp fallback")
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_aot_pp_dp_tp_flash_no_partitioner_crash():
    """Round-5 regression for the round-4 north-star blocker: the
    dp2 x pp2 x tp2 combo (1F1B + ZeRO-1 + full remat + nested-manual
    flash) CHECK-crashed XLA's scatter partitioner via the embedding-grad
    scatter-add inside the tick loop (spmd_partitioner_util.cc:506). With
    the matmul-backward embedding (language_model._take_rows_matmul_bwd)
    it must compile WITH the flash kernel in the HLO — the same structure
    tools/aot_scale_check.py certifies at tp8 x pp8 x dp4 / 70B."""
    from megatron_llm_tpu.core.parallel_state import build_mesh
    from megatron_llm_tpu.models import make_config

    devices = _topo_devices("v5e:2x4")
    mesh = build_mesh(tensor_model_parallel_size=2,
                      pipeline_model_parallel_size=2,
                      data_parallel_size=2, devices=devices)
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=512, num_attention_heads=8,
        num_attention_heads_kv=8, ffn_hidden_size=1024, vocab_size=4096,
        seq_length=512, max_position_embeddings=512,
        params_dtype="bfloat16",
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2,
        sequence_parallel=True, use_distributed_optimizer=True,
        micro_batch_size=1, global_batch_size=8, train_iters=10)
    cfg.parallel.data_parallel_size = 2
    cfg.parallel.num_micro_batches = 4
    cfg.parallel.pipeline_schedule = "1f1b"
    cfg.parallel.recompute_granularity = "full"
    cfg.finalize()
    lowered, compiled = _lower_and_compile(cfg, mesh, 8, 512)
    assert lowered.as_text().count("tpu_custom_call") > 0, (
        "flash must dispatch at the pp x dp x tp layout, not fall back")
    assert compiled.memory_analysis().argument_size_in_bytes > 0


# ---------------------------------------------------------------------------
# serving: the paged kernels and the engine's programs (ISSUE 21).  Mosaic
# refuses block shapes the interpreter accepts, so "runs in interpret mode"
# (tests/test_paged_engine.py) never showed these would start on a chip.
# ---------------------------------------------------------------------------


def _abstract_pool(shape, kv_dtype, sharding, scale_sharding):
    """The K/V pool of logical ``shape`` = [..., pages, page, nkv, d] in
    the row ops/kv_quant.py gives it, abstract: what make_kv_pool builds."""
    from megatron_llm_tpu.ops import kv_quant

    *lead, page, nkv, d = shape
    made = jax.eval_shape(lambda: kv_quant.make_pool(
        (*lead, page, 2 * nkv, d), kv_dtype, jnp.bfloat16))
    assert kv_quant.row_width(made) == 2 * nkv * d
    shardings = (kv_quant.QuantPagedKV(q=sharding, scale=scale_sharding)
                 if kv_quant.is_quantized(made) else sharding)
    return jax.tree.map(
        lambda a, s_: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s_),
        made, shardings)


def _weights_moved(compiled, min_bytes, dtype=""):
    """Names of the compiled program's copies and slice-only fusions of at
    least ``min_bytes`` (tools/tick_hlo_copies.py reads the text), of
    ``dtype`` (an HLO shape's prefix) where one is given."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import tick_hlo_copies

    return [r.instr.name for r in tick_hlo_copies.moved(
        tick_hlo_copies.parse_hlo(compiled.as_text()), min_bytes)
        if r.instr.shape.startswith(dtype)]


def _compiles_with_kernel(fn, *args, **jit_kw):
    lowered = jax.jit(fn, **jit_kw).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), (
        "the program must hold the Pallas kernel, not the jnp path")
    lowered.compile()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize(
    "heads", [(32, 8, 128), (32, 32, 128), (71, 1, 64), (128, 8, 64)],
    ids=["gqa32q8kv", "mha32q32kv", "mqa71q1kv64", "gqa128q8kv64"])
def test_aot_paged_kernels_compile(heads, kv_dtype):
    """Decode, prefill-chunk and ragged kernels lower and compile for a
    v5e at the preset head geometries (Mistral's and Llama-2's heads of
    128, Falcon-7B's one kv head of 64 and Falcon-40B's eight; default page
    size), on plain and quantized pools, reading the pool's row as it is
    stored."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.ops import paged_attention as pa

    n, nkv, d = heads
    page, pages, maxp, b = 16, 64, 8, 8
    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pool = _abstract_pool((pages, page, nkv, d), kv_dtype, repl, repl)
    with global_mesh(mesh):
        assert pa._kernel_refusal(pool, d) is None
        _compiles_with_kernel(
            pa.paged_attention_decode, S((b, 1, n, d), jnp.bfloat16), pool,
            S((b, maxp), jnp.int32), S((b,), jnp.int32))
        _compiles_with_kernel(
            pa.paged_attention_prefill, S((1, 64, n, d), jnp.bfloat16), pool,
            S((1, maxp), jnp.int32), S((1,), jnp.int32))
        _compiles_with_kernel(
            pa.paged_attention_ragged, S((72, 1, n, d), jnp.bfloat16), pool,
            S((11, maxp), jnp.int32), S((72,), jnp.int32),
            S((72,), jnp.int32), S((72,), jnp.int32))


def test_paged_kernel_refusal_rule():
    """_kernel_refusal is the whole dispatch rule: lanes (a head's
    key|value pair, 2 * head_dim, in whole 128-lane groups; a latent row
    whole lanes itself), sublanes (page % 8), TPU target."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.ops import paged_attention as pa

    def refusal(page, nkv, d):
        return pa._kernel_refusal(
            _abstract_pool((9, page, nkv, d), "bf16", None, None), d)

    assert "cpu" in refusal(16, 8, 128)
    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    with global_mesh(mesh):
        assert refusal(16, 8, 128) is None
        assert refusal(16, 2, 256) is None
        assert refusal(16, 1, 64) is None   # Falcon-7B
        # Falcon-40B: refused while a head's 64 lanes were sliced alone
        assert refusal(16, 8, 64) is None
        assert "128 lanes" in refusal(16, 8, 96)
        assert "128 lanes" in refusal(16, 1, 32)
        assert "8 sublanes" in refusal(12, 8, 128)
        latent = jax.ShapeDtypeStruct((9, 16, 640), jnp.bfloat16)
        assert pa._kernel_refusal(latent, 640, latent=True) is None
        assert "128 lanes" in pa._kernel_refusal(
            jax.ShapeDtypeStruct((9, 16, 576), jnp.bfloat16), 576, True)


@pytest.mark.parametrize("tp", [pytest.param(1, marks=pytest.mark.slow), 4])
def test_aot_engine_programs_compile(tp):
    """The engine's ragged tick (decode + prefill rows) and its
    prefill-chunk program at chip_smoke.py's geometry — Mistral-7B widths,
    abstract parameters — compile for one v5e and, shard_mapped over the
    heads, for the four-chip host."""
    from megatron_llm_tpu.core.parallel_state import (
        TP_AXIS, build_mesh, global_mesh)
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.models.language_model import (
        make_rope_cache, model_forward)
    from megatron_llm_tpu.ops.paged_attention import PagedState
    from megatron_llm_tpu.parallel.tp import param_shardings

    mesh = build_mesh(tensor_model_parallel_size=tp, data_parallel_size=1,
                      devices=_topo_devices("v5e:2x2")[:tp])
    cfg = make_config("mistral-7b", num_layers=2, params_dtype="bfloat16",
                      vocab_size=32000, seq_length=1024,
                      tensor_model_parallel_size=tp)
    m = cfg.model
    slots, page, pre, rows = 8, 16, 64, 64
    width = cfg.data.seq_length // page
    shape = (m.num_layers, slots * width + 1, page,
             m.num_attention_heads_kv, m.kv_channels)
    heads_ax = TP_AXIS if tp > 1 else None
    repl = NamedSharding(mesh, P())
    pool = _abstract_pool(
        shape, "bf16", NamedSharding(mesh, P(None, None, None, heads_ax)),
        None)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params, param_shardings(mesh, params))
        tick = make_ragged_tick_fn(cfg, None, 0, pre, tp=tp, mesh=mesh)
        _compiles_with_kernel(
            tick, params, pool, S((slots, width), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), S((2, width), jnp.int32),
            S((pre,), jnp.int32), S((pre,), jnp.int32),
            donate_argnums=(1,))

        def chunk(params, tokens, start, bt, pool_kv):
            # the body of ContinuousBatchingEngine._chunk_prefill
            return model_forward(
                cfg, params, tokens,
                position_ids=start[:, None] + jnp.arange(rows)[None, :],
                rope_cache=make_rope_cache(cfg), kv_caches=pool_kv,
                paged=PagedState(bt, start), logits_postprocess=True)

        _compiles_with_kernel(
            chunk, params, S((1, rows), jnp.int32), S((1,), jnp.int32),
            S((1, 8), jnp.int32), pool, donate_argnums=(4,))


def test_aot_latent_tick_compiles_at_published_widths():
    """The ragged tick of JoyAI-LLM-Flash at its published widths (one
    dense + one expert layer, abstract parameters) compiles for one v5e:
    Mosaic takes the paged kernel with ONE shared latent leaf of 640 lanes
    (576 values) as key and value, and jax's grouped-matmul kernel with the
    whole expert stack as its operand; the program's temporaries stay far
    under one layer's experts (2.4 GB), i.e. nothing copies a stack."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("joyai-llm-flash", num_layers=1,
                      params_dtype="bfloat16", seq_length=1024)
    m = cfg.model
    slots, page, pre = 16, 16, 64
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pool = S((m.depth, slots * width + 1, page, 640), jnp.bfloat16)
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pool, S((slots, width), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), S((2, width), jnp.int32),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        text = lowered.as_text()
        assert "paged_attention" in text and "gmm" in text
        stats = lowered.compile().memory_analysis()
    assert stats.temp_size_in_bytes < 1 << 30


def test_aot_state_tick_compiles_at_published_widths():
    """The ragged tick of Brumby-14B at its published widths (one layer of
    the cell's four, the whole 151,936-row vocabulary, 40 slots, abstract
    parameters) compiles for one v5e: Mosaic takes the state sweep's kernel
    with a KV head's whole float32 state ``[128, 8704]`` as its block, the
    pool (two leaves, slot-indexed) is updated in place, and the program's
    temporaries are the feature rows and the logits, not a copy of the
    pool."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.ops import retention as ret

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("brumby-14b", num_layers=1, params_dtype="bfloat16",
                      seq_length=6144)
    m = cfg.model
    slots, pre = 40, 64
    big_d = ret.feature_dim(m.kv_channels)
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    lead = (m.num_layers, slots + 1, m.num_attention_heads_kv)
    pool = ret.State(S((*lead, m.kv_channels, big_d), jnp.float32),
                     S((*lead, 1, big_d), jnp.float32))
    pool_bytes = sum(np.prod(a.shape) * 4 for a in pool)
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pool, S((slots, 1), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), S((2, 1), jnp.int32),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        assert "retention_sweep" in lowered.as_text()
        assert "glu_stack_matmul" in lowered.as_text()
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes      # in place
    assert stats.temp_size_in_bytes < pool_bytes // 2   # and never copied
    # nor is a weight (PR 42): no copy or slice-only fusion the size of a
    # layer's smallest projection, where the parent wrote fc1 out twice
    # (340 MiB each) and QKV once transposed (70 MiB) a layer
    assert not _weights_moved(compiled, 32 << 20)


def test_aot_two_class_tick_compiles_at_published_widths():
    """The ragged tick of Command A+ at its published widths (one period:
    three window layers and the full one; 16 of 128 experts held; abstract
    parameters) compiles for one v5e with the pool as TWO leaves, one a
    page class, each behind its own block tables: Mosaic takes the paged
    kernel under both masks (128 query heads on 8 KV heads of 128) in one
    program, and the grouped kernel takes the held experts' stack where
    it lies."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("commanda-plus", num_layers=4, vocab_size=32768,
                      moe_experts_held=16, moe_capacity_factor=8.0,
                      params_dtype="bfloat16", seq_length=8192)
    slots, page, pre = 16, 16, 64
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    row = 2 * 8 * 128
    pools = (S((1, slots * width + 1, page, row), jnp.bfloat16),
             S((3, slots * 262 + 1, page, row), jnp.bfloat16))
    tables = lambda n: (S((n, width), jnp.int32),) * 2  # noqa: E731
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pools, tables(slots),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), tables(2),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        text = lowered.as_text()
        assert "paged_attention" in text and "gmm" in text
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    # the scopes a device trace tells the two masks' kernels apart by
    hlo = compiled.as_text()
    assert "attention/window" in hlo and "attention/global" in hlo
    # 0.2 GB as compiled.  Until PR 42 it was 1.8: XLA laid every layer's
    # QKV and shared-expert weights out anew before their projections, 0.42
    # GB a layer, all four at once (PERF.md section 6, PR 42).  Now the
    # tick closes over the dense stacks as over the held experts' (1.6 GB a
    # layer): QKV's dot reads its slice fused, the shared experts' GLU fc1
    # goes to the pair kernel, the grouped kernel reads the experts' stack
    assert "glu_stack_matmul" in text
    assert stats.temp_size_in_bytes < 1 << 29
    assert not _weights_moved(compiled, 32 << 20)
    out = compiled.output_shardings
    assert len(jax.tree.leaves(out)) == 2 + 5     # two leaves, four rows, moe


def test_aot_hybrid_tick_compiles_at_published_widths():
    """The ragged tick of GigaChat3.5 at its published widths (the cut the
    cell runs: one dense linear layer, then one period of a latent layer
    and three linear ones over 16 held experts of 256; 128 slots; abstract
    parameters) compiles for one v5e with a latent page leaf AND a state
    class (``DeltaState``: 64 value heads' ``[128, 128]`` float32 states and
    the conv's tail a slot) in ONE program: Mosaic takes the paged kernel's
    latent reading at 64 heads and the ``delta_sweep`` kernel, both pools
    are updated in place, and no weight is laid out anew."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.models.transformer import pool_classes
    from megatron_llm_tpu.ops.gated_delta import DeltaState

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("gigachat35-432b-a28b", num_layers=4,
                      dense_prefix_layers=1, vocab_size=16032,
                      moe_experts_held=16, moe_capacity_factor=16.0,
                      params_dtype="bfloat16", seq_length=6144)
    m = cfg.model
    page_cls, state_cls = pool_classes(cfg)
    assert (page_cls.layers(cfg), state_cls.layers(cfg)) == (1, 4)
    slots, page, pre = 128, 16, 128
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    channels = 2 * 32 * 128 + 64 * 128
    pools = (S((1, slots * width + 1, page, 640), jnp.bfloat16),
             DeltaState(S((4, slots + 1, 64, 128, 128), jnp.float32),
                        S((4 * (slots + 1), 3 * channels), jnp.float32)))
    state_bytes = sum(np.prod(a.shape) * 4 for a in pools[1])
    tables = lambda n: (S((n, width), jnp.int32),       # noqa: E731
                        S((n, 1), jnp.int32))
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        assert params["mixers"]["delta"]["qkvz"]["kernel"].shape == (
            3, 7168, 24576)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pools, tables(slots),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), tables(3),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        text = lowered.as_text()
        assert "delta_sweep" in text and "paged_attention" in text
        assert "gmm" in text and "glu_stack_matmul" in text
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    # the scopes and the kernel's name that the cell's readers match
    hlo = compiled.as_text()
    assert "attention/delta" in hlo and "attention/mla" in hlo
    assert "delta_sweep" in hlo
    assert stats.alias_size_in_bytes >= state_bytes     # in place
    assert stats.temp_size_in_bytes < 1 << 29           # and never copied
    # no weight and no pool leaf is laid out anew (the conv's tails, 101
    # MB, were: four times a tick as ``[layers, slots, 3, channels]``, once
    # as rows of 128 lanes; ops/gated_delta.DeltaState says what they are
    # now).  What is left over 32 MiB is the latent kernel's float32
    # query, 40 MiB at 64 heads x 640 lanes x 256 rows (JoyAI's 32 heads:
    # 20), so the line is drawn above it
    assert not _weights_moved(compiled, 48 << 20)


@pytest.mark.parametrize("model,vocab,heads,slots", [
    ("falcon-7b", 65024, (71, 1, 64), 128),
    ("mistral-7b", 32000, (32, 8, 128), 32)],
    ids=["falcon7b_mqa64", "mistral7b_gqa128"])
def test_aot_tick_keeps_the_pool_in_one_layout(model, vocab, heads, slots):
    """The ragged tick at published widths (2 layers, abstract bf16
    parameters, a pool of a few hundred MB) compiles for one v5e with the
    Pallas kernel in it and touches the pool in place: the compiled program
    holds no pad, copy, slice or transpose whose result has the pool's, a
    layer slice's or a layer's K (or V) slice's shape, and its temporaries
    do not grow with the pool — a pool four times the size adds well under
    ONE layer's K slice of the growth (the tied embedding's transposed copy
    and the sampler's vocabulary rows are temporaries of any pool).  CPU
    interpret mode cannot see any of this: it accepts every layout and plans
    no buffers."""
    import re

    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config

    n, nkv, d = heads
    row = 2 * nkv * d
    page, pre = 16, 128
    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    def compiled_tick(seq_length):
        cfg = make_config(model, num_layers=2, params_dtype="bfloat16",
                          vocab_size=vocab, seq_length=seq_length)
        m = cfg.model
        assert (m.num_attention_heads, m.num_attention_heads_kv,
                m.kv_channels) == heads
        width = seq_length // page
        pages = slots * width + 1
        pool = _abstract_pool((m.num_layers, pages, page, nkv, d), "bf16",
                              repl, None)
        assert pool.shape == (m.num_layers, pages, page, row)
        with global_mesh(mesh):
            params = jax.eval_shape(functools.partial(
                init_model_params, cfg), jax.random.PRNGKey(0))
            params = jax.tree.map(lambda a: S(a.shape, jnp.bfloat16), params)
            tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
            lowered = jax.jit(tick, donate_argnums=(1,)).lower(
                params, pool, S((slots, width), jnp.int32),
                S((slots,), jnp.int32), S((slots,), jnp.int32),
                S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
                S((slots,), jnp.float32), S((slots,), jnp.int32),
                S((slots,), jnp.float32), S((slots,), jnp.int32),
                S((slots,), jnp.bool_), S((pre,), jnp.int32),
                S((pre,), jnp.int32), S((pre // page + 1, width), jnp.int32),
                S((pre,), jnp.int32), S((pre,), jnp.int32))
            assert "paged_attention" in lowered.as_text()
            return pages, lowered.compile()

    pages, compiled = compiled_tick(2048)
    assert row % 128 == 0 and pages * page * row * 2 * 2 > 100 << 20
    pool_shapes = {
        f"2,{pages},{page},{row}",          # the pool
        f"{2 * pages},{page},{row}",        # its flat view
        f"{pages},{page},{row}",            # a layer's slice
        f"{pages},{page},{nkv * d}",        # a layer's K (or V)
        f"{pages},{page},{nkv},{d}",
    }
    moved = re.compile(
        r"= bf16\[([0-9,]+)\][^ ]* (pad|copy|dynamic-slice|transpose|"
        r"concatenate)\(")
    bad = [mt.group(0) for mt in moved.finditer(compiled.as_text())
           if mt.group(1) in pool_shapes]
    assert not bad, bad
    # the scatter that replaces them writes the flat view in place
    assert re.search(rf"bf16\[{2 * pages},{page},{row}\][^ ]* scatter\(",
                     compiled.as_text())

    pages4, compiled4 = compiled_tick(8192)
    k_slice_growth = (pages4 - pages) * page * nkv * d * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    temp4 = compiled4.memory_analysis().temp_size_in_bytes
    assert temp4 - temp < k_slice_growth // 8, (temp, temp4, k_slice_growth)


def test_aot_mamba_sweep_compiles_at_published_shapes():
    """Mosaic takes ``mamba_sweep`` at Nemotron-3-Nano's shapes: 64 heads
    of 64 on a state of 128 in 8 groups, a program's block four groups'
    ``[128, 2048]`` float32, 96 rows (32 decode rows and a 64-row prompt
    run) against 23 layers of 33 slots, in place."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.ops.pallas.mamba2 import mamba_sweep

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    r, h, p, g, n = 96, 64, 64, 8, 128
    pool = S((23, 33, n, h * p))
    with global_mesh(mesh):
        compiled = jax.jit(
            lambda *a: mamba_sweep(*a, layer=jnp.int32(5)),
            donate_argnums=(5,)).lower(
            S((r, h, p)), S((r, h)), S((r, h)), S((r, g, n)), S((r, g, n)),
            pool, S((r,), jnp.int32), S((r,), jnp.int32)).compile()
    stats = compiled.memory_analysis()
    assert "mamba_sweep" in compiled.as_text()
    assert stats.alias_size_in_bytes >= np.prod(pool.shape) * 4   # in place


def test_aot_sublayer_tick_compiles_at_published_widths_and_depth():
    """The ragged tick of NVIDIA-Nemotron-3-Nano-30B-A3B as its cell runs it
    (ALL 52 one-sublayer layers in the published order, 16 held experts of
    128, an eighth of the vocabulary, 32 slots and 64 prompt rows; abstract
    parameters) compiles for one v5e in ONE program: Mosaic takes
    ``mamba_sweep``, the paged kernel at GQA 32 / 2 and the grouped GEMMs
    at an expert width of 1,856 (off the 128 grid: a cut last tile), both
    pools are updated in place, no weight is laid out anew, and the
    repeated stretches are SCANNED and the units' six Mamba places share
    the jitted sweep: the program holds ONE lowered ``mamba_sweep``, not one
    a place (6) or a Mamba layer (23)."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.models.sublayers import stretches
    from megatron_llm_tpu.models.transformer import pool_classes
    from megatron_llm_tpu.ops.mamba2 import MambaState

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("nemotron_h-3-nano-30b-a3b", vocab_size=16384,
                      moe_experts_held=16, moe_capacity_factor=8.0,
                      params_dtype="bfloat16", seq_length=5152)
    m = cfg.model
    page_cls, state_cls = pool_classes(cfg)
    assert (page_cls.layers(cfg), state_cls.layers(cfg)) == (6, 23)
    units = stretches(m.sublayer_pattern)
    assert sum(len(u) * c for u, c in units) == 52
    bodies = sum(len(u) for u, _ in units)
    assert bodies == 14
    slots, page, pre = 32, 16, 64
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pools = (S((6, 4096, page, 2 * 2 * 128), jnp.bfloat16),
             MambaState(S((23, slots + 1, 128, 4096), jnp.float32),
                        S((23 * (slots + 1), 3 * 6144), jnp.float32)))
    state_bytes = sum(np.prod(a.shape) * 4 for a in pools[1])
    tables = lambda n: (S((n, width), jnp.int32),       # noqa: E731
                        S((n, 1), jnp.int32))
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        assert params["mixers"]["mamba"]["in_proj"]["kernel"].shape == (
            23, 2688, 4096 + 6144 + 64)
        assert params["mixers"]["experts"]["experts"]["fc1"][
            "kernel"].shape == (23, 16, 1856, 2688)   # transposed
        assert params["mixers"]["experts"]["shared"]["fc1"][
            "kernel"].shape == (23, 2688, 3712)
        assert params["mixers"]["experts"]["router"]["kernel"].shape == (
            23, 2688, 128)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pools, tables(slots),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), tables(2),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        text = lowered.as_text()
        assert "paged_attention" in text and "gmm" in text
        # the units' six Mamba places share ONE lowered kernel (the jitted
        # sweep, its layer an array): more means the sharing broke, and each
        # one more is a second of the cell's set-up (PERF.md 7ee)
        assert sum(u.count("M") for u, _ in units) == 6
        sweeps = text.count('kernel_name = "mamba_sweep"')
        assert sweeps == 1, sweeps
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    # the scopes and the kernel's name that the cell's readers match
    hlo = compiled.as_text()
    assert "/mamba/" in hlo and "/moe/" in hlo and "attention/global" in hlo
    assert "mamba_sweep" in hlo
    assert stats.alias_size_in_bytes >= state_bytes     # in place
    assert stats.temp_size_in_bytes < 1 << 29           # and never copied
    assert not _weights_moved(compiled, 48 << 20)


def test_aot_short_conv_tick_compiles_in_place_at_the_cells_shape():
    """``conv_tick`` as LFM2-24B-A2B's cell runs it (512 rows of 2,048
    channels against 30 layers' tails of 257 slots, bfloat16, a filter of
    three taps) compiles for one v5e: the tails are updated in place, the
    write is ``_put_rows``'s blocks and no loop of one row a step over the
    512, and nothing the size of the pool is a temporary."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.ops import gated_delta as gd

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    r, ch, slots, layers = 512, 2048, 257, 30
    tails = S((layers * slots, 2 * ch))
    with global_mesh(mesh):
        compiled = jax.jit(
            lambda x, w, t, s, p: gd.conv_tick(x, w, t, s, p, 7 * slots),
            donate_argnums=(2,)).lower(
            S((r, ch)), S((3, ch)), tails, S((r,), jnp.int32),
            S((r,), jnp.int32)).compile()
    stats = compiled.memory_analysis()
    pool_bytes = layers * slots * 2 * ch * 2
    assert stats.alias_size_in_bytes >= pool_bytes           # in place
    assert stats.temp_size_in_bytes < pool_bytes // 2, stats.temp_size_in_bytes


def test_aot_lfm2_tick_compiles_at_published_widths_and_depth():
    """The ragged tick of LFM2-24B-A2B as its cell runs it (ALL 80
    sublayers of the 40 layers in the published order, 8 held experts of 64,
    the whole vocabulary, 256 slots and 256 prompt rows; abstract
    parameters) compiles for one v5e in ONE program: the paged kernel at
    GQA 32 / 8 heads of 64 with QK-normed rotated keys, the grouped GEMMs at
    64 experts of 1,536, the short-conv mixers through ``conv_tick``; both
    pools are updated in place, no weight is laid out anew, and the stack is
    three scans of fourteen sublayer bodies."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.models.sublayers import stretches
    from megatron_llm_tpu.models.transformer import pool_classes
    from megatron_llm_tpu.ops.gated_delta import ConvTail

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("lfm2-24b-a2b", moe_experts_held=8,
                      moe_capacity_factor=8.0, params_dtype="bfloat16",
                      seq_length=5152)
    m = cfg.model
    page_cls, state_cls = pool_classes(cfg)
    assert (page_cls.layers(cfg), state_cls.layers(cfg)) == (10, 30)
    units = stretches(m.sublayer_pattern)
    assert sum(len(u) * c for u, c in units) == 80
    assert sum(len(u) for u, _ in units) == 14
    slots, page, pre, pages = 256, 16, 256, 18433
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pools = (S((10, pages, page, 2 * 8 * 64), jnp.bfloat16),
             ConvTail(S((30 * (slots + 1), 2 * 2048), jnp.bfloat16)))
    pool_bytes = sum(np.prod(a.shape) * 2 for a in (pools[0], *pools[1]))
    tables = lambda n: (S((n, width), jnp.int32),       # noqa: E731
                        S((n, 1), jnp.int32))
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params)
        assert params["mixers"]["conv"]["in_proj"]["kernel"].shape == (
            30, 2048, 6144)
        assert params["mixers"]["experts"]["experts"]["fc1"][
            "kernel"].shape == (38, 8, 2, 2048, 1536)
        assert params["mixers"]["experts"]["router"]["kernel"].shape == (
            38, 2048, 64)
        assert params["mixers"]["mlp"]["fc1"]["kernel"].shape == (
            2, 2048, 2, 11776)
        assert "lm_head" not in params                       # tied
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pools, tables(slots),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), tables(2),
            S((pre,), jnp.int32), S((pre,), jnp.int32))
        text = lowered.as_text()
        assert "paged_attention" in text and "gmm" in text
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    # the scopes that the cell's readers match
    hlo = compiled.as_text()
    for scope in ("/short_conv/in_proj", "/short_conv/conv",
                  "/short_conv/out_proj", "/moe/expert_gemm",
                  "attention/global"):
        assert scope in hlo, scope
    assert stats.alias_size_in_bytes >= pool_bytes      # in place
    assert stats.temp_size_in_bytes < 1 << 30           # and never copied
    # no bfloat16 operand the size of a layer's smallest projection stack
    # is laid out anew (the sampler's sort over 256 x 65,536 float32 logits
    # moves 64 MiB of ITS temporaries, under a branch greedy rows skip)
    assert not _weights_moved(compiled, 16 << 20, "bf16")


def test_aot_block_tick_compiles_at_published_widths_and_depth():
    """The block tick of SDAR-30B-A3B-Chat as its cell runs it (ALL 48
    layers, 16 held experts of 128, an eighth of the vocabulary, 32 slots x
    2 x 4 block rows and 64 prompt rows; abstract parameters) compiles for
    one v5e in ONE program: the paged kernel at GQA 32 / 4 heads of 128 on
    rows whose mask position is their block's last, the grouped GEMMs at
    128 experts of 768, the head on the denoise rows alone; the pool is
    updated in place and the scopes the cell's readers match are there."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.blocks import (
        BlockState,
        Unmasking,
        make_block_tick_fn,
    )
    from megatron_llm_tpu.models import init_model_params, make_config

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("sdar-30b-a3b-chat", moe_experts_held=16,
                      moe_capacity_factor=8.0, vocab_size=18992,
                      mask_token_id=18991, params_dtype="bfloat16",
                      seq_length=5152)
    slots, page, pre, pages, B = 32, 16, 64, 2561, 4
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pool = S((48, pages, page, 2 * 4 * 128), jnp.bfloat16)
    pool_bytes = np.prod(pool.shape) * 2
    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    state = BlockState(S((slots,), i32), S((slots, B), i32),
                       S((slots, B), flag), S((slots, B), i32),
                       S((slots,), flag), S((slots,), i32),
                       S((slots,), flag), S((slots,), i32))
    un = Unmasking(S((slots,), f32), S((slots,), i32), S((slots,), f32),
                   S((slots,), i32), S((slots,), i32), S((slots,), f32))
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a: S(a.shape, jnp.bfloat16), params)
        assert params["layers"]["moe"]["experts"]["fc1"]["kernel"].shape == (
            48, 16, 2, 2048, 768)
        assert params["layers"]["moe"]["router"]["kernel"].shape == (
            48, 2048, 128)
        assert params["lm_head"]["kernel"].shape == (2048, 19072)
        lowered = jax.jit(make_block_tick_fn(cfg, pre),
                          donate_argnums=(1,)).lower(
            params, pool, S((slots, width), i32), state,
            S((slots,), flag), state, S((slots, 2), jnp.uint32), un,
            S((pre,), i32), S((pre,), i32), S((2, width), i32),
            S((pre,), i32))
        text = lowered.as_text()
        assert "paged_attention" in text and "gmm" in text
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    hlo = compiled.as_text()
    for scope in ("block_unmask", "block_commit", "/moe/expert_gemm",
                  "lm_head_loss"):
        assert scope in hlo, scope
    assert stats.alias_size_in_bytes >= pool_bytes      # in place
    assert stats.temp_size_in_bytes < 1 << 30           # and never copied


@pytest.mark.parametrize("pre", (0, 64))
def test_aot_looped_tick_compiles_in_place_at_published_widths(pre):
    """The ragged tick of Ouro-2.6B as its cell runs it (the WHOLE model: 48
    layers x 4 passes, every width, the whole vocabulary, 16 slots and 0 or
    64 prompt rows, 321 pages of 24 MiB over 192 layer slots; abstract
    parameters) compiles for one v5e in ONE program: the paged kernel at 16
    K/V heads with a group of ONE query head each, ONE layer body under TWO
    nested loops (passes around layers), the 8 GB pool riding both loops'
    carries in place, and both named scopes where the cell's readers look."""
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config

    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    cfg = make_config("ouro-2.6b", params_dtype="bfloat16", seq_length=576)
    m = cfg.model
    assert (m.depth, m.loop_steps, m.cache_layer_slots) == (48, 4, 192)
    slots, page, pages = 16, 16, 321
    width = cfg.data.seq_length // page
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    pool = S((192, pages, page, 2 * 16 * 128), jnp.bfloat16)
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert pool_bytes == pages * 16 * 1_572_864
    with global_mesh(mesh):
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(params)) == 2_667_974_657
        params = jax.tree.map(lambda a: S(a.shape, jnp.bfloat16), params)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        pre_args = (S((pre,), jnp.int32), S((pre,), jnp.int32),
                    S((pre // 64 + 1, width), jnp.int32),
                    S((pre,), jnp.int32), S((pre,), jnp.int32)) if pre else ()
        lowered = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pool, S((slots, width), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), *pre_args)
        assert "paged_attention" in lowered.as_text()
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
    hlo = compiled.as_text()
    for scope in ("/loop_pass/", "/loop_norm_gate/", "lm_head_loss"):
        assert scope in hlo, scope
    # ONE layer body: two loops (the passes, the layers), two kernels (the
    # paged one, the GLU fc1's), however many passes and layers run
    assert hlo.count(" while(") == 2
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert stats.alias_size_in_bytes >= pool_bytes      # in place
    assert stats.temp_size_in_bytes < 1 << 28           # and never copied
    assert not _weights_moved(compiled, 16 << 20, "bf16")
    # the exit masses ride out beside the tokens: [slots, passes]
    assert lowered.out_info[-1].shape == (slots, 4)


# ---------------------------------------------------------------------------
# the word-embedding table's layout (generation/placement.py, PR 64)
# ---------------------------------------------------------------------------

_TABLE_COPY = r"= bf16\[65024,4544\]\S* copy\("


def test_aot_table_rule_on_a_described_chip():
    """What the chip's compiler makes of a 2-D bf16 array by default, asked
    of a compile-only client, and what the rule answers: Falcon's table
    (4,544 = 35.5 x 128 lanes) lies vocabulary-minor and is wanted in rows
    with the tiling it had; every table whose hidden size is whole lanes
    lies in rows and is left alone.  A lone ``take`` + tied head then
    compiles with a copy of the whole table in the one layout and with none
    in the other, the gather and the head's dot reading the parameter."""
    import re

    from megatron_llm_tpu.generation import placement

    sh = jax.sharding.SingleDeviceSharding(_topo_devices("v5e:2x2")[0])

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    falcon = S((65024, 4544))
    assert placement.device_layout(falcon).major_to_minor == (1, 0)
    fmt = placement.rows_format(falcon)
    assert fmt.layout.major_to_minor == (0, 1)
    assert fmt.layout.tiling == ((8, 128), (2, 1)) and fmt.sharding == sh
    for shape in ((32000, 4096), (131072, 2048), (151936, 5120),
                  (65024, 4608)):
        leaf = S(shape)
        assert placement.in_rows(leaf) is leaf, shape
    # the other leaves a v5e lays out minor-axis-first are untied heads
    # ``[h, v]`` with v no whole number of lanes: nothing gathers from them
    assert placement.device_layout(S((2048, 16032))).major_to_minor == (1, 0)

    def lookup_and_head(table, ids, x):
        return jnp.take(table, ids, axis=0).sum(), x @ table.T

    program = jax.jit(lookup_and_head)
    texts = {name: program.lower(
        table, S((256,), jnp.int32), S((128, 4544))).compile().as_text()
        for name, table in (("default", falcon),
                            ("rows", placement.in_rows(falcon)))}
    assert "bf16[65024,4544]{0,1:T(8,128)(2,1)}" in texts["default"].split(
        "\n", 1)[0]
    assert len(re.findall(_TABLE_COPY, texts["default"])) == 1
    assert "bf16[65024,4544]{1,0:T(8,128)(2,1)}" in texts["rows"].split(
        "\n", 1)[0]
    assert not re.findall(_TABLE_COPY, texts["rows"])


def test_aot_falcon_tick_reads_the_table_as_it_lies():
    """The ragged tick at Falcon's widths (2 layers, 128 prompt rows) on
    abstract parameters put through the engine's own placement call: the
    table enters in rows and no operation of the compiled tick copies it
    (the parent's tick wrote 564 MiB a launch, ``copy.222``)."""
    import re

    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.placement import tables_in_rows
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params, make_config

    slots, page, pre, width = 128, 16, 128, 128
    mesh = build_mesh(devices=_topo_devices("v5e:2x2")[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    cfg = make_config("falcon-7b", num_layers=2, params_dtype="bfloat16",
                      vocab_size=65024, seq_length=2048)
    pool = _abstract_pool((2, slots * width + 1, page, 1, 64), "bf16", repl,
                          None)
    with global_mesh(mesh):
        params = jax.eval_shape(functools.partial(
            init_model_params, cfg), jax.random.PRNGKey(0))
        params, = tables_in_rows(jax.tree.map(
            lambda a: S(a.shape, jnp.bfloat16), params))
        table = params["embedding"]["word_embeddings"]
        assert table.format.layout.major_to_minor == (0, 1)
        tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        text = jax.jit(tick, donate_argnums=(1,)).lower(
            params, pool, S((slots, width), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.int32),
            S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.float32), S((slots,), jnp.int32),
            S((slots,), jnp.bool_), S((pre,), jnp.int32),
            S((pre,), jnp.int32), S((pre // page + 1, width), jnp.int32),
            S((pre,), jnp.int32), S((pre,), jnp.int32)).compile().as_text()
    assert "bf16[65024,4544]{1,0:T(8,128)(2,1)}" in text.split("\n", 1)[0]
    assert not re.findall(_TABLE_COPY, text)
