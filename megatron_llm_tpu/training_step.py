"""The sharded training step — the hot loop.

Analog of the reference train_step (megatron/training.py:393-460): zero grads,
microbatched forward/backward with grad accumulation, grad all-reduce,
optimizer step, param gather. Under XLA SPMD the whole sequence is ONE jitted
program over the (dp, pp, cp, tp) mesh:

* DP grad all-reduce (model/distributed.py:202-232)        -> emitted by XLA
  from the dp-replicated-params / dp-sharded-batch contraction
* distributed-optimizer reduce-scatter + all-gather
  (distrib_optimizer.py:527-615)                           -> emitted by XLA
  from dp-sharded Adam state (opt_state_partition_specs)
* TP all-reduces (mappings.py) and SP gather/scatter       -> emitted by XLA
  from the param/activation shardings in parallel/tp.py
* microbatch grad accumulation loop (schedules.py:213-250
  no-pipelining schedule)                                  -> lax.scan below

Pipeline-parallel schedules extend this in parallel/pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu.core import rng as rng_mod
from megatron_llm_tpu.models.language_model import (
    LOOP_NOT_TRAINED,
    loss_from_batch,
    make_rope_cache,
)
from megatron_llm_tpu.optimizer.optimizer import (
    get_optimizer,
    global_grad_norm,
    opt_state_shardings,
)
from megatron_llm_tpu.optimizer.scheduler import lr_schedule
from megatron_llm_tpu.parallel.tp import (
    batch_shardings,
    data_spec,
    make_sp_constraint,
    param_shardings,
)


def _split_microbatches(batch: Dict[str, jax.Array], num_micro: int):
    """[gbs, ...] -> [num_micro, gbs/num_micro, ...] for scan.

    ``token_idx`` (the [s] zigzag index vector, parallel/ring.py) is batch-
    invariant and is broadcast to every microbatch rather than split.
    """
    batch = dict(batch)
    token_idx = batch.pop("token_idx", None)

    def r(x):
        gbs = x.shape[0]
        assert gbs % num_micro == 0, f"batch {gbs} % num_micro {num_micro} != 0"
        return x.reshape(num_micro, gbs // num_micro, *x.shape[1:])

    out = jax.tree.map(r, batch)
    if token_idx is not None:
        out["token_idx"] = jnp.broadcast_to(
            token_idx[None], (num_micro, *token_idx.shape)
        )
    return out


def make_train_step(cfg, optimizer: Optional[optax.GradientTransformation] = None,
                    mesh: Optional[Mesh] = None,
                    num_micro: Optional[int] = None,
                    loss_fn=None, pipeline_hooks=None, pipeline_loss=None):
    """Build the pure train_step(params, opt_state, batch, iteration, seed).

    Returns (loss-averaged-over-microbatches, metrics dict) alongside the new
    (params, opt_state) — the reference's train_step contract
    (training.py:393: loss dict, skipped-iter flag, grad_norm, num_zeros).

    ``num_micro`` overrides cfg.parallel.num_micro_batches (batch-size
    ramp-up builds one step per stage, microbatches.py semantics).

    ``pipeline_hooks`` enables non-GPT losses under pipeline parallelism
    (the reference's schedules are loss-agnostic via forward_step_func;
    here a hooks builder ``(cfg, batch) -> (pipe_batch, embed_fn,
    head_loss_fn)`` maps the family's batch onto the pipeline engine's
    tokens/labels/loss_mask/aux contract — see
    models/bert.py:bert_pipeline_hooks).

    ``pipeline_loss`` replaces the schedule entirely for topologies the
    single-stack engine cannot express (T5's encoder+decoder:
    models/t5.py:t5_pipeline_loss_fn); signature ``(cfg, mesh, params,
    batch, num_micro=, dropout_key=) -> (loss, metrics)``, differentiated
    GPipe-style.
    """
    if cfg.model.loop_steps > 1:
        raise ValueError(LOOP_NOT_TRAINED.format(loops=cfg.model.loop_steps))
    sp_constraint = make_sp_constraint(cfg)
    lr_fn = lr_schedule(cfg)
    if num_micro is None:
        num_micro = cfg.parallel.num_micro_batches or 1
    # pluggable loss (BERT/T5 entry points pass bert_loss_from_batch /
    # t5_loss_from_batch; default is the GPT-family LM loss)
    if loss_fn is None:
        loss_fn = loss_from_batch

    # Name the forward region by its tp degree: the column/row-parallel
    # collectives GSPMD inserts inherit this scope in their HLO op
    # metadata, so device profiles (observability/profiler.py) attribute
    # the TP all-reduces to the forward instead of an anonymous fusion.
    # With --tp_overlap ring the scope carries the overlap marker
    # (forward-tp{N}-overlap) and the sublayers' row/column projections
    # run as chunked collective-matmul rings (parallel/overlap.py).
    from megatron_llm_tpu.parallel import overlap as tp_overlap_mod

    _tp_deg = (mesh.shape.get("tp", 1) if mesh is not None else 1)
    _ovl = tp_overlap_mod.overlap_params(cfg, mesh)
    if _ovl is not None:
        _fwd_scope = tp_overlap_mod.overlap_scope_name(_tp_deg)
    else:
        _fwd_scope = "forward" if _tp_deg == 1 else f"forward-tp{_tp_deg}"

    def micro_loss(params, mb, dropout_key, rope):
        deterministic = (
            cfg.model.hidden_dropout == 0.0 and cfg.model.attention_dropout == 0.0
        ) or dropout_key is None
        with jax.named_scope(_fwd_scope), tp_overlap_mod.activate(_ovl):
            return loss_fn(
                cfg, params, mb,
                dropout_key=dropout_key,
                deterministic=deterministic,
                rope_cache=rope,
                sp_constraint=sp_constraint,
            )

    pp = cfg.parallel.pipeline_model_parallel_size

    # quantized DP gradient sync (parallel/quantized.py, ISSUE 13): an
    # explicit int8 reduce-scatter + all-gather inside a full-manual
    # shard_map replaces the implicit bf16 all-reduce XLA emits from the
    # replicated-params / dp-sharded-batch contraction.  Flag-gated and
    # dp-pure-mesh-only; pipeline configs keep their own schedules.
    qdp_fn = None
    if getattr(cfg.training, "quantized_grad_allreduce", False) and pp == 1:
        from megatron_llm_tpu.parallel.quantized import (
            make_quantized_dp_grad_fn,
            quantized_dp_supported,
        )

        if quantized_dp_supported(cfg, mesh):
            qdp_fn = make_quantized_dp_grad_fn(
                cfg, mesh, loss_fn, num_micro, fwd_scope=_fwd_scope)

    def train_step(params, opt_state, batch, iteration, opt=optimizer):
        if opt is None:
            raise ValueError("optimizer must be bound via make_train_step or arg")
        rope = make_rope_cache(cfg)
        base_key = rng_mod.dropout_key(cfg.training.seed, iteration)

        # fp16: multiply the loss by the current scale (read from the scaler
        # state inside opt_state); grads are un-scaled in the optimizer wrapper
        # (optimizer/grad_scaler.py).
        from megatron_llm_tpu.optimizer.grad_scaler import find_scaler_state

        scaler = find_scaler_state(opt_state)
        scale = scaler.loss_scale if scaler is not None else jnp.float32(1.0)
        inv_scale = 1.0 / scale

        def scaled_loss(p, mb, k):
            l, mets = micro_loss(p, mb, k, rope)
            # mets carries the loss_fn's reporting dict (bare CE as "lm loss",
            # MoE router losses, ...) — unscaled raw values
            return l * jax.lax.stop_gradient(scale), mets

        grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

        loss_mets = None
        grad_prescale = 1.0  # != 1 only on the fused grad-accumulation path
        if pp > 1 and pipeline_loss is not None:
            # family-owned pipeline (T5 encoder+decoder): differentiated
            # GPipe-style as one program
            assert cfg.parallel.pipeline_schedule == "gpipe", (
                "custom pipeline_loss implementations are GPipe-only"
            )
            deterministic = (
                cfg.model.hidden_dropout == 0.0
                and cfg.model.attention_dropout == 0.0
            )
            def scaled_pipe(p):
                l, mets = pipeline_loss(
                    cfg, mesh, p, batch, num_micro=num_micro,
                    dropout_key=None if deterministic else base_key,
                )
                return l * jax.lax.stop_gradient(scale), mets

            (loss, loss_mets), grads = jax.value_and_grad(
                scaled_pipe, has_aux=True
            )(params)
        elif pp > 1:
            # pipelined path: the microbatch loop lives inside the pipeline
            assert loss_fn is loss_from_batch or pipeline_hooks is not None, (
                "pipeline parallelism needs the GPT-family LM loss or a "
                "pipeline_hooks builder for the family (models/bert.py:"
                "bert_pipeline_hooks is the template)"
            )
            pipe_batch, embed_fn, head_loss_fn = (
                pipeline_hooks(cfg, batch) if pipeline_hooks is not None
                else (batch, None, None)
            )
            deterministic = (
                cfg.model.hidden_dropout == 0.0
                and cfg.model.attention_dropout == 0.0
            )
            vpp = cfg.parallel.virtual_pipeline_model_parallel_size or 1
            if cfg.parallel.pipeline_schedule == "1f1b" and vpp > 1:
                # interleaved 1F1B: virtual stages cut the bubble by v while
                # keeping O(V) in-flight activations (ref schedules.py:253-502)
                from megatron_llm_tpu.parallel.pipeline import (
                    pipeline_1f1b_interleaved_loss_and_grads,
                )

                loss, grads, loss_mets = pipeline_1f1b_interleaved_loss_and_grads(
                    cfg, mesh, params, pipe_batch, rope=rope,
                    loss_scale=jax.lax.stop_gradient(scale),
                    num_micro=num_micro,
                    dropout_key=None if deterministic else base_key,
                    embed_fn=embed_fn, head_loss_fn=head_loss_fn,
                    with_metrics=True,
                )
            elif cfg.parallel.pipeline_schedule == "1f1b":
                # true 1F1B: grads computed inside the tick loop, O(pp)
                # activation memory (parallel/pipeline.py)
                from megatron_llm_tpu.parallel.pipeline import (
                    pipeline_1f1b_loss_and_grads,
                )

                loss, grads, loss_mets = pipeline_1f1b_loss_and_grads(
                    cfg, mesh, params, pipe_batch, rope=rope,
                    loss_scale=jax.lax.stop_gradient(scale),
                    num_micro=num_micro,
                    dropout_key=None if deterministic else base_key,
                    embed_fn=embed_fn, head_loss_fn=head_loss_fn,
                    with_metrics=True,
                )
            else:
                # GPipe-style: autodiff through the tick scan; metrics
                # (MoE router losses etc.) ride through has_aux
                from megatron_llm_tpu.parallel.pipeline import pipeline_loss_fn

                def scaled_gpipe(p):
                    l, mets = pipeline_loss_fn(
                        cfg, mesh, p, pipe_batch,
                        dropout_key=None if deterministic else base_key,
                        deterministic=deterministic, rope=rope,
                        sp_constraint=sp_constraint, num_micro=num_micro,
                        embed_fn=embed_fn, head_loss_fn=head_loss_fn,
                    )
                    return l * jax.lax.stop_gradient(scale), mets

                (loss, loss_mets), grads = jax.value_and_grad(
                    scaled_gpipe, has_aux=True
                )(params)
        elif qdp_fn is not None:
            # per-rank local grads + explicit int8 quantized dp sync
            # (microbatch accumulation handled inside the manual region)
            (loss, loss_mets), grads = qdp_fn(params, batch, base_key,
                                              scale)
        elif num_micro == 1:
            (loss, loss_mets), grads = grad_fn(params, batch, base_key)
        else:
            mbs = _split_microbatches(batch, num_micro)

            # fp32 accumulation is the reference default (main_grad,
            # distributed.py:111-157); accumulate_allreduce_grads_in_fp32 =
            # False accumulates in the compute dtype instead — halves the
            # accumulator, which is what fits 7B TP=8 on 16-GiB v5e chips
            accum_dtype = None
            if not cfg.training.accumulate_allreduce_grads_in_fp32:
                from megatron_llm_tpu.models.language_model import _compute_dtype

                accum_dtype = _compute_dtype(cfg)

            def to_accum(g):
                return g.astype(accum_dtype) if accum_dtype else g

            def accum(carry, xs):
                g_sum, loss_sum, m_sum = carry
                mb, idx = xs
                (l, mets), g = grad_fn(params, mb, jax.random.fold_in(base_key, idx))
                return (jax.tree.map(lambda s, gg: s + to_accum(gg), g_sum, g),
                        loss_sum + l,
                        jax.tree.map(jnp.add, m_sum, mets)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(
                    p.shape, accum_dtype if accum_dtype else p.dtype),
                params)
            first_mb = jax.tree.map(lambda a: a[0], mbs)
            mets0 = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(
                    lambda p, mb: micro_loss(p, mb, base_key, rope)[1],
                    params, first_mb,
                ),
            )
            (g_sum, loss_sum, m_sum), _ = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32), mets0),
                (mbs, jnp.arange(num_micro)),
            )
            inv = 1.0 / num_micro
            if getattr(opt, "fused_apply", None) is not None:
                # the fused optimizer folds the 1/num_micro average in
                # (prescale) — dividing here would materialize another
                # full-size grad tree
                grads = g_sum
                grad_prescale = inv
            else:
                grads = jax.tree.map(lambda g: g * inv, g_sum)
            loss = loss_sum * inv
            loss_mets = jax.tree.map(lambda x: x * inv, m_sum)

        loss = loss * inv_scale  # report the un-scaled loss
        # named scopes surface as labeled regions in jax.profiler xplane
        # traces — the analog of the reference's optimizer span timers
        # (training.py:500-525)
        with jax.named_scope("optimizer"):
            grad_norm = global_grad_norm(grads) * (grad_prescale * inv_scale)
            fused = getattr(opt, "fused_apply", None)
            if fused is not None:
                # memory-bounded in-place apply (optimizer.scanned_adam):
                # params/moments updated slice-wise on the donated buffers
                new_params, new_opt_state = fused(
                    grads, opt_state, params, prescale=grad_prescale)
            else:
                updates, new_opt_state = opt.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
        metrics = {
            "lm loss": loss,
            "grad_norm": grad_norm,
            "learning_rate": lr_fn(iteration),
        }
        if loss_mets is not None:
            # loss_fn reporting dict (bare CE, MoE router losses, ...)
            metrics.update(loss_mets)
        if cfg.logging.log_num_zeros_in_grad:
            from megatron_llm_tpu.optimizer.optimizer import count_zeros

            metrics["num_zeros"] = count_zeros(grads)
        if cfg.logging.log_params_norm:
            # calc_params_l2_norm analog (reference utils.py:38)
            metrics["params_norm"] = optax.global_norm(new_params)
        if scaler is not None:
            new_scaler = find_scaler_state(new_opt_state)
            metrics["loss_scale"] = new_scaler.loss_scale
            metrics["skipped_iterations"] = new_scaler.skipped_total
            metrics["skipped_iter"] = new_scaler.last_skipped.astype(jnp.int32)
        return new_params, new_opt_state, metrics

    return train_step


def make_jitted_train_step(cfg, mesh: Mesh, params: Any,
                           num_micro: Optional[int] = None,
                           optimizer: Optional[optax.GradientTransformation] = None,
                           opt_state: Any = None,
                           loss_fn=None, pipeline_hooks=None,
                           pipeline_loss=None):
    """Bind shardings and jit. Returns (step_fn, optimizer, shardings dict).

    Donates params/opt_state (the XLA analog of the reference's in-place
    param update + contiguous grad buffer reuse, distributed.py:111-157).
    ``num_micro``/``optimizer``/``opt_state`` overrides support batch-size
    ramp-up (one compiled step per stage, sharing one optimizer/state).
    """
    if optimizer is None:
        optimizer = get_optimizer(cfg, params)
    p_shard = param_shardings(mesh, params)
    if opt_state is None:
        o_shard = opt_state_shardings(
            cfg, mesh, params, jax.eval_shape(optimizer.init, params))
        # born under its sharding: an eagerly initialized state is typed
        # without the mesh, so the second step — fed the first step's
        # mesh-typed outputs — would retrace and compile the program again
        opt_state = jax.jit(optimizer.init, out_shardings=o_shard)(params)
    else:
        o_shard = opt_state_shardings(cfg, mesh, params, opt_state)
    cp = cfg.parallel.context_parallel_size > 1
    b_shard = NamedSharding(mesh, data_spec(cp))
    scalar = NamedSharding(mesh, P())

    step = make_train_step(cfg, optimizer, mesh=mesh, num_micro=num_micro,
                           loss_fn=loss_fn, pipeline_hooks=pipeline_hooks,
                           pipeline_loss=pipeline_loss)
    # batch in_sharding is UNSPECIFIED (follows the committed input): batches
    # may carry the [s] token_idx vector whose sharding differs per key —
    # callers place batches with place_batch / batch_shardings.
    jstep = jax.jit(
        step,
        in_shardings=(p_shard, o_shard, None, scalar),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1),
    )

    # sharding specs depend only on (key, ndim), so cache them: placement
    # runs once per step on the data path's critical thread (inline in the
    # blocking loop, on the prefetch worker in the overlapped loop —
    # data/prefetch.py) and must stay a dict lookup, not a spec rebuild
    shard_cache: Dict[tuple, Any] = {}

    def place_batch(batch):
        import numpy as np

        from megatron_llm_tpu.observability import registry as obs_registry
        from megatron_llm_tpu.observability import trace as obs_trace

        # traced + counted (observability/): this runs on the prefetch
        # worker in the overlapped loop, so the span lands on that
        # thread's track and the counter exercises the registry's
        # cross-thread path.  device_put is async — still sync-free.
        with obs_trace.span("place-batch"):
            key = tuple(sorted(
                (k, int(np.ndim(v))) for k, v in batch.items()))
            sh = shard_cache.get(key)
            if sh is None:
                sh = shard_cache[key] = batch_shardings(cfg, mesh, batch)
            if jax.process_count() > 1:
                # multi-host: hosts hold only their rows of the global
                # batch (core/distributed.process_batch_slice); assemble
                # global arrays
                from megatron_llm_tpu.core.distributed import (
                    place_host_local_batch,
                )

                placed = place_host_local_batch(batch, sh)
            else:
                placed = jax.device_put(batch, sh)
        if obs_registry.publishing():
            obs_registry.get_registry().counter(
                "mlt_batches_placed_total",
                help="batches staged on device by place_batch").inc()
        return placed

    return jstep, optimizer, {
        "params": p_shard,
        "opt_state": o_shard,
        "batch": b_shard,
        "place_batch": place_batch,
        "opt_state_value": opt_state,
    }


def measure_span_breakdown(cfg, params, batch, step_time_s: float,
                           loss_fn=None, reps: int = 3):
    """One-off forward/backward/optimizer wall-clock split.

    The analog of the reference's per-span timer readout (training.py:500-525)
    — a single jitted step cannot be split from the host, so this times two
    auxiliary programs (forward-only, forward+backward) and attributes the
    rest of ``step_time_s`` to the optimizer. Compiles two extra programs:
    call once, behind timing_log_level >= 2. Returns dict of seconds or None
    for pipelined configs (spans interleave; use the xplane trace instead).
    """
    import time

    if cfg.parallel.pipeline_model_parallel_size > 1:
        return None
    from megatron_llm_tpu.models.language_model import (
        loss_from_batch as default_loss,
        make_rope_cache,
    )

    lf = loss_fn or default_loss
    rope = make_rope_cache(cfg)
    sp_constraint = make_sp_constraint(cfg)

    # time ONE microbatch and scale: the real step scans num_micro of them,
    # and a monolithic full-global-batch program would need num_micro x the
    # activation memory the tuned step was sized for
    num_micro = cfg.parallel.num_micro_batches or 1
    if num_micro > 1:
        batch = _split_microbatches(batch, num_micro)
        batch = jax.tree.map(lambda a: a[0], batch)

    def loss_only(p, b):
        return lf(cfg, p, b, deterministic=True, rope_cache=rope,
                  sp_constraint=sp_constraint)[0]

    fwd = jax.jit(loss_only)
    fwdbwd = jax.jit(jax.value_and_grad(loss_only))

    def best_of(fn):
        fn(params, batch)  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(params, batch)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best

    t_fwd = best_of(fwd) * num_micro
    t_fwdbwd = best_of(fwdbwd) * num_micro
    return {
        "forward": t_fwd,
        "backward": max(t_fwdbwd - t_fwd, 0.0),
        "optimizer": max(step_time_s - t_fwdbwd, 0.0),
    }


def init_sharded(cfg, mesh: Mesh, init_fn, key: jax.Array):
    """Initialize params directly sharded (no host-side full materialization).

    jit-of-init with out_shardings — the analog of the reference's
    use_cpu_initialization + scatter, but single-program.
    """
    shapes = jax.eval_shape(init_fn, key)
    shardings = param_shardings(mesh, shapes)
    return jax.jit(init_fn, out_shardings=shardings)(key), shardings
