"""Training driver: the ``pretrain`` orchestration loop.

Reference: megatron/training.py — ``pretrain``:55, ``train_step``:393 (ours is
jitted whole in training_step.py), ``_train`` loop:654 (eval :713,
signal-exit :731, save :739, time/iter exits :746-767), ``evaluate``:773,
``training_log``:462 with tokens/sec (:591-609).

Single-controller redesign: no rank gymnastics (is-last-rank printing, TP-rank
data broadcast, all-reduced exit flags) — one process drives the mesh; exit
decisions are plain Python.

Async loop (ISSUE 2): the hot loop rides JAX's async dispatch so the host
never sits between device steps — metrics stay on device in a bounded
in-flight deque (--async_dispatch_depth) and are fetched in ONE batched
``jax.device_get`` at log_interval boundaries; batches are collated and
placed ahead of time on a background thread (data/prefetch.py,
--prefetch_depth); checkpoint writes are deferred to a writer thread behind
a host snapshot (--async_save, checkpointing.AsyncCheckpointSaver).  The
numerical trajectory is bitwise-identical to the synchronous loop
(tests/test_async_loop.py) — only WHEN the host observes results changes,
never what the device computes.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from megatron_llm_tpu.checkpointing import (
    AsyncCheckpointSaver,
    load_checkpoint,
    save_checkpoint,
)
from megatron_llm_tpu.core.parallel_state import (
    build_mesh_from_config,
    get_global_mesh,
    global_mesh,
    placement_report,
)
from megatron_llm_tpu.core import rng as rng_mod
from megatron_llm_tpu.data.batch_utils import get_ltor_batch
from megatron_llm_tpu.models import init_model_params
from megatron_llm_tpu.models.language_model import loss_from_batch, make_rope_cache
from megatron_llm_tpu.optimizer.optimizer import opt_state_shardings
from megatron_llm_tpu.parallel.tp import make_sp_constraint, param_shardings
from megatron_llm_tpu.observability import flight as flight_mod
from megatron_llm_tpu.observability import flops as flops_mod
from megatron_llm_tpu.observability import registry as registry_mod
from megatron_llm_tpu.observability import trace as trace_mod
from megatron_llm_tpu.observability.compiles import (
    install_compile_counter,
    startup_phase,
    summary as startup_summary,
)
from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
from megatron_llm_tpu.training_step import (
    make_jitted_train_step,
    measure_span_breakdown,
)
from megatron_llm_tpu.utils.logging_utils import (
    SignalHandler,
    build_writer,
    print0,
    set_global,
)
from megatron_llm_tpu.utils.timers import Timers


# window of fetched (iteration, lm loss) pairs the loop keeps for the
# result dict — bounded, like every other per-step record in the driver
_LOSS_SERIES_MAXLEN = 512


def model_flops_per_token(cfg) -> float:
    """Matmul FLOPs/token for fwd+bwd — now delegated to the shared
    accounting in observability/flops.py (kept here for the tools that
    import it from the driver)."""
    return flops_mod.flops_per_token(cfg)


def _train_valid_test_num_samples(cfg):
    """Sample counts for the three splits (training.py:877-961 math)."""
    t = cfg.training
    gbs = t.global_batch_size
    train_samples = (t.train_samples or (t.train_iters or 0) * gbs)
    eval_samples = t.eval_iters * gbs * (
        1 + (t.train_iters or 0) // max(t.eval_interval, 1)
    )
    return train_samples, eval_samples, t.eval_iters * gbs


def _loader_granularity(cfg) -> int:
    """Batches the loader yields: the full global batch normally, or one
    micro_batch*dp chunk under batch-size ramp-up (the loop then pulls
    gbs_t/chunk chunks per iteration as the ramp grows, microbatches.py)."""
    if cfg.training.rampup_batch_size is not None:
        return cfg.training.micro_batch_size * (
            cfg.parallel.data_parallel_size or 1
        )
    return cfg.training.global_batch_size


def _make_loader_factory(cfg, collate):
    from megatron_llm_tpu.data.samplers import build_pretraining_data_loader

    def loader(ds, consumed, batch_size=None):
        return build_pretraining_data_loader(
            ds, consumed, batch_size or _loader_granularity(cfg),
            cfg.data.dataloader_type, cfg.training.seed, collate_fn=collate,
            process_sliced=True,
        )

    return loader


def build_gpt_data_iterators(cfg, tokenizer):
    """Default dataset provider: GPT pretraining over --data_path."""
    from megatron_llm_tpu.data.gpt_dataset import build_train_valid_test_datasets

    if not cfg.data.data_path:
        raise ValueError(
            "--data_type gpt requires --data_path (per-split "
            "--train_data_path is only supported with --data_type instruction)"
        )
    train_ds, valid_ds, test_ds = build_train_valid_test_datasets(
        cfg.data.data_path,
        cfg.data.split,
        _train_valid_test_num_samples(cfg),
        cfg.data.seq_length,
        cfg.training.seed,
        data_impl=cfg.data.data_impl,
    )

    eod = getattr(tokenizer, "eod", None) if tokenizer else None

    def collate(samples):
        text = np.stack([s["text"] for s in samples])
        return get_ltor_batch(
            text,
            eod_token=eod,
            reset_position_ids=cfg.data.reset_position_ids,
            reset_attention_mask=cfg.data.reset_attention_mask,
            eod_mask_loss=cfg.data.eod_mask_loss,
        )

    return _make_loader_factory(cfg, collate), (train_ds, valid_ds, test_ds)


def build_instruction_data_iterators(cfg, tokenizer):
    """Instruction-tuning dataset provider (--data_type instruction)."""
    from megatron_llm_tpu.data.instruction_dataset import (
        build_train_valid_test_datasets as build_instruct,
        instruction_collator,
    )

    train_ds, valid_ds, test_ds = build_instruct(
        cfg.data.data_path,
        cfg.data.split,
        _train_valid_test_num_samples(cfg),
        cfg.data.seq_length,
        cfg.training.seed,
        train_data_prefix=cfg.data.train_data_path,
        valid_data_prefix=cfg.data.valid_data_path,
        test_data_prefix=cfg.data.test_data_path,
    )

    try:
        pad = tokenizer.pad
    except (NotImplementedError, AttributeError):
        pad = getattr(tokenizer, "eod", 0)

    def collate(samples):
        return instruction_collator(
            samples,
            seq_length=cfg.data.seq_length,
            pad_id=pad,
            loss_role=cfg.data.loss_role,
            scalar_loss_mask=cfg.data.scalar_loss_mask,
            variable_seq_lengths=cfg.data.variable_seq_lengths,
        )

    return _make_loader_factory(cfg, collate), (train_ds, valid_ds, test_ds)


def build_data_iterators(cfg, tokenizer):
    """Dispatch on --data_type (gpt | instruction)."""
    if cfg.data.data_type == "instruction":
        return build_instruction_data_iterators(cfg, tokenizer)
    return build_gpt_data_iterators(cfg, tokenizer)


def make_eval_step(cfg, loss_fn=None):
    sp_c = make_sp_constraint(cfg)
    names = list(cfg.logging.metrics or [])
    if loss_fn is None:
        loss_fn = loss_from_batch

    if names and loss_fn is not loss_from_batch:
        raise ValueError(
            "--metrics currently supports the GPT-family LM loss path only "
            f"(requested {names} with a custom loss_fn)"
        )

    def eval_step(params, batch):
        if not names:
            loss, metrics = loss_fn(
                cfg, params, batch, deterministic=True, sp_constraint=sp_c
            )
            return metrics
        # --metrics path (reference metrics registry computed in loss_func
        # during validation, finetune.py:183-187): keep the logits around
        # for argmax metrics.
        from megatron_llm_tpu.metrics import (
            MetricInput,
            compute_metrics,
            needs_logits,
        )
        from megatron_llm_tpu.models.language_model import model_forward
        from megatron_llm_tpu.ops.cross_entropy import softmax_cross_entropy

        import jax.numpy as jnp

        logits, _ = model_forward(
            cfg, params, batch["tokens"],
            position_ids=batch.get("position_ids"),
            segment_ids=batch.get("segment_ids"),
            token_idx=batch.get("token_idx"),
            deterministic=True, sp_constraint=sp_c,
        )
        per_token = softmax_cross_entropy(logits, batch["labels"])
        mask = batch["loss_mask"].astype(jnp.float32)
        loss = (per_token * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        inp = MetricInput(
            batch=batch, per_token_loss=per_token,
            logits=logits if needs_logits(names) else None,
        )
        metrics = {"lm loss": loss}
        metrics.update(compute_metrics(names, inp))
        return metrics

    return jax.jit(eval_step)


# eval steps dispatch back-to-back and their metric dicts drain in one
# batched device_get per this many iterations (bounds device memory for
# pending eval programs) — instead of a blocking float(v) per metric per
# iteration, which serialized host and device every eval step
_EVAL_DRAIN_EVERY = 32


def evaluate(cfg, params, eval_step, data_iterator,
             max_iters: Optional[int] = None, place_batch=None):
    """evaluate analog (training.py:773-860): mean loss over eval_iters.

    ``place_batch`` (the training step's placer) must be passed in
    multi-host runs: eval loaders are process-sliced like training loaders,
    so the local rows need the same global-array assembly."""
    totals: Dict[str, float] = {}
    n = 0
    pending: list = []
    max_iters = max_iters or cfg.training.eval_iters

    def drain():
        for host in jax.device_get(pending):
            for k, v in host.items():
                totals[k] = totals.get(k, 0.0) + float(v)
        pending.clear()

    for _ in range(max_iters):
        try:
            batch = next(data_iterator)
        except StopIteration:
            break
        if place_batch is not None:
            batch = place_batch(batch)
        pending.append(eval_step(params, batch))
        n += 1
        if len(pending) >= _EVAL_DRAIN_EVERY:
            drain()
    drain()
    return {k: v / max(n, 1) for k, v in totals.items()}


def training_log(cfg, metrics, iteration, step_time, writer, timers,
                 consumed_samples, global_batch_size=None):
    """training_log analog (training.py:462-641)."""
    t = cfg.training
    gbs = global_batch_size or t.global_batch_size
    tokens_per_step = gbs * cfg.data.seq_length
    tps = tokens_per_step / step_time if step_time > 0 else 0.0
    flops = model_flops_per_token(cfg) * tps
    loss = float(metrics.get("lm loss", float("nan")))
    lr = float(metrics.get("learning_rate", 0.0))
    gnorm = float(metrics.get("grad_norm", 0.0))
    msg = (
        f"iteration {iteration:8d}/{t.train_iters or 0:8d} | "
        f"consumed samples: {consumed_samples:12d} | "
        f"elapsed time per iteration (ms): {step_time * 1000:.1f} | "
        f"learning rate: {lr:.3E} | global batch size: {gbs:5d} | "
        f"lm loss: {loss:.6E} | grad norm: {gnorm:.3f} | "
        f"tokens/sec: {tps:,.0f} | TFLOP/s (model): {flops / 1e12:.1f}"
    )
    if "loss_scale" in metrics:
        msg += (f" | loss scale: {float(metrics['loss_scale']):.1f} | "
                f"skipped iterations: {int(metrics['skipped_iterations']):4d}")
    if "num_zeros" in metrics:
        msg += f" | num zeros: {float(metrics['num_zeros']):.0f}"
    if "params_norm" in metrics:
        msg += f" | params norm: {float(metrics['params_norm']):.3f}"
    print0(msg, flush=True)
    if writer is not None:
        writer.add_scalar("lm-loss-training/lm loss", loss, iteration)
        if cfg.logging.log_learning_rate_to_tensorboard:
            writer.add_scalar("learning-rate/learning-rate", lr, iteration)
        writer.add_scalar("grad-norm/grad-norm", gnorm, iteration)
        writer.add_scalar("throughput/tokens-per-sec", tps, iteration)
        writer.add_scalar("batch-size/batch-size", gbs, iteration)
        if "num_zeros" in metrics:
            writer.add_scalar("num-zeros/num-zeros",
                              float(metrics["num_zeros"]), iteration)
        if "params_norm" in metrics:
            writer.add_scalar("params-norm/params-norm",
                              float(metrics["params_norm"]), iteration)
        if cfg.logging.log_memory_to_tensorboard:
            # report_memory analog (reference utils.py:82-96 +
            # training.py:573-589): device memory_stats -> tensorboard
            try:
                stats = jax.local_devices()[0].memory_stats() or {}
            except Exception:
                stats = {}
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in stats:
                    writer.add_scalar(f"memory/{key}", stats[key], iteration)
        if cfg.logging.log_timers_to_tensorboard and timers is not None:
            timers.write(writer, iteration)
    if registry_mod.publishing():
        # mirror the log line into the process-wide registry so a live
        # scrape of /metrics sees what the console sees (sync-free: all
        # inputs are the host floats computed above)
        reg = registry_mod.get_registry()
        reg.gauge("mlt_iteration", help="training iteration").set(iteration)
        reg.gauge("mlt_consumed_samples",
                  help="samples consumed").set(consumed_samples)
        reg.gauge("mlt_lm_loss", help="last fetched lm loss").set(loss)
        reg.gauge("mlt_learning_rate", help="current learning rate").set(lr)
        reg.gauge("mlt_tokens_per_sec",
                  help="training throughput over the last interval").set(tps)
        reg.gauge("mlt_step_time_seconds",
                  help="mean step time over the last interval").set(step_time)
        frac = flops_mod.mfu(cfg, tps,
                             device_kind=jax.devices()[0].device_kind,
                             n_devices=get_global_mesh().size)
        reg.gauge("mlt_steady_mfu",
                  help="model flops utilization over the last interval "
                       "(0 when no device peak is known)").set(frac or 0.0)
    if timers is not None and cfg.logging.timing_log_level > 0:
        log = timers.log()
        if log:
            print0(f"    timers(ms): {log}", flush=True)


def pretrain(
    cfg,
    data_iterators_provider: Optional[Callable] = None,
    params_provider: Optional[Callable] = None,
    loss_fn: Optional[Callable] = None,
    pipeline_hooks: Optional[Callable] = None,
    pipeline_loss: Optional[Callable] = None,
) -> Dict[str, Any]:
    """End-to-end training (pretrain analog, training.py:55-196).

    Returns final state dict for programmatic use/testing.
    """
    t0 = time.time()
    from megatron_llm_tpu.core.distributed import initialize_distributed

    initialize_distributed()  # no-op single-host; pod autodetect multi-host
    with startup_phase("mesh"):
        mesh = build_mesh_from_config(cfg)
    print0(f"mesh: {dict(mesh.shape)}")
    for _ax, _size in dict(mesh.shape).items():
        registry_mod.get_registry().gauge(
            "mlt_mesh_axis_size", help="mesh axis size",
            labels={"axis": str(_ax)}).set(_size)
    tokenizer = None
    if cfg.data.tokenizer_type and (cfg.data.data_path or cfg.data.tokenizer_model
                                    or cfg.data.tokenizer_type == "NullTokenizer"):
        tokenizer = build_tokenizer(cfg)
        set_global("tokenizer", tokenizer)

    timers = Timers(cfg.logging.timing_log_level, cfg.logging.timing_log_option)
    writer = build_writer(cfg)
    sig = SignalHandler() if cfg.training.exit_signal_handler else None

    # ---- observability (megatron_llm_tpu/observability/,
    # docs/guide/observability.md): span tracer, metrics endpoint,
    # on-demand profiler.  All host-side and sync-free — the async loop's
    # overlap (and its bitwise loss guarantee) survives instrumentation.
    from megatron_llm_tpu.observability import profiler as profiler_mod

    obs = cfg.logging
    profile_dir = profiler_mod.profile_dir(obs)
    tracer = None
    if obs.trace_dir:
        os.makedirs(obs.trace_dir, exist_ok=True)
        tracer = trace_mod.configure(capacity=obs.trace_buffer_events)
        print0(f"observability: span tracing -> {obs.trace_dir} "
               f"(window {obs.trace_steps} steps, ring "
               f"{obs.trace_buffer_events} events)")
    install_compile_counter()  # mlt_jit_* on /metrics, the compile log
    profile_trigger = profiler_mod.ProfileTrigger(
        os.path.join(profile_dir, "ondemand"),
        max_captures=obs.profile_max_captures,
    )
    prev_usr2 = profiler_mod.install_sigusr2(profile_trigger)
    exporter = None
    if obs.metrics_port is not None:
        from megatron_llm_tpu.observability.exporter import MetricsExporter

        exporter = MetricsExporter(registry_mod.get_registry(),
                                   profile_trigger, port=obs.metrics_port)
        print0(f"observability: /metrics + /profile on port "
               f"{exporter.start()}")

    with global_mesh(mesh):
        # ---- model + optimizer ----
        init_fn = params_provider or (lambda key: init_model_params(cfg, key))
        key = rng_mod.init_key(cfg.training.seed)
        shapes = jax.eval_shape(init_fn, key)
        p_shardings = param_shardings(mesh, shapes)
        timers("model-setup", 0).start()
        with startup_phase("model-setup"):
            params = jax.jit(init_fn, out_shardings=p_shardings)(key)
            step_fn, optimizer, shardings = make_jitted_train_step(
                cfg, mesh, params, loss_fn=loss_fn,
                pipeline_hooks=pipeline_hooks, pipeline_loss=pipeline_loss,
            )
            opt_state = shardings["opt_state_value"]
        timers("model-setup").stop()
        if cfg.parallel.pipeline_model_parallel_size > 1:
            from megatron_llm_tpu.parallel.pipeline import (
                pipeline_bubble_fraction,
            )

            ppl = cfg.parallel
            bubble = pipeline_bubble_fraction(
                ppl.num_micro_batches or 1,
                ppl.pipeline_model_parallel_size,
                ppl.virtual_pipeline_model_parallel_size or 1,
            )
            # a batch-size ramp runs fewer microbatches early on — this is
            # the steady-state (full global batch) figure
            print0(f"pipeline: schedule={ppl.pipeline_schedule} "
                  f"vpp={ppl.virtual_pipeline_model_parallel_size or 1} "
                  f"steady-state bubble fraction={bubble:.3f}", flush=True)
        if cfg.optimizer.use_distributed_optimizer:
            from megatron_llm_tpu.core.parallel_state import DP_AXIS, EP_AXIS
            from megatron_llm_tpu.optimizer.optimizer import (
                zero1_sharded_fraction,
            )

            dp_ax = mesh.shape.get(DP_AXIS, 1)
            ep_ax = mesh.shape.get(EP_AXIS, 1)
            frac = zero1_sharded_fraction(
                cfg, params, opt_state, dp_ax, ep_size=ep_ax
            )
            over = f"dp={dp_ax}" + (f" x ep={ep_ax}" if ep_ax > 1 else "")
            print0(f"ZeRO-1: {frac * 100:.1f}% of optimizer-state elements "
                  f"sharded over {over}", flush=True)

        iteration, consumed_samples = 0, 0
        if cfg.checkpoint.load:
            try:
                o_shardings = opt_state_shardings(cfg, mesh, params, opt_state)
                with startup_phase("checkpoint-load"):
                    (params, loaded_opt, iteration, consumed_samples,
                     _) = load_checkpoint(
                        cfg, cfg.checkpoint.load, params, opt_state,
                        p_shardings, o_shardings,
                    )
                if loaded_opt is not None:
                    opt_state = loaded_opt
                print0(f"loaded checkpoint from {cfg.checkpoint.load} "
                      f"at iteration {iteration}")
            except FileNotFoundError as e:
                if cfg.checkpoint.exit_on_missing_checkpoint:
                    raise
                print0(f"WARNING: {e}; training from scratch")

        # ---- resilience: goodput accounting + hang watchdog ----
        # (docs/guide/resilience.md) The supervisor (tools/run_resilient.py)
        # exports MLT_RESIL_DIR; standalone runs fall back to a subdir of
        # the save dir so goodput/progress records always have a home when
        # checkpoints do.
        from megatron_llm_tpu.resilience import goodput as gp_mod
        from megatron_llm_tpu.resilience.watchdog import StepWatchdog

        resil_dir = os.environ.get("MLT_RESIL_DIR") or (
            os.path.join(cfg.checkpoint.save, "resilience")
            if cfg.checkpoint.save else None
        )
        goodput = gp_mod.GoodputTracker(t0)
        goodput.run_started(iteration, gp_mod.read_progress(resil_dir))
        if goodput.replayed_steps:
            print0(f"resilience: replaying {goodput.replayed_steps} steps "
                   f"(progress high-water {goodput.prev_progress_iteration}, "
                   f"resumed at {iteration})")

        watchdog = None
        if cfg.resilience.watchdog:
            def _emergency_snapshot():
                # host snapshot of the last COMPLETED state the driver
                # holds; bounded by the watchdog (a wedged device hangs
                # device_get too), and safe: the tracker only advances
                # past a verified manifest, so a torn write is never
                # referenced
                if cfg.checkpoint.save:
                    save_checkpoint(cfg, cfg.checkpoint.save, iteration,
                                    params, opt_state, consumed_samples)

            r = cfg.resilience
            watchdog = StepWatchdog(
                multiplier=r.watchdog_multiplier,
                min_deadline=r.watchdog_min_deadline,
                first_deadline=r.watchdog_first_deadline,
                snapshot_fn=_emergency_snapshot,
                snapshot_timeout=r.emergency_save_timeout,
                gauge_fn=lambda: timers.gauge("watchdog-expired", 1.0),
                # a hang report should carry a timeline: the span ring
                # buffer dumps next to the thread-stack dump (satellite;
                # without --trace_dir the watchdog falls back to a text
                # tail of the global tracer, if any)
                trace_dump_fn=(
                    (lambda: tracer.dump(
                        os.path.join(obs.trace_dir, "trace_watchdog.json"),
                        drain=False))
                    if tracer is not None else None),
                # and the in-flight request flight records next to it
                # (ISSUE 12): a hang report should name the request
                # state, not just the thread stacks.  Resolved at expiry
                # time — an engine constructed after the watchdog (e.g.
                # a serving sidecar) still gets its records dumped.
                flight_dump_fn=(
                    (lambda: (flight_mod.get_recorder().dump(
                        os.path.join(obs.trace_dir,
                                     "flight_watchdog.json"))
                        if flight_mod.get_recorder() is not None
                        and flight_mod.get_recorder().enabled else None))
                    if tracer is not None else None),
            ).start()
            print0(f"resilience: watchdog armed per step "
                   f"(deadline {r.watchdog_multiplier}x EMA, floor "
                   f"{r.watchdog_min_deadline:.0f}s, first step "
                   f"{r.watchdog_first_deadline:.0f}s)")

        # ---- data ----
        rebuild_full_loader = None
        if data_iterators_provider is not None:
            if cfg.training.rampup_batch_size is not None:
                raise ValueError(
                    "rampup_batch_size requires the built-in data path: "
                    "provider loaders yield fixed global_batch_size batches, "
                    "which the ramp's chunked accounting would mis-count"
                )
            train_iter, valid_iter_factory = data_iterators_provider(
                cfg, tokenizer, consumed_samples
            )
        elif cfg.data.data_path or cfg.data.train_data_path:
            loader, (train_ds, valid_ds, _) = build_data_iterators(cfg, tokenizer)
            train_iter = loader(train_ds, consumed_samples)
            # validation always runs at the FULL global batch size (the ramp
            # only chunks the training loader)
            valid_iter_factory = (
                (lambda: loader(valid_ds, 0, cfg.training.global_batch_size))
                if valid_ds else None
            )
            # once a batch-size ramp completes, drop back to full-global-batch
            # loading (no per-iteration chunk concatenation)
            rebuild_full_loader = lambda consumed: loader(  # noqa: E731
                train_ds, consumed, cfg.training.global_batch_size
            )
        else:
            raise ValueError("no data: set cfg.data.data_path or pass a provider")

        eval_step = make_eval_step(cfg, loss_fn=loss_fn)

        # ---- train loop (_train analog, training.py:654-770) ----
        # Overlapped: dispatch runs ahead of completion (bounded by
        # --async_dispatch_depth), data is staged by a prefetch thread
        # (--prefetch_depth), checkpoint writes go to a writer thread
        # (--async_save). Dispatch order — and so the numerical
        # trajectory — is identical to the synchronous loop.
        from megatron_llm_tpu.microbatches import build_num_microbatches_calculator

        t = cfg.training
        calc = build_num_microbatches_calculator(cfg)
        rampup = t.rampup_batch_size is not None
        chunk = _loader_granularity(cfg)
        # one compiled step per num-microbatches stage (constant: exactly one)
        step_cache = {cfg.parallel.num_micro_batches or 1: step_fn}
        train_iters = t.train_iters or 0
        exit_reason = "train_iters reached"
        metrics: Dict[str, Any] = {}
        log_interval = max(cfg.logging.log_interval, 1)
        depth = max(int(t.async_dispatch_depth or 0), 0)
        # bounded (the old list grew for the whole run): host-side
        # dispatch-to-dispatch deltas, kept for the last interval only
        step_times: deque = deque(maxlen=log_interval)
        loss_series: deque = deque(maxlen=_LOSS_SERIES_MAXLEN)
        in_flight: deque = deque()  # (iteration, metrics-on-device)
        warmup_time = None  # first dispatched step = compile + warmup
        interval_t0 = time.perf_counter()
        interval_steps = 0
        steady_t0 = None
        steady_steps = 0
        last_dispatch = None
        placed = None

        def _retire(n: Optional[int] = None):
            """Completion probe: fetch the oldest ``n`` in-flight metric
            dicts (all when None) in ONE batched device_get — this is the
            only place the host waits on the device."""
            nonlocal metrics
            take = len(in_flight) if n is None else min(n, len(in_flight))
            if take == 0:
                return metrics
            entries = [in_flight.popleft() for _ in range(take)]
            with trace_mod.span("metric-drain", count=take):
                hosts = jax.device_get([m for _, m in entries])
            for (it, _), host in zip(entries, hosts):
                loss_series.append((it, float(host.get("lm loss", np.nan))))
                metrics = host
                if "moe assignments" in host:
                    # what the step's routers did, where a capture can lay
                    # it beside the step's device time (zero length: the
                    # numbers came with the drain above)
                    with trace_mod.span(
                            "train-moe", step=it,
                            assignments=int(host["moe assignments"]),
                            held=int(host["moe held"]),
                            dropped=int(host["moe dropped"])):
                        pass
            return metrics

        prefetcher = None
        if (t.prefetch_depth and int(t.prefetch_depth) > 0
                and not t.skip_train and iteration < train_iters):
            from megatron_llm_tpu.data.prefetch import BatchPrefetcher

            shadow = build_num_microbatches_calculator(cfg)

            def _gbs_fn(consumed):
                # shadow of the driver's ramp schedule: a pure function of
                # consumed samples, so worker and driver stay in lockstep
                shadow.update(consumed, False)
                return shadow.get_current_global_batch_size()

            prefetcher = BatchPrefetcher(
                train_iter,
                depth=int(t.prefetch_depth),
                # multi-host placement assembles global arrays from every
                # process — keep it on the driver thread there
                place_fn=(shardings["place_batch"]
                          if jax.process_count() == 1 else None),
                gbs_fn=_gbs_fn,
                chunk_size=chunk if rampup else None,
                consumed_samples=consumed_samples,
                max_steps=train_iters - iteration,
                switch_source=rebuild_full_loader,
                full_gbs=t.global_batch_size,
            )

        saver = None
        if cfg.checkpoint.async_save:
            if jax.process_count() == 1:
                saver = AsyncCheckpointSaver()
            else:
                print0("WARNING: --async_save is single-host only (the "
                       "snapshot of multi-host sharded arrays needs every "
                       "process in the orbax save); saving synchronously")

        def _save(it):
            timers("save-checkpoint", 0).start()
            # "ckpt-flush" = what the DRIVER pays at a save point: under
            # --async_save the previous write's flush barrier + the host
            # snapshot; synchronously the whole write (the writer thread's
            # own span is "ckpt-write", checkpointing.py)
            with trace_mod.span("ckpt-flush", iteration=it):
                if saver is not None:
                    waited = saver.save(cfg, cfg.checkpoint.save, it, params,
                                        opt_state, consumed_samples)
                    timers.gauge("ckpt-flush-wait-ms", waited * 1e3)
                else:
                    save_checkpoint(cfg, cfg.checkpoint.save, it, params,
                                    opt_state, consumed_samples)
            timers("save-checkpoint").stop()

        profiling = False
        profile_stop_at = None  # set when the trace starts
        spans_printed = False

        try:
            while iteration < train_iters:
                if t.skip_train:
                    break
                # watchdog window covers the loop body (data wait, dispatch,
                # completion probe, log drain) — the places a wedged device
                # or dead loader silently blocks the host.  Eval and
                # checkpoint saves run disarmed: legitimately slow.
                if watchdog is not None:
                    watchdog.arm(first=warmup_time is None)
                iter_t0 = time.perf_counter()
                trace_mod.instant("step-begin", iteration=iteration)
                # on-demand capture (SIGUSR2 / GET /profile?steps=N) starts
                # at a step boundary — never from a handler frame, never
                # inside the static --profile window
                if not profiling and profile_trigger.maybe_start(iteration):
                    print0(f"profiler: on-demand capture started at "
                           f"iteration {iteration}", flush=True)
                # xplane tracing over [profile_step_start, profile_step_end)
                # (SURVEY §5: jax-profiler analog of the reference's span
                # timers). >= not ==: a resumed run past the start step still
                # gets a trace (of at least one step, even past the window)
                if (cfg.logging.profile and profile_stop_at is None
                        and not profile_trigger.active
                        and iteration >= cfg.logging.profile_step_start):
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                    profile_stop_at = max(cfg.logging.profile_step_end,
                                          iteration + 1)
                calc.update(consumed_samples)
                gbs = calc.get_current_global_batch_size()
                num_micro = calc.get()
                if (prefetcher is None and rampup
                        and gbs == t.global_batch_size and rebuild_full_loader):
                    # ramp finished: switch to full-global-batch loading so
                    # steady state pays no per-iteration chunk concatenation
                    # (the prefetch worker makes this same switch itself)
                    train_iter = rebuild_full_loader(consumed_samples)
                    rampup = False
                if num_micro not in step_cache:
                    step_cache[num_micro] = make_jitted_train_step(
                        cfg, mesh, params, num_micro=num_micro,
                        optimizer=optimizer, opt_state=opt_state,
                        loss_fn=loss_fn, pipeline_hooks=pipeline_hooks,
                        pipeline_loss=pipeline_loss,
                    )[0]
                cur_step_fn = step_cache[num_micro]
                try:
                    timers("batch-generator", 1).start()
                    wait_t0 = time.perf_counter()
                    with trace_mod.span("data-wait", iteration=iteration):
                        if prefetcher is not None:
                            pre_gbs, placed = next(prefetcher)
                            if pre_gbs is not None and pre_gbs != gbs:
                                raise RuntimeError(
                                    f"prefetch schedule diverged: worker "
                                    f"staged gbs {pre_gbs}, driver expects "
                                    f"{gbs}")
                            if prefetcher.place_fn is None:  # multi-host
                                placed = shardings["place_batch"](placed)
                        else:
                            if rampup:
                                chunks = [next(train_iter)
                                          for _ in range(gbs // chunk)]
                                # token_idx is batch-invariant [s] — never
                                # concatenated
                                batch = {
                                    k: (chunks[0][k] if k == "token_idx"
                                        else np.concatenate(
                                            [c[k] for c in chunks]))
                                    for k in chunks[0]
                                }
                            else:
                                batch = next(train_iter)
                            placed = shardings["place_batch"](batch)
                    timers.gauge("data-wait-ms",
                                 (time.perf_counter() - wait_t0) * 1e3)
                    timers("batch-generator").stop()
                except StopIteration:
                    exit_reason = "data exhausted"
                    break

                timers("train-step", 0).start()
                dispatch_t0 = time.perf_counter()
                if last_dispatch is not None:
                    step_times.append(dispatch_t0 - last_dispatch)
                last_dispatch = dispatch_t0
                first_step = False
                if iteration not in (t.skip_iters or []):
                    # --skip_iters skips the update (training.py:397-399)
                    if warmup_time is None:
                        # the first step is the last phase of start-up:
                        # fenced (retired inside the phase) so that the
                        # compile stays out of throughput and the first
                        # training_log line is honest
                        with startup_phase("first-step") as first:
                            with trace_mod.span("dispatch",
                                                iteration=iteration):
                                params, opt_state, metrics_dev = cur_step_fn(
                                    params, opt_state, placed, iteration,
                                )
                                in_flight.append(
                                    (iteration + 1, metrics_dev))
                            timers.gauge("in-flight-depth", len(in_flight))
                            _retire()
                        warmup_time = first.t1 - first.t0
                        first_step = True
                        print0(f"first step (compile + warmup): "
                               f"{warmup_time:.2f}s — excluded from "
                               f"throughput averages", flush=True)
                        print0(startup_summary(), flush=True)
                    else:
                        with trace_mod.span("dispatch", iteration=iteration):
                            params, opt_state, metrics_dev = cur_step_fn(
                                params, opt_state, placed, iteration,
                            )
                            in_flight.append((iteration + 1, metrics_dev))
                        timers.gauge("in-flight-depth", len(in_flight))
                        while len(in_flight) > depth:
                            _retire(1)
                timers("train-step").stop()
                iteration += 1
                consumed_samples += gbs
                if first_step:
                    interval_t0 = steady_t0 = time.perf_counter()
                    interval_steps = 0
                else:
                    interval_steps += 1
                    steady_steps += 1

                if profiling and iteration >= profile_stop_at:
                    jax.profiler.stop_trace()
                    profiling = False
                    print0(f"profiler: xplane trace written to {profile_dir}",
                           flush=True)
                if profile_trigger.step_done():
                    print0(f"profiler: on-demand capture written to "
                           f"{profile_trigger.capture_dirs[-1]}", flush=True)
                if (tracer is not None and obs.trace_steps > 0
                        and iteration % obs.trace_steps == 0):
                    # one Chrome-trace file per N-step window (drains the
                    # ring, so windows are disjoint)
                    tracer.dump(os.path.join(
                        obs.trace_dir, f"trace_{iteration:08d}.json"))

                if iteration % log_interval == 0:
                    # drain: one batched fetch for the whole interval
                    _retire()
                    now = time.perf_counter()
                    avg = ((now - interval_t0) / interval_steps
                           if interval_steps > 0 else (warmup_time or 0.0))
                    training_log(cfg, metrics, iteration, avg, writer, timers,
                                 consumed_samples, global_batch_size=gbs)
                    if cfg.logging.timing_log_level >= 2 and not spans_printed:
                        spans_printed = True  # once per run, incl. resumed
                        spans = measure_span_breakdown(
                            cfg, params, placed, avg, loss_fn=loss_fn,
                        )
                        if spans:
                            print0("    span breakdown (ms): " + " | ".join(
                                f"{k}: {v * 1e3:.1f}"
                                for k, v in spans.items()), flush=True)
                    if registry_mod.publishing():
                        # live goodput snapshot for /metrics scrapes (the
                        # exit path overwrites these with final numbers;
                        # report() publishes its fields to the registry)
                        goodput.record_compile(warmup_time or 0.0)
                        if steady_t0 is not None:
                            goodput.record_productive(
                                steady_steps, now - steady_t0)
                        goodput.report()
                    interval_t0 = time.perf_counter()
                    interval_steps = 0
                    if resil_dir:
                        # progress high-water mark: what a restart would
                        # have to replay from the last checkpoint
                        gp_mod.write_progress(resil_dir, iteration)

                if watchdog is not None:
                    watchdog.disarm(None if first_step
                                    else time.perf_counter() - iter_t0)

                if (cfg.training.eval_interval and valid_iter_factory
                        and iteration % cfg.training.eval_interval == 0):
                    with trace_mod.span("eval", iteration=iteration):
                        ev = evaluate(cfg, params, eval_step,
                                      valid_iter_factory(),
                                      place_batch=shardings["place_batch"])
                    print0(f" validation loss at iteration {iteration}: "
                           + " | ".join(f"{k}: {v:.6E}" for k, v in ev.items()),
                           flush=True)
                    if writer:
                        for k, v in ev.items():
                            writer.add_scalar(f"lm-loss-validation/{k}", v,
                                              iteration)

                if (cfg.checkpoint.save and cfg.checkpoint.save_interval
                        and iteration % cfg.checkpoint.save_interval == 0):
                    _save(iteration)

                # exit conditions (training.py:731-767) — checked on the
                # deferred state: breaking with steps still in flight is
                # fine, the drain below lands their metrics
                if sig is not None and sig.signals_received():
                    exit_reason = "signal"
                    break
                if t.exit_interval and iteration % t.exit_interval == 0:
                    exit_reason = "exit_interval"
                    break
                if t.exit_duration_in_mins and (
                    (time.time() - t0) / 60.0 > t.exit_duration_in_mins
                ):
                    exit_reason = "exit_duration"
                    break

            # land any still-deferred metrics before leaving the loop
            if watchdog is not None:
                watchdog.disarm()  # StopIteration breaks exit armed
            _retire()
            steady_end = time.perf_counter()
        finally:
            # watchdog first: cleanup below (close/join/flush) is
            # legitimately slow and must not trip a stale deadline
            if watchdog is not None:
                watchdog.stop()
            if prefetcher is not None:
                prefetcher.close()
            if profiling:  # early exit mid-window: don't leak an open trace
                jax.profiler.stop_trace()
                profiling = False
            profile_trigger.close()  # nor an open on-demand window
            if saver is not None:
                # exit barrier: never leave the loop (even on an exception
                # or a signal) with checkpoint bytes half-written
                saver.wait()
            # goodput report on EVERY exit path (normal, exception,
            # signal-break) so the supervisor can aggregate what this
            # attempt kept vs. lost
            goodput.record_compile(warmup_time or 0.0)
            if steady_t0 is not None:
                goodput.record_productive(
                    steady_steps, time.perf_counter() - steady_t0)
            goodput_report = goodput.report()
            if resil_dir:
                gp_mod.write_report(resil_dir, goodput_report)
            print0("goodput: "
                   f"{goodput_report['goodput_fraction'] * 100:.1f}% "
                   f"({goodput_report['productive_seconds']:.1f}s productive"
                   f" / {goodput_report['wall_seconds']:.1f}s wall, "
                   f"compile {goodput_report['lost_compile_seconds']:.1f}s, "
                   f"replay {goodput_report['replayed_steps']} steps)",
                   flush=True)
            if tracer is not None:
                # whatever the exit path, the tail of the timeline lands
                # on disk (the window dumps drained everything older)
                print0("observability: final trace window -> " + tracer.dump(
                    os.path.join(obs.trace_dir,
                                 f"trace_final_{iteration:08d}.json")))
            if exporter is not None:
                exporter.stop()
            if prev_usr2 is not None:
                import signal as signal_mod

                signal_mod.signal(signal_mod.SIGUSR2, prev_usr2)

        # after the last step: the allocator's view of the whole run
        print0(placement_report(mesh, params=params, opt_state=opt_state),
               flush=True)
        steady_sps = None
        if steady_t0 is not None and steady_steps > 0:
            steady_sps = steady_steps / max(steady_end - steady_t0, 1e-9)
        steady_tps = steady_mfu_val = None
        if steady_sps is not None:
            # config-derived flops (observability/flops.py) feed the result
            # dict and the registry: the Megatron-style MFU signal
            steady_tps = (steady_sps * t.global_batch_size
                          * cfg.data.seq_length)
            steady_mfu_val = flops_mod.mfu(
                cfg, steady_tps, device_kind=jax.devices()[0].device_kind,
                n_devices=mesh.size)
            if registry_mod.publishing():
                reg = registry_mod.get_registry()
                reg.gauge("mlt_tokens_per_sec").set(steady_tps)
                reg.gauge("mlt_steady_mfu").set(steady_mfu_val or 0.0)

        if cfg.checkpoint.save and exit_reason != "train_iters reached":
            _save(iteration)
            if saver is not None:
                saver.wait()
        if writer is not None and hasattr(writer, "flush"):
            writer.flush()

        return {
            "params": params,
            "opt_state": opt_state,
            "iteration": iteration,
            "consumed_samples": consumed_samples,
            "exit_reason": exit_reason,
            "last_metrics": metrics,
            "mesh": mesh,
            # async-loop observability (bench_train_loop.py evidence):
            # compile+warmup wall time, post-warmup steps/sec, and the
            # fetched (iteration, lm loss) trajectory (bounded window)
            "warmup_time": warmup_time,
            "steady_steps_per_sec": steady_sps,
            # observability (docs/guide/observability.md): steady-state
            # throughput in tokens and model-flops terms (MFU is None on
            # hosts with no known peak, e.g. CPU), and the bound /metrics
            # port when --metrics_port was set (0 binds ephemerally)
            "tokens_per_sec": steady_tps,
            "steady_mfu": steady_mfu_val,
            "metrics_port": exporter.port if exporter is not None else None,
            "loss_series": list(loss_series),
            # resilience observability (docs/guide/resilience.md): what this
            # run kept vs. lost to compile/replay — also persisted to
            # <resil_dir>/goodput_last.json for the supervisor
            "goodput": goodput_report,
        }
