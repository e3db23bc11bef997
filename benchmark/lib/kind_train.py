"""Traffic kind ``train``: the run drives ``training.pretrain`` — the
driver ``finetune.py`` calls — in process, feeding it packed batches made
on the host from the seed.

The window, on the benchmark's own clock.  The provider's iterator stamps
every draw of a batch.  The trainer's pipeline between a draw and that
step's completion is bounded (prefetch queue + asynchronous dispatch depth,
both program defaults), so once it is full a batch is drawn exactly when a
step retires.  The window opens at draw number ``warmup_draws`` (by then the
first step — compile and warm-up — has been retired, which the run
verifies: every draw gap inside the window is a step, none a compile) and
closes at the first draw at or after ``open + seconds``, which the iterator
answers with StopIteration.  Steps completed in the window = draws between
the two stamps; both edges are draw instants, so no step is cut.  pretrain's
own ``tokens_per_sec`` (first retire to last fetch) is printed beside it as
a cross-check and is not the metric.

``correct`` rests on: no compile inside the window, every loss finite, and
the reference comparison.  How evenly the steps came is recorded beside
them (``window_steady``: no draw gap of three times the median or more;
the longest gap, the step it followed and the median, in ms) and decides
nothing: a program that stalls completes fewer steps, which
``train_tokens_per_s`` counts over every second of the window, and one late
step on a shared host is a far-off run to repeat, not a wrong output
(PERF.md 7i: three PRs refused by it that had touched no training code).
In a ``--trace 1`` run the longest gap is the profiler's own start.
"""

from __future__ import annotations

import math
import time
from typing import Dict

from benchmark.lib import check as check_mod
from benchmark.lib import harness, traffic as traffic_mod


def judge(run_: harness.Run, gaps, losses, ref: Dict) -> None:
    """Fill ``run_.checks`` and ``run_.correct`` from the window's draw
    gaps (seconds), the trainer's losses and the reference comparison."""
    import numpy as np

    finite = bool(losses) and all(math.isfinite(v) for v in losses)
    gaps = np.asarray(gaps, np.float64)
    late = int(gaps.argmax()) if len(gaps) else None
    median = float(np.median(gaps)) if len(gaps) else None
    run_.checks = {
        "compiles_in_window": run_.compiles_in_window, "losses_finite": finite,
        "window_steady": late is not None and bool(gaps[late] < 3.0 * median),
        "longest_gap_ms": None if late is None else float(gaps[late]) * 1e3,
        "longest_gap_after_step": late,
        "median_gap_ms": None if late is None else median * 1e3,
        "last_loss": losses[-1] if losses else None, **ref}
    # a window that completed no step measured nothing
    run_.correct = bool(run_.compiles_in_window == 0 and finite
                        and late is not None and ref["reference_ok"])


def run(cell, args, clock) -> harness.Run:
    import numpy as np

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.models.families import validate_family
    from megatron_llm_tpu.observability import registry as registry_mod
    from megatron_llm_tpu.training import pretrain

    run_ = harness.Run(cell, args, clock)
    run_.stamp_device()
    compiles = harness.CompileCounter()
    mix = dict(cell.traffic)
    flags: Dict = {}
    if args.rehearsal:
        flags.update(cell.config.get("rehearsal", {}).get("flags", {}))
        cell.model.update(cell.config.get("rehearsal", {}).get("model", {}))
        mix.update(mix.get("rehearsal", {}))
    seq, mbs = int(mix["seq_length"]), int(mix["micro_batch_size"])
    flags.update(seq_length=seq, micro_batch_size=mbs,
                 train_iters=10 ** 9, eval_iters=0, eval_interval=10 ** 9,
                 log_interval=10 ** 9, seed=int(args.seed) % (2 ** 31 - 1))
    cfg = parse_args(cell.flags(flags), n_devices=cell.chips)
    dp = cfg.parallel.data_parallel_size
    global_batch = mbs * dp * int(mix.get("micro_batches_per_step", 1))
    cfg = parse_args(cell.flags({**flags, "global_batch_size": global_batch}),
                     n_devices=cell.chips)
    validate_family(cfg)
    vocab = cfg.model.vocab_size
    run_.tokens_per_step = global_batch * seq
    warm = int(mix["warmup_draws"])
    trace_steps = int(mix.get("trace_steps", 5))
    prof = harness.Profiler(run_) if args.trace else None
    gauge = registry_mod.get_registry().gauge(
        "mlt_driver_gauge", labels={"name": "data-wait-ms"})
    state = {"open": None, "close": None, "trace_from": None}
    run_.gauges["data-wait-ms"] = []

    def provider(_cfg, _tokenizer, _consumed):
        def draws():
            batches = traffic_mod.train_batches(mix, args.seed, global_batch, vocab)
            i = 0
            while True:
                now = time.monotonic()
                run_.draw_t.append(now)
                if i == warm:
                    state["open"] = now
                    run_.first_window_draw = i
                if state["open"] is not None:
                    # the program's gauge holds the last step's wait
                    run_.gauges["data-wait-ms"].append((now, gauge.value))
                    if now >= state["open"] + run_.seconds:
                        state["close"] = now
                        run_.last_window_draw = i
                        if prof and prof.started and not prof.stopped:
                            prof.stop()
                        return
                    if prof and state["trace_from"] is None and (
                            now >= state["open"] + run_.seconds / 2):
                        # the profiler takes about a second to start and
                        # stalls the runtime meanwhile: what the traced run
                        # reads before this draw is undisturbed
                        run_.disturbed_from = now
                        prof.start()
                        state["trace_from"] = i
                    elif prof and prof.started and not prof.stopped and (
                            i >= state["trace_from"] + trace_steps):
                        prof.stop()
                yield next(batches)
                i += 1
        return draws(), None

    result = pretrain(cfg, data_iterators_provider=provider)
    returned = time.monotonic()
    run_.t_open, run_.t_close = state["open"], state["close"]
    if run_.t_open is None or run_.t_close is None:
        raise RuntimeError("the trainer stopped before the window closed: "
                           f"{result.get('exit_reason')}")
    run_.setup_s = run_.t_open - clock.process_start
    run_.compiles_in_window = compiles.inside(run_.t_open, returned)
    steps = run_.last_window_draw - run_.first_window_draw
    gaps = np.diff(run_.draw_t[run_.first_window_draw:run_.last_window_draw + 1])
    run_.program = {k: result.get(k) for k in (
        "warmup_time", "steady_steps_per_sec", "tokens_per_sec", "steady_mfu",
        "iteration", "exit_reason")}
    own = steps * run_.tokens_per_step / (run_.t_close - run_.t_open)
    print(f"benchmark: window {run_.t_close - run_.t_open:.3f}s, {steps} steps, "
          f"{own:.1f} tokens/s on the benchmark's clock; pretrain reports "
          f"{result.get('tokens_per_sec')} tokens/s over first retire to last "
          f"fetch ({result.get('iteration')} steps, warm-up "
          f"{result.get('warmup_time'):.2f}s, drain {returned - run_.t_close:.3f}s); "
          f"step gap median {np.median(gaps) * 1e3:.2f} ms, max "
          f"{gaps.max() * 1e3:.2f} ms", flush=True)

    losses = [v for _, v in result["loss_series"]]
    finite = bool(losses) and all(math.isfinite(v) for v in losses)
    run_.attempted = steps
    run_.failed = 0 if finite else sum(not math.isfinite(v) for v in losses) or steps
    ref = check_mod.train_against_reference(
        cell, cfg, result["params"], result["mesh"], args.seed,
        rows=global_batch, positions=min(int(mix.get("probe_positions", 512)), seq))
    judge(run_, gaps, losses, ref)
    if prof:
        prof.reduce()
    return run_
