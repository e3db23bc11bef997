"""Single-chip training benchmark — prints ONE JSON line for the driver.

Metric: model FLOPs utilization (MFU) of a bf16 Llama-2-style training step
(~470M params, micro-batch 16, seq 1024, full activation recompute, Pallas
flash attention) on the local chip. Config chosen by the PERF.md sweep:
full recompute frees enough HBM for mbs 16, which beats selective+mbs 8.

Baseline (BASELINE.md): the reference's only published number is ~7.1k tok/s
for Llama-2-7B on one 8x A100-80GB node (DP=2 TP=4, seq 1024,
docs/guide/getting_started.md:205). With the same FLOP accounting used here
(6*N dense + 6*L*s*h causal-attention matmul FLOPs per token):
    7.1e3 tok/s * 41.2e9 FLOP/tok / (8 * 312e12 peak) ~= 11.7% MFU.
``vs_baseline`` is our MFU / 11.7% — an apples-to-apples utilization ratio
across different hardware.

Device: the benchmark measures an accelerator.  ``probe_backend`` takes the
CPU shape only when ``JAX_PLATFORMS=cpu`` was asked for (a liveness run whose
headline fields are ``value: 0 / vs_baseline: 0`` by contract — a CPU timing
is not an MFU measurement — with the sanity timing under ``cpu_sanity``);
otherwise finding no accelerator is a non-zero exit, and a configuration
that fails reports its error instead of being retried as another one.
  * a watchdog thread emits a structured JSON error line and exits if the
    whole run exceeds --watchdog seconds;
  * timing ends in a device->host fetch (float()), which cannot return
    before the step has executed;
  * compile time and steady-state step time are reported separately;
  * any exception is reported as a structured JSON line, never a bare
    traceback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# peak tables live with the rest of the flops accounting
# (megatron_llm_tpu/observability/flops.py — the registry's MFU gauge and
# this bench's measured MFU divide by the same numbers); re-exported here
# under the historical names (tools/aot_scale_check.py imports them)
from megatron_llm_tpu.observability.flops import (  # noqa: E402
    PEAK_BF16_FLOPS_BY_KIND,
    device_peak_flops,
)

BASELINE_MFU = 0.117  # reference 8xA100 node, see module docstring
METRIC = "train_mfu_llama_470m_seq1024_1chip"


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def metric_name(seq: int) -> str:
    return METRIC.replace("seq1024", f"seq{seq}")


def fail(reason: str, seq: int = 1024, **extra) -> None:
    emit({"metric": metric_name(seq), "value": 0.0, "unit": "%MFU",
          "vs_baseline": 0.0, "error": reason, **extra})


# Host-cost budgets for CPU-sanity evidence. The BENCH_r02-r05 trajectory
# shows step time drifting 18.4s -> 25.3s -> 52.2s and compile 38s -> 100s
# with nothing failing loudly (ROADMAP item 5 tail): these are deliberately
# GENEROUS ceilings — a regression guard against unbounded host-side drift,
# not a performance target.  A violated budget stamps ``error`` on the
# contract line.
# Override per-run via MLT_BENCH_BUDGET_<FIELD> env vars (same unit as the
# field: seconds for *_s, microseconds for *_us_*, percent for *_pct).
CPU_SANITY_BUDGETS = {
    "compile_time_s": 180.0,
    "step_time_s": 120.0,
    "step_time_dispatch_s": 5.0,
    # trace-cost ceilings (ROADMAP item 4 leftover): the observability
    # bench reports the isolated per-step instrumentation bill and the
    # end-to-end overhead; both get generous drift guards so a tracer
    # regression stamps the evidence line instead of creeping silently
    # (bench_observability.py gates the honest <3% separately)
    "instrument_cost_us_per_step": 2000.0,
    "overhead_pct": 10.0,
}


def _budget(field: str) -> float:
    env = os.environ.get("MLT_BENCH_BUDGET_" + field.upper())
    return float(env) if env else CPU_SANITY_BUDGETS[field]


def apply_budgets(line: dict, budgets: dict | None = None) -> dict:
    """Annotate a contract line with compile/dispatch budget verdicts.

    Reads the timing fields from ``cpu_sanity`` (or the line itself for
    on-TPU lines), records ``budgets`` = {field: {value, budget}} for every
    field present, and on any violation sets ``budget_exceeded`` AND
    ``error`` so the failure is loud instead of a slow upward drift across
    evidence files."""
    caps = {k: _budget(k) for k in (budgets or CPU_SANITY_BUDGETS)}
    src = line.get("cpu_sanity", line)
    checked, violations = {}, []
    for k, cap in caps.items():
        v = src.get(k)
        if v is None:
            continue
        checked[k] = {"value": v, "budget": cap}
        if float(v) > cap:
            violations.append(f"{k} {v} > budget {cap}")
    if checked:
        line["budgets"] = checked
    if violations:
        line["budget_exceeded"] = violations
        line["error"] = "host-cost budget exceeded: " + "; ".join(violations)
    return line


def cpu_contract_line(result: dict, seq: int = 1024,
                      tag: str | None = None) -> dict:
    """Off-TPU contract shared by bench.py and tools/moe_bench.py: the
    headline fields report 0 (a CPU step time divided by a nominal "peak" is
    not an MFU measurement — round-2 judging flagged the plausible-looking
    line it produced) and the run's numbers survive under ``cpu_sanity`` as
    a liveness check.  ``seq``/``tag`` are unused (the callers still pass
    the evidence-file key they once selected)."""
    sanity = dict(result)
    metric = sanity.pop("metric", METRIC)
    unit = sanity.pop("unit", "%MFU")
    has_vs = "vs_baseline" in sanity
    for k in ("value", "vs_baseline"):
        sanity.pop(k, None)
    line = {"metric": metric, "value": 0.0, "unit": unit}
    if has_vs:
        line["vs_baseline"] = 0.0
    line.update({
        "backend": "cpu",
        "note": ("off-TPU: headline 0 by contract; cpu_sanity is a "
                 "liveness check"),
        "cpu_sanity": sanity,
    })
    return apply_budgets(line)


def cpu_requested() -> bool:
    """``JAX_PLATFORMS=cpu`` was asked for — the one way to get a CPU
    shape.  Reads the environment only: orchestrators that must stay off
    jax (a parent that touched the chip holds it) decide with this."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def probe_backend() -> str:
    """'tpu' or 'cpu', determined in this process.

    The CPU shape is taken only when :func:`cpu_requested` — answered
    WITHOUT initializing a backend, so a caller can still pin a virtual
    device count.  Otherwise jax must find an accelerator: coming up on
    the CPU is a non-zero exit, not a smaller model."""
    if cpu_requested():
        return "cpu"
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        emit({"error": "no accelerator: jax came up on the CPU and "
                       "JAX_PLATFORMS=cpu was not asked for"})
        sys.exit(1)
    return "tpu"


def peak_flops() -> float | None:
    """Per-device peak of the local device; None on an explicit CPU run,
    an error for a device the table does not know."""
    import jax

    return device_peak_flops(jax.devices()[0].device_kind)


def flops_per_token(n_params: int, num_layers: int, hidden: int, seq: int) -> float:
    """6N dense + causal attention matmuls (QK^T and AV, fwd+bwd):
    4*s^2*h per layer per sequence non-causal fwd, /2 causal, x3 fwd+bwd
    => 6*L*s*h per token. Same family of formulas as the reference's FLOP
    estimate (language_model.py:370-384), with the attention term included
    so long-seq configs are not under-credited."""
    return 6.0 * n_params + 6.0 * num_layers * seq * hidden


def timed_multistep(step, params, opt_state, batch, iters: int,
                    metric_keys=("lm loss",), reps: int = 3):
    """Compile + time `iters` train steps inside ONE jitted lax.scan dispatch
    (per-call dispatch latency stays out of the measurement; the forced
    float() fetch is the completion barrier). Shared by bench.py
    and tools/moe_bench.py. Donates and returns the training state: callers
    must use the RETURNED params/opt_state (the passed-in buffers are gone).
    Returns (best_seconds_per_step, compile_s, first_metrics, last_metrics,
    params, opt_state)."""
    import jax
    import jax.numpy as jnp

    def multi(p, o, b):
        def body(c, it):
            p, o = c
            p, o, m = step(p, o, b, it)
            return (p, o), tuple(m[k] for k in metric_keys)

        (p, o), ms = jax.lax.scan(body, (p, o), jnp.arange(iters))
        return p, o, ms

    multi = jax.jit(multi, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    params, opt_state, ms = multi(params, opt_state, batch)
    first = [float(x[0]) for x in ms]
    compile_s = time.perf_counter() - t0
    best, last = float("inf"), first
    for _ in range(reps):
        t0 = time.perf_counter()
        params, opt_state, ms = multi(params, opt_state, batch)
        barrier = float(ms[0][-1])  # ONE forced fetch = completion barrier
        best = min(best, (time.perf_counter() - t0) / iters)
        # remaining metrics fetched outside the timed window
        last = [barrier] + [float(x[-1]) for x in ms[1:]]
    return best, compile_s, first, last, params, opt_state


def run_bench(iters: int, mbs: int, seq: int, recompute: str = "full",
              policy: str = None, ce_chunks: int = 0,
              rope_scaling: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.core.parallel_state import build_mesh
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.training_step import make_jitted_train_step

    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()

    layers, hidden, heads, kv, ffn, vocab = 24, 1024, 16, 16, 4096, 32000
    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        # fallback exists to produce *a* line, not a meaningful number
        iters, mbs, layers = 2, 2, 2
        if seq > 2048:
            # long-context liveness check: keep the full sequence (RoPE
            # scaling + masking path under test) but shrink width — the CPU
            # XLA-attention fallback materializes [sq, skv] scores, which at
            # real width would run for tens of minutes or OOM
            mbs, hidden, heads, kv, ffn, vocab = 1, 256, 4, 4, 1024, 2048
    cfg = make_config(
        "llama2",
        num_layers=layers,
        hidden_size=hidden,
        num_attention_heads=heads,
        num_attention_heads_kv=kv,
        ffn_hidden_size=ffn,
        vocab_size=vocab,
        seq_length=seq,
        max_position_embeddings=max(2048, seq),
        rope_scaling_factor=rope_scaling,
        params_dtype="bfloat16",
        micro_batch_size=mbs,
        global_batch_size=mbs,
        train_iters=100,
        lr=1e-4,
    )
    # measured on v5e (PERF.md sweep): full recompute + mbs 16 beats
    # selective + mbs 8 (40.0% vs 35.3% MFU) — the bigger batch amortizes
    # fixed overheads more than the extra forward costs
    cfg.parallel.recompute_granularity = (
        None if recompute == "none" else recompute
    )
    if policy is not None:
        cfg.training.remat_policy = policy
    if ce_chunks:
        # head-fused vocab-chunked CE (ops/cross_entropy.py) — sweep knob
        cfg.model.ce_vocab_chunks = ce_chunks
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        step, _opt, sh = make_jitted_train_step(cfg, mesh, params)
        opt_state = sh["opt_state_value"]

        tok = jax.random.randint(jax.random.PRNGKey(1), (mbs, seq + 1), 0, vocab)
        batch = sh["place_batch"]({
            "tokens": tok[:, :-1],
            "labels": tok[:, 1:],
            "loss_mask": jnp.ones((mbs, seq), jnp.float32),
        })

        dt, compile_s, first, last, params, opt_state = timed_multistep(
            step, params, opt_state, batch, iters,
            reps=1 if on_cpu else 3,
        )
        loss0, loss = first[0], last[0]

        # secondary: per-dispatch step time (what a host-driven loop sees).
        # Only measured on TPU — the CPU fallback used to COPY the full
        # step time here, which tripped the 5 s dispatch budget on every
        # CPU contract line and (worse) stamped ``error`` on the round
        # records the drift detector reads, silently hiding fresh
        # trajectory points.  Un-measured fields are omitted, not faked.
        dispatch_dt = None
        if not on_cpu:
            t0 = time.perf_counter()
            for i in range(5):
                params, opt_state, m = step(params, opt_state, batch, i)
            _ = float(m["lm loss"])
            dispatch_dt = (time.perf_counter() - t0) / 5

    mem = {}
    stats = jax.local_devices()[0].memory_stats()  # None on XLA:CPU
    if stats:
        mem["peak_hbm_gib"] = round(stats["peak_bytes_in_use"] / 2**30, 2)

    peak = peak_flops()
    mfu = (flops_per_token(n_params, layers, hidden, seq) * mbs * seq / dt
           / peak) if peak else 0.0
    return {
        "metric": metric_name(seq),
        "value": round(mfu * 100, 2),
        "unit": "%MFU",
        "vs_baseline": round(mfu / BASELINE_MFU, 3),
        "tokens_per_sec": round(mbs * seq / dt, 1),
        "step_time_s": round(dt, 4),
        **({"step_time_dispatch_s": round(dispatch_dt, 4)}
           if dispatch_dt is not None else {}),
        "compile_time_s": round(compile_s, 1),
        "n_params": n_params,
        "loss": round(loss, 4),
        # sanity signal, not a gate: a valid timing is reported either way
        "loss_descended": bool(loss < loss0),
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        **mem,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--mbs", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--recompute", default="full",
                    choices=["none", "selective", "full"])
    ap.add_argument("--policy", default=None,
                    help="remat policy when --recompute selective "
                         "(default: the config default, "
                         "save_dots_except_logits)")
    ap.add_argument("--ce_chunks", type=int, default=0,
                    help="vocab chunks for head-fused CE (0 = off)")
    ap.add_argument("--rope_scaling", type=float, default=1.0,
                    help="RoPE position-interpolation factor (long-context "
                         "mode, e.g. --seq 32768 --rope_scaling 8)")
    ap.add_argument("--watchdog", type=float, default=1500.0)
    args = ap.parse_args()

    finished = threading.Event()

    def on_timeout():
        if finished.is_set():  # result already emitted; don't double-print
            return
        fail(f"watchdog: bench exceeded {args.watchdog}s", seq=args.seq)
        os._exit(3)

    dog = threading.Timer(args.watchdog, on_timeout)
    dog.daemon = True
    dog.start()

    try:
        if probe_backend() == "cpu":
            from megatron_llm_tpu.utils.platform import pin_cpu_platform

            pin_cpu_platform()
        result = run_bench(args.iters, args.mbs, args.seq,
                           recompute=args.recompute, policy=args.policy,
                           ce_chunks=args.ce_chunks,
                           rope_scaling=args.rope_scaling)
        finished.set()
        dog.cancel()
        if result["backend"] != "cpu":
            emit(result)
        else:
            # Off-TPU the headline MUST be 0 — a CPU step time divided by a
            # nominal "peak" is not an MFU measurement.  The run still
            # proves the train step executes end to end, so its numbers
            # survive under cpu_sanity.
            emit(cpu_contract_line(result, args.seq))
    except Exception as e:  # structured error, never a bare traceback
        finished.set()
        dog.cancel()
        fail(f"{type(e).__name__}: {e}", seq=args.seq)
        sys.exit(1)


if __name__ == "__main__":
    main()
