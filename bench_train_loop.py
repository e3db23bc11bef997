"""Training-loop overlap benchmark — prints ONE JSON line for the driver.

Metric: steady-state training steps/sec of the OVERLAPPED driver loop
(async dispatch depth 2 + background data prefetch + deferred metrics,
ISSUE 2) versus the fully BLOCKING loop (depth 0, no prefetch, per-step
metric sync — the pre-ISSUE-2 driver), running the real ``pretrain`` loop
end to end with SIMULATED host-side data latency: the synthetic provider
sleeps for one measured device-step time per batch, the regime where the
host data path costs a full step per iteration — exactly what the
reference's pinned-memory worker pipeline (and our prefetch stage) exists
to hide.  Both modes run identical configs, so the ratio isolates the
loop restructure.

Gate (ISSUE 2 acceptance): overlapped >= 1.5x blocking steps/sec on the
CPU sanity shape (asserted by tests/test_async_loop.py's slow-lane gate
test; an ideal overlap of equal host/device times is 2x).

Same device contract as bench.py (``bench.probe_backend``); a watchdog
turns hangs into structured error lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import (  # noqa: E402
    cpu_contract_line,
    probe_backend,
)

METRIC = "train_loop_overlap_steps_s_1chip"


def make_provider(latency_s: float, vocab: int, seq: int, seed: int = 0):
    """Synthetic in-memory data provider for ``pretrain``: deterministic
    batches, each pull paying ``latency_s`` of simulated host-side
    collate/tokenize cost."""
    import numpy as np

    def provider(cfg, tokenizer, consumed_samples):
        gbs = cfg.training.global_batch_size
        rng = np.random.default_rng(seed)
        # a fixed pool of batches, cycled: data cost is the sleep, not RNG
        pool = [
            {
                "tokens": rng.integers(1, vocab, (gbs, seq)).astype(np.int32),
                "labels": rng.integers(1, vocab, (gbs, seq)).astype(np.int32),
                "loss_mask": np.ones((gbs, seq), np.float32),
            }
            for _ in range(4)
        ]

        def gen():
            i = 0
            while True:
                if latency_s > 0:
                    time.sleep(latency_s)
                yield pool[i % len(pool)]
                i += 1

        return gen(), None

    return provider


def run_mode(make_cfg, latency_s: float, vocab: int, seq: int,
             dispatch_depth: int, prefetch_depth: int, iters: int) -> dict:
    """One full pretrain() run; returns its steady-state timing fields."""
    from megatron_llm_tpu.training import pretrain

    cfg = make_cfg(iters)
    cfg.training.async_dispatch_depth = dispatch_depth
    cfg.training.prefetch_depth = prefetch_depth
    result = pretrain(
        cfg, data_iterators_provider=make_provider(latency_s, vocab, seq)
    )
    return {
        "steps_per_sec": result["steady_steps_per_sec"],
        "warmup_s": result["warmup_time"],
        "loss": result["loss_series"][-1][1] if result["loss_series"] else None,
    }


def _run(args, finished):
    import jax

    layers, hidden, heads, ffn, vocab = 24, 1024, 16, 4096, 32000
    seq, mbs = 512, 8
    if probe_backend() == "cpu":
        from megatron_llm_tpu.utils.platform import pin_cpu_platform

        pin_cpu_platform()
        # CPU sanity shape: small enough for tier-1 time, big enough that
        # a device step is tens of ms — a real overlap target, not noise
        layers, hidden, heads, ffn, vocab = 2, 256, 4, 512, 1024
        seq, mbs = 128, 4
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()

    from megatron_llm_tpu.models import make_config

    def make_cfg(iters):
        return make_config(
            "llama2", num_layers=layers, hidden_size=hidden,
            num_attention_heads=heads, num_attention_heads_kv=heads,
            ffn_hidden_size=ffn, vocab_size=vocab, seq_length=seq,
            max_position_embeddings=seq,
            params_dtype="bfloat16" if jax.default_backend() != "cpu"
            else "float32",
            use_flash_attn=jax.default_backend() != "cpu",
            micro_batch_size=mbs, global_batch_size=mbs, train_iters=iters,
            log_interval=10 ** 6,  # no mid-run log drains: pure loop timing
            eval_interval=0, tokenizer_type=None,
        )

    # calibrate: measure the blocking device-step time with zero data
    # latency, then set the simulated latency EQUAL to it — the ideal
    # overlap regime (blocking = S + L = 2S, overlapped ~= max(S, L) = S)
    calib = run_mode(make_cfg, 0.0, vocab, seq, 0, 0, args.calib_iters)
    step_s = 1.0 / max(calib["steps_per_sec"] or 1e-9, 1e-9)
    latency_s = min(max(step_s, 0.02), 0.5)

    blocking = run_mode(make_cfg, latency_s, vocab, seq, 0, 0, args.iters)
    overlapped = run_mode(make_cfg, latency_s, vocab, seq,
                          args.dispatch_depth, args.prefetch_depth, args.iters)

    speedup = (overlapped["steps_per_sec"] or 0.0) / max(
        blocking["steps_per_sec"] or 1e-9, 1e-9)
    result = {
        "metric": METRIC,
        "value": round(overlapped["steps_per_sec"] or 0.0, 3),
        "unit": "steps/s",
        "speedup_vs_blocking": round(speedup, 2),
        "blocking_steps_per_sec": round(blocking["steps_per_sec"] or 0.0, 3),
        "step_ms": round(step_s * 1e3, 2),
        "data_latency_ms": round(latency_s * 1e3, 2),
        "iters": args.iters,
        "dispatch_depth": args.dispatch_depth,
        "prefetch_depth": args.prefetch_depth,
        "model": {"layers": layers, "hidden": hidden, "seq": seq, "mbs": mbs},
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
    }
    if result["backend"] == "cpu":
        result = cpu_contract_line(result, tag="train_loop")
    finished.set()
    print(json.dumps(result), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=24,
                    help="measured iterations per mode (first excluded as "
                         "compile/warmup)")
    ap.add_argument("--calib_iters", type=int, default=8)
    ap.add_argument("--dispatch_depth", type=int, default=2)
    ap.add_argument("--prefetch_depth", type=int, default=2)
    ap.add_argument("--watchdog", type=float, default=1500.0)
    args = ap.parse_args()

    finished = threading.Event()

    def on_timeout():
        if finished.is_set():
            return
        print(json.dumps({
            "metric": METRIC, "value": 0.0, "unit": "steps/s",
            "error": f"watchdog: train loop bench exceeded {args.watchdog}s",
        }), flush=True)
        os._exit(3)

    dog = threading.Timer(args.watchdog, on_timeout)
    dog.daemon = True
    dog.start()

    try:
        _run(args, finished)
    except Exception as e:  # structured error line, never a bare traceback
        finished.set()
        print(json.dumps({
            "metric": METRIC, "value": 0.0, "unit": "steps/s",
            "error": f"{type(e).__name__}: {e}",
        }), flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
