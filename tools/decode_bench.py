"""Decode-path benchmark on the local chip — KV-cached autoregressive
generation tokens/sec and per-token latency (VERDICT round-3 item 5: the
decode path had correctness tests but no performance number on any backend).

    python tools/decode_bench.py [--batches 1,8 --prompt 128 --gen 128]

Measures the device-resident ``lax.while_loop`` decode
(generation/generation.py:100-203 — the one-program analog of the
reference's per-token host loop, /root/reference/megatron/text_generation/
generation.py:89) on the 470M bench model, greedy sampling, early
termination off so every run emits exactly ``--gen`` tokens.

Prefill vs decode split without intra-program timers: the whole
prefill+loop runs as ONE program, so two runs are timed per batch size —
``samples_length = prompt+1`` (prefill + a single sampled token) and
``prompt+gen`` — and the decode-only rate is ``(b*(gen-1)) / (T_full -
T_prefill1)``. Both programs are compiled before any timing.

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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (  # noqa: E402
    cpu_contract_line,
    probe_backend,
)


def bench_one(cfg, params, batch: int, prompt: int, gen: int, vocab: int,
              reps: int) -> dict:
    """Time generation at one batch size; returns the per-size row."""
    import jax
    import numpy as np

    from megatron_llm_tpu.generation import generation as g

    rng = np.random.default_rng(0)
    S = prompt + gen
    tokens = rng.integers(1, vocab, (batch, S), dtype=np.int32)
    lengths = np.full((batch,), prompt, dtype=np.int32)
    key = jax.random.PRNGKey(0)

    def run(samples_length):
        r = g.generate_tokens(
            cfg, params, tokens, lengths, samples_length,
            prefill_len=prompt, termination_id=0, sample_key=key,
            top_k=1,  # greedy
            use_eod_for_termination=False,  # exact gen-token runs
        )
        jax.block_until_ready(r.tokens)
        return r

    def timed(samples_length):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(samples_length)
            best = min(best, time.perf_counter() - t0)
        return best

    # compile both programs (separate samples_length values share one
    # compilation — samples_length is a traced arg — but the first call
    # pays the compile)
    t0 = time.perf_counter()
    run(prompt + 1)
    compile_s = time.perf_counter() - t0

    t_prefill1 = timed(prompt + 1)        # prefill + 1 decoded token
    t_full = timed(S)                     # prefill + gen decoded tokens
    decode_s = max(t_full - t_prefill1, 1e-9)
    n_decode = gen - 1
    return {
        "batch": batch,
        "prompt_len": prompt,
        "gen_len": gen,
        "compile_time_s": round(compile_s, 1),
        "prefill_plus1_s": round(t_prefill1, 4),
        "total_s": round(t_full, 4),
        "decode_tok_s": round(batch * n_decode / decode_s, 1),
        "decode_ms_per_token": round(decode_s / n_decode * 1e3, 3),
        "prefill_tok_s": round(batch * prompt / t_prefill1, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,8",
                    help="comma-separated batch sizes")
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--gen", type=int, default=128)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 for the transformer layers "
                         "(ops/quant.py W8A16)")
    ap.add_argument("--watchdog", type=float, default=1500.0)
    args = ap.parse_args()

    # bench.py's clean-exit watchdog
    finished = threading.Event()

    def on_timeout():
        if finished.is_set():
            return
        print(json.dumps({
            "metric": "decode_tok_s_llama470m_1chip", "value": 0.0,
            "unit": "tok/s",
            "error": f"watchdog: decode bench exceeded {args.watchdog}s",
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
            "metric": "decode_tok_s_llama470m_1chip", "value": 0.0,
            "unit": "tok/s", "error": f"{type(e).__name__}: {e}",
        }), flush=True)
        sys.exit(1)


def _run(args, finished):
    layers, hidden, heads, ffn, vocab = 24, 1024, 16, 4096, 32000
    batches = [int(x) for x in args.batches.split(",")]
    if probe_backend() == "cpu":
        from megatron_llm_tpu.utils.platform import pin_cpu_platform

        pin_cpu_platform()
        # liveness shape, not a measurement
        layers, args.prompt, args.gen, args.reps = 2, 32, 16, 1
        batches = batches[:1]

    import jax

    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()

    cfg = make_config(
        "llama2", num_layers=layers, hidden_size=hidden,
        num_attention_heads=heads, num_attention_heads_kv=heads,
        ffn_hidden_size=ffn, vocab_size=vocab,
        seq_length=max(2048, args.prompt + args.gen),
        max_position_embeddings=max(2048, args.prompt + args.gen),
        params_dtype="bfloat16",
        micro_batch_size=1, global_batch_size=1, train_iters=1,
    )
    mesh = build_mesh(devices=jax.devices()[:1])
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        if args.int8:
            # weight-only int8 (ops/quant.py): decode is HBM-bound, so
            # halving the layer-weight bytes is the headline lever
            from megatron_llm_tpu.ops.quant import quantize_layer_weights_int8

            params = quantize_layer_weights_int8(params)
        rows = [bench_one(cfg, params, b, args.prompt, args.gen, vocab,
                          args.reps) for b in batches]

    headline = rows[-1]  # largest batch
    variant = "_int8" if args.int8 else ""
    result = {
        "metric": f"decode_tok_s_llama470m{variant}_b{headline['batch']}"
                  f"_p{args.prompt}_g{args.gen}_1chip",
        "value": headline["decode_tok_s"],
        "unit": "tok/s",
        "n_params": n_params,
        "rows": rows,
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
    }
    if result["backend"] == "cpu":
        result = cpu_contract_line(result, tag="decode" + variant)
    finished.set()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
