"""MoE training-step benchmark on the local chip — reproduces the PERF.md
"MoE training step" table (Mixtral-style 8-expert top-2, 531M total / 191M
active params). Prints one JSON line; same device contract as bench.py.

    python tools/moe_bench.py [--experts 8 --topk 2 --mbs 8 --seq 1024]

MFU accounting uses ACTIVE parameters (each token runs topk of the E expert
FFNs): 6*N_active + causal-attention FLOPs — the standard MoE utilization
metric. The reference has no MoE path to compare against (SURVEY §2.1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (  # noqa: E402
    cpu_contract_line,
    flops_per_token,
    peak_flops,
    probe_backend,
    timed_multistep,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--topk", type=int, default=2)
    ap.add_argument("--mbs", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--ffn", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    if probe_backend() == "cpu":
        from megatron_llm_tpu.utils.platform import pin_cpu_platform

        pin_cpu_platform()
        args.iters, args.mbs, args.layers = 2, 2, 2

    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.training_step import make_jitted_train_step
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    E, K = args.experts, args.topk
    L, h, f = args.layers, args.hidden, args.ffn
    mbs, seq = args.mbs, args.seq
    heads = max(h // 64, 1)
    cfg = make_config(
        "mixtral", num_layers=L, hidden_size=h, num_attention_heads=heads,
        num_attention_heads_kv=heads, ffn_hidden_size=f, vocab_size=32000,
        seq_length=seq, max_position_embeddings=max(2048, seq),
        params_dtype="bfloat16", num_experts=E, moe_router_topk=K,
        moe_group_size=min(seq, 4096), micro_batch_size=mbs,
        global_batch_size=mbs, train_iters=100, lr=1e-4,
    )
    mesh = build_mesh(devices=jax.devices()[:1])
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        step, _o, sh = make_jitted_train_step(cfg, mesh, params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (mbs, seq + 1), 0, 32000)
        batch = sh["place_batch"]({
            "tokens": tok[:, :-1], "labels": tok[:, 1:],
            "loss_mask": jnp.ones((mbs, seq), jnp.float32),
        })
        o = sh["opt_state_value"]
        best, compile_s, _first, last = timed_multistep(
            step, params, o, batch, args.iters,
            metric_keys=("lm loss", "moe aux loss"),
        )[:4]

        expert_params = L * E * 3 * h * f
        active = n_params - expert_params * (E - K) // E
        flops_tok = flops_per_token(active, L, h, seq)  # shared accounting
        peak = peak_flops()  # None on an explicit CPU run
        mfu = flops_tok * mbs * seq / best / peak if peak else 0.0
        result = {
            "metric": f"train_active_mfu_moe{E}x{K}_seq{seq}_1chip",
            "value": round(mfu * 100, 2),
            "unit": "%MFU(active)",
            "tokens_per_sec": round(mbs * seq / best, 1),
            "step_time_s": round(best, 4),
            "compile_time_s": round(compile_s, 1),
            "n_params": n_params,
            "n_active_params": active,
            "loss": round(last[0], 4),
            "aux": round(last[1], 4),
            "backend": jax.devices()[0].platform,
        }
        if result["backend"] == "cpu":
            result = cpu_contract_line(result, seq, tag=f"moe{E}x{K}")
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
