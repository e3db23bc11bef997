#!/usr/bin/env python
"""Launch the REST text-generation server —
tools/run_text_generation_server.py analog (:24-90).

Loads a model from a checkpoint (or random-inits a tiny one with
``--random_init`` for smoke testing) and serves PUT /api.  Single process:
no torchrun, no rank loop (ranks >0 in the reference spin on broadcast —
SPMD needs none of that).

Default engine is the continuous-batching paged-KV engine
(generation/engine.py): concurrent requests share fused decode ticks, a
refcounted prefix cache reuses shared-prompt KV pages (``--prefix_cache``),
and prefill runs in schedulable chunks packed into the decode tick
(``--prefill_chunk``, a positive whole number of pages).
``--legacy_engine`` serves the
dense one-request-at-a-time path instead.  Engine geometry and
backpressure come from ``cfg.inference`` (--max_batch_slots, --page_size,
--page_watermark, --max_queued_requests: overflow answers a structured 503
with an EMA-drain Retry-After, docs/guide/serving.md).

Scheduling is pluggable (``--sched_policy fcfs|priority|slo``,
generation/scheduling/): requests may carry ``priority`` (0 = most
urgent) and ``ttft_deadline_ms``/``tpot_deadline_ms`` fields; priority
and slo policies reorder admission, preempt low-value decodes by page
release (resume is bitwise through the prefix cache), and shed requests
whose deadline is already unmeetable.  ``--sched_aging_s`` bounds
starvation, ``--sched_quota "0:64,2:16"`` bounds queue depth per class.

Speculative decoding (``--spec_k N --spec_draft
"llama2:num_layers=2,...[@/ckpt/dir]"``, generation/speculative/): a
draft model proposes N tokens per tick and the target verifies them in
one forward — losslessly (greedy output is bitwise-identical to
``--spec_k 0``; sampled output matches the target distribution).  The
draft's K/V shares the engine's paged pool; ``/health`` exposes the
live acceptance rate under ``spec``.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model_name", default="llama2")
    ap.add_argument("--load", help="checkpoint directory to serve")
    ap.add_argument("--tokenizer_type", default="HFTokenizer")
    ap.add_argument("--tokenizer_model", help="tokenizer name/path")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=5000,
                    help="0 = ephemeral: the OS picks a free port and the "
                         "bound port is printed on startup (local fleets "
                         "spawn replicas this way without port races)")
    ap.add_argument("--random_init", action="store_true",
                    help="serve a random tiny model (smoke test)")
    ap.add_argument("--legacy_engine", action="store_true",
                    help="serve the dense single-stream InferenceEngine "
                         "instead of the continuous-batching engine")
    ap.add_argument("--register_url",
                    help="router base url to heartbeat POST "
                         "/admin/register at (elastic discovery: the "
                         "router needs --allow_registration; no static "
                         "--replica entry required)")
    ap.add_argument("--register_interval", type=float, default=2.0,
                    help="seconds between registration heartbeats")
    ap.add_argument("--advertise_url",
                    help="url the router should reach this replica at "
                         "(default http://127.0.0.1:<bound port>)")
    ap.add_argument("--serving_role", default="unified",
                    choices=("unified", "prefill", "decode"),
                    help="disaggregated prefill/decode role advertised in "
                         "/health (serving/handoff/): the router's disagg "
                         "policy sends long-prompt prefills to prefill-"
                         "role replicas, which push the KV pages to a "
                         "decode-role replica over POST /admin/kv_push; "
                         "unified (default) serves both phases exactly "
                         "as before")
    args, extra = ap.parse_known_args()

    import jax

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.generation import (
        ContinuousBatchingEngine,
        InferenceEngine,
    )
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.tokenizer import build_tokenizer
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    cfg = parse_args(
        ["--model_name", args.model_name] + extra
        + (["--tokenizer_type", args.tokenizer_type] if args.tokenizer_type else [])
        + (["--tokenizer_model", args.tokenizer_model] if args.tokenizer_model else [])
    )
    tokenizer = build_tokenizer(cfg)
    if cfg.model.vocab_size is None:
        cfg.model.vocab_size = tokenizer.vocab_size

    # --tp N (--tensor_model_parallel_size) shards the engine over a named
    # mesh: params by the parallel/tp.py rules, the KV pool over the heads
    # dim — one engine then serves a model larger than a single chip's HBM.
    # tp=1 keeps the single-chip engine unchanged.  --pp N
    # (--pipeline_model_parallel_size) additionally runs the tick as pp
    # pipeline stages (parallel/pp_serve.py): each stage holds L/pp layers
    # of params AND pool — tp*pp chips per replica.  --pp 1 builds no mesh
    # axis work at all (byte-for-byte the flat engine).
    mesh = None
    if not args.legacy_engine and (
            cfg.parallel.tensor_model_parallel_size > 1
            or cfg.parallel.pipeline_model_parallel_size > 1):
        from megatron_llm_tpu.core.parallel_state import (
            build_mesh, set_global_mesh,
        )

        mesh = build_mesh(
            tensor_model_parallel_size=(
                cfg.parallel.tensor_model_parallel_size),
            pipeline_model_parallel_size=(
                cfg.parallel.pipeline_model_parallel_size),
            data_parallel_size=1,
        )
        set_global_mesh(mesh)
        print(f"engine mesh: {dict(mesh.shape)}", flush=True)

    # params are born under their shardings (one jitted init, or a sharded
    # load): a full copy on the first device would cap the servable model
    # at one chip's HBM however many chips the mesh has
    key = jax.random.PRNGKey(cfg.training.seed)
    template = jax.eval_shape(lambda k: init_model_params(cfg, k), key)
    shardings = None
    if mesh is not None:
        from megatron_llm_tpu.parallel.tp import param_shardings

        shardings = param_shardings(mesh, template)
    if args.random_init:
        params = jax.jit(lambda k: init_model_params(cfg, k),
                         out_shardings=shardings)(key)
    else:
        if not args.load:
            ap.error("--load is required unless --random_init")
        from megatron_llm_tpu.checkpointing import load_checkpoint

        params, _, _, _, _ = load_checkpoint(
            cfg, args.load, template, param_shardings=shardings)

    if args.legacy_engine:
        engine = InferenceEngine(cfg, params, tokenizer)
    else:
        engine = ContinuousBatchingEngine(cfg, params, tokenizer, mesh=mesh)
        if mesh is not None:
            from megatron_llm_tpu.core.parallel_state import placement_report

            print(placement_report(
                mesh, params=engine.params,
                kv_pool=engine.pool.kv), flush=True)
    server = MegatronServer(engine, register_url=args.register_url,
                            register_interval_s=args.register_interval,
                            advertise_url=args.advertise_url,
                            role=args.serving_role)
    kind = "legacy" if args.legacy_engine else "continuous-batching"
    if args.serving_role != "unified":
        kind += f", role={args.serving_role}"
    if not args.legacy_engine:
        kind += f", sched={engine.policy.name}"
        if engine.spec_k:
            kind += (f", spec_k={engine.spec_k} "
                     f"(draft {engine.draft_cfg.model.num_layers}L)")
    # bind BEFORE printing so --port 0 reports the real ephemeral port
    # (fleet spawners parse this line, then poll /health until ready)
    port = server.bind(args.host, args.port)
    print(f"serving ({kind}) on http://{args.host}:{port}/api", flush=True)
    server.serve()


if __name__ == "__main__":
    main()
