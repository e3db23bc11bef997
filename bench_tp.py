"""Tensor-parallel mesh benchmark — prints ONE JSON line for the driver.

Metric: training-step throughput and decode-tick throughput of the SAME
model/step code across mesh layouts (``--tp 1,4`` by default), exercising
the end-to-end GSPMD path of ISSUE 6: params sharded by the
``parallel/tp.py`` rules, batch over (dp, ep), the engine's paged KV pool
over the heads dim.  For every layout it verifies the MECHANISM, not just
the timing:

* param leaves actually carry tp shardings (spec check on qkv/fc kernels);
* the compiled step contains the column/row-parallel collectives the
  ``tp.py`` docstring promises (``all-reduce`` in the optimized HLO —
  absent at tp=1, present at tp>1);
* the final loss matches tp=1 within a documented tolerance (row-parallel
  contractions change the reduction order; nothing else may drift);
* engine decode on a tp-sharded pool emits the same tokens as tp=1.

The ISSUE 15 overlap arm (``--tp_overlap ring``, default on) re-runs
every tp>1 layout with the chunked collective-matmul forward
(parallel/overlap.py) and machine-checks the mechanism: ppermute chain +
``forward-tp{N}-overlap`` scope in the compiled HLO, loss rel <= 1e-4
vs the overlap-off row (chunked-GEMM reassociation — tolerance, not
bitwise), and engine greedy-token identity.  On TPU the per-layout
ring-vs-off steps/sec IS the overlap payoff; on CPU the arm is a
mechanism/parity record.

On a CPU host the virtual devices share one core, so "scaling" numbers are
NOT speedups — the CPU line is a correctness/liveness record (headline 0
by contract, run under ``cpu_sanity``) whose compile/dispatch fields feed
the bench-contract host-cost budgets (bench.apply_budgets).  On TPU the
per-layout steps/sec IS the scaling evidence.

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
    apply_budgets,
    cpu_contract_line,
    probe_backend,
)

METRIC = "tp_mesh_train_steps_s"
EVIDENCE_TAG = "tp"


def tiny_cfg(tp: int, dp: int, seq: int, layers: int, hidden: int,
             overlap: str = "off"):
    from megatron_llm_tpu.config import Config, apply_architecture

    cfg = Config()
    apply_architecture(cfg, "llama2")
    cfg.parallel.tp_overlap = overlap
    cfg.model.num_layers = layers
    cfg.model.hidden_size = hidden
    cfg.model.num_attention_heads = 4
    cfg.model.num_attention_heads_kv = 4
    cfg.model.vocab_size = 512
    cfg.model.max_position_embeddings = max(256, seq)
    cfg.data.seq_length = seq
    cfg.data.tokenizer_type = "NullTokenizer"
    cfg.training.params_dtype = "float32"
    cfg.training.use_flash_attn = False
    cfg.training.micro_batch_size = 4
    cfg.training.global_batch_size = 4 * dp
    cfg.training.train_iters = 4
    cfg.parallel.tensor_model_parallel_size = tp
    cfg.parallel.data_parallel_size = dp
    cfg.finalize(n_devices=tp * dp)
    return cfg


def _sharded_param_report(params, shardings) -> dict:
    """Count leaves whose NamedSharding spec references the tp axis, and
    spot-check that the canonical rules landed (qkv column-parallel,
    fc2/dense row-parallel)."""
    import jax

    leaves = jax.tree_util.tree_leaves_with_path(shardings)
    tp_sharded = 0
    rules_seen = {"qkv_col": False, "row_parallel": False, "vocab": False}
    for path, sh in leaves:
        spec = tuple(sh.spec)
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        flat = [x for part in spec if part is not None
                for x in (part if isinstance(part, tuple) else (part,))]
        if "tp" in flat:
            tp_sharded += 1
            if "qkv" in names and spec and spec[-1] == "tp":
                rules_seen["qkv_col"] = True
            if ("fc2" in names or "dense" in names) and "tp" in flat:
                rules_seen["row_parallel"] = True
            if "word_embeddings" in names or "lm_head" in names:
                rules_seen["vocab"] = True
    return {"tp_sharded_leaves": tp_sharded, **rules_seen}


def bench_train_layout(tp: int, dp: int, iters: int, seq: int,
                       layers: int, hidden: int,
                       overlap: str = "off") -> dict:
    """Run the real jitted train step on a (tp, dp) mesh; return timings +
    mechanism checks.  ``overlap='ring'`` exercises the ISSUE 15 chunked
    collective-matmul forward; its rows carry the ring mechanism
    evidence (ppermute chain + overlap scope asserted in compiled HLO)."""
    import jax
    import numpy as np

    from megatron_llm_tpu.core import parallel_state as ps
    from megatron_llm_tpu.core import rng as rng_mod
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.parallel.tp import param_shardings
    from megatron_llm_tpu.training_step import make_jitted_train_step

    cfg = tiny_cfg(tp, dp, seq, layers, hidden, overlap=overlap)
    mesh = ps.build_mesh_from_config(cfg)
    with ps.global_mesh(mesh):
        key = rng_mod.init_key(1234)
        shapes = jax.eval_shape(lambda k: init_model_params(cfg, k), key)
        p_shard = param_shardings(mesh, shapes)
        params = jax.jit(lambda k: init_model_params(cfg, k),
                         out_shardings=p_shard)(key)
        step_fn, optimizer, shardings = make_jitted_train_step(
            cfg, mesh, params)
        opt_state = optimizer.init(params)
        gbs = cfg.training.global_batch_size
        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(1, 512, (gbs, seq)).astype(np.int32),
            "labels": rng.integers(1, 512, (gbs, seq)).astype(np.int32),
            "loss_mask": np.ones((gbs, seq), np.float32),
        }
        placed = shardings["place_batch"](batch)
        lr = jax.numpy.float32(1e-3)

        # mechanism: the collectives GSPMD inserted for this layout; the
        # ring arm additionally asserts the decomposed structure — a
        # ppermute chain (collective-permute ops) and the
        # forward-tp{N}-overlap scope in the HLO op metadata
        lowered = step_fn.lower(params, opt_state, placed, lr)
        hlo = lowered.compile().as_text()
        all_reduce_count = hlo.count("all-reduce")
        ppermute_count = hlo.count("collective-permute")
        overlap_scope_in_hlo = f"forward-tp{tp}-overlap" in hlo

        t0 = time.perf_counter()
        params2, opt2, metrics = step_fn(params, opt_state, placed, lr)
        jax.block_until_ready(metrics["lm loss"])
        compile_s = time.perf_counter() - t0

        best = float("inf")
        dispatch = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            params2, opt2, metrics = step_fn(params2, opt2, placed, lr)
            t_disp = time.perf_counter() - t0
            jax.block_until_ready(metrics["lm loss"])
            dt = time.perf_counter() - t0
            best = min(best, dt)
            dispatch = min(dispatch, t_disp)
        loss = float(metrics["lm loss"])
        report = _sharded_param_report(params, p_shard)
    return {
        "tp": tp, "dp": dp,
        "tp_overlap": overlap,
        "step_time_s": round(best, 4),
        "steps_per_sec": round(1.0 / best, 3),
        "step_time_dispatch_s": round(dispatch, 4),
        "compile_time_s": round(compile_s, 1),
        "loss": loss,
        "all_reduce_count": all_reduce_count,
        "collective_permute_count": ppermute_count,
        "overlap_scope_in_hlo": overlap_scope_in_hlo,
        **report,
    }


def bench_engine_layout(tp: int, ticks: int, overlap: str = "off") -> dict:
    """Decode ticks/sec + token stream on a (possibly tp-sharded) engine."""
    import jax

    from megatron_llm_tpu.core import parallel_state as ps
    from megatron_llm_tpu.generation.engine import ContinuousBatchingEngine
    from megatron_llm_tpu.models import init_model_params

    cfg = tiny_cfg(1, 1, 64, 2, 64, overlap=overlap)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    mesh = None
    if tp > 1:
        mesh = ps.build_mesh(tensor_model_parallel_size=tp,
                             data_parallel_size=1,
                             devices=jax.devices()[:tp])
    eng = ContinuousBatchingEngine(
        cfg, params, None, max_slots=4, num_pages=64, page_size=16,
        mesh=mesh)
    prompts = [[2 + (7 * i + j) % 500 for j in range(13)] for i in range(4)]
    reqs = [eng.submit(p, ticks, temperature=1.0, top_k=0, top_p=0.0,
                       seed=11 + i) for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    toks = [r.result()[0] for r in reqs]
    return {
        "tp": tp,
        "tp_overlap": overlap,
        "decode_wall_s": round(wall, 3),
        "ticks": eng.ticks,
        "ticks_per_sec": round(eng.ticks / wall, 2) if wall else 0.0,
        "tokens": toks,
    }


def run_overlap_arm(tps, iters: int, seq: int, layers: int, hidden: int,
                    engine_ticks: int, base_rows, base_eng) -> dict:
    """The ISSUE 15 overlap on/off arm: for every tp > 1 layout, run the
    SAME train step and engine with ``--tp_overlap ring`` and verify the
    mechanism + numerics against the overlap-off rows measured above:

    * the compiled ring HLO carries a ppermute chain (collective-permute
      ops beyond the off layout's) and the ``forward-tp{N}-overlap``
      scope in op metadata — overlap asserted, not assumed;
    * training loss matches overlap-off within rel 1e-4 (chunked-GEMM
      reassociation: tolerance, NOT bitwise — parallel/overlap.py
      documents why);
    * engine greedy decode emits identical tokens.
    """
    off_by_tp = {r["tp"]: r for r in base_rows if "skipped" not in r}
    eng_by_tp = {r["tp"]: r for r in (base_eng or [])}
    rows, mechanism_ok = [], True
    for tp in tps:
        if tp <= 1 or tp not in off_by_tp:
            continue
        row = bench_train_layout(tp, 1, iters, seq, layers, hidden,
                                 overlap="ring")
        off = off_by_tp[tp]
        loss_rel = (abs(row["loss"] - off["loss"])
                    / max(abs(off["loss"]), 1e-12))
        checks = {
            "overlap_scope_in_hlo": row["overlap_scope_in_hlo"],
            "ppermute_chain": (row["collective_permute_count"]
                               > off.get("collective_permute_count", 0)),
            "loss_rel_vs_off": round(loss_rel, 9),
            "loss_parity_ok": loss_rel <= 1e-4,
        }
        entry = {**row, **checks,
                 "speedup_vs_off": round(off["step_time_s"]
                                         / row["step_time_s"], 3)}
        if engine_ticks and tp in eng_by_tp:
            ering = bench_engine_layout(tp, engine_ticks, overlap="ring")
            entry["engine_ticks_per_sec"] = ering["ticks_per_sec"]
            entry["engine_tokens_match_off"] = (
                ering.pop("tokens") == eng_by_tp[tp].get("tokens"))
            checks["engine_tokens_match_off"] = entry[
                "engine_tokens_match_off"]
        ok = (checks["overlap_scope_in_hlo"] and checks["ppermute_chain"]
              and checks["loss_parity_ok"]
              and checks.get("engine_tokens_match_off", True))
        entry["mechanism_ok"] = ok
        mechanism_ok = mechanism_ok and ok
        rows.append(entry)
    return {"layouts": rows, "mechanism_ok": mechanism_ok}


def run(iters: int, tps, seq: int, layers: int, hidden: int,
        engine_ticks: int, overlap_arm: str = "ring") -> dict:
    import jax

    n_dev = len(jax.devices())
    rows = []
    for tp in tps:
        if tp > n_dev:
            rows.append({"tp": tp, "skipped": f"needs {tp} devices, "
                                              f"have {n_dev}"})
            continue
        rows.append(bench_train_layout(tp, 1, iters, seq, layers, hidden))
    ok_rows = [r for r in rows if "skipped" not in r]
    base = next((r for r in ok_rows if r["tp"] == 1), None)
    parity = None
    if base is not None:
        parity = {
            f"tp{r['tp']}_loss_delta": round(abs(r["loss"] - base["loss"]), 8)
            for r in ok_rows if r["tp"] != 1
        }

    eng_rows, eng_parity = [], None
    if engine_ticks:
        for tp in tps:
            if tp > n_dev:
                continue
            eng_rows.append(bench_engine_layout(tp, engine_ticks))
        eb = next((r for r in eng_rows if r["tp"] == 1), None)
        if eb is not None:
            eng_parity = all(r["tokens"] == eb["tokens"]
                             for r in eng_rows if r["tp"] != 1)

    # the ISSUE 15 overlap arm rides on the off rows just measured
    # (needs the engine token streams, so it runs before the pop)
    overlap = None
    if overlap_arm == "ring":
        overlap = run_overlap_arm(tps, iters, seq, layers, hidden,
                                  engine_ticks, ok_rows, eng_rows)
    for r in eng_rows:
        r.pop("tokens", None)

    head = max(ok_rows, key=lambda r: r["tp"], default=None)
    result = {
        "metric": METRIC,
        "value": head["steps_per_sec"] if head else 0.0,
        "unit": "steps/s",
        "layouts": rows,
        "loss_parity_vs_tp1": parity,
        "engine_layouts": eng_rows,
        "engine_tokens_match_tp1": eng_parity,
        "overlap": overlap,
        "n_devices": n_dev,
        "backend": jax.devices()[0].platform,
    }
    if head:
        # headline timing fields at top level so the bench-contract
        # host-cost budgets bind to them (bench.apply_budgets)
        for k in ("step_time_s", "step_time_dispatch_s", "compile_time_s"):
            result[k] = head[k]
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--tp", default="1,4",
                    help="comma-separated tp sizes to sweep")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--engine_ticks", type=int, default=8,
                    help="decode ticks per engine parity row (0 = skip)")
    ap.add_argument("--tp_overlap", default="ring",
                    choices=["off", "ring"],
                    help="run the compute/collective-overlap arm for "
                         "tp > 1 layouts (ISSUE 15; 'off' skips it)")
    ap.add_argument("--watchdog_s", type=float, default=1200.0)
    args = ap.parse_args()
    tps = [int(x) for x in args.tp.split(",") if x]

    def on_timeout():
        print(json.dumps({"metric": METRIC, "value": 0.0,
                          "unit": "steps/s",
                          "error": f"watchdog {args.watchdog_s}s"}),
              flush=True)
        os._exit(3)

    timer = threading.Timer(args.watchdog_s, on_timeout)
    timer.daemon = True
    timer.start()

    backend = probe_backend()
    if backend == "cpu":
        # host-device-count sanity mode: the layout sweep needs virtual
        # devices (the committed evidence is an 8-device CPU record);
        # without this pin a bare host would skip every tp > 1 row
        from megatron_llm_tpu.utils.platform import pin_cpu_platform

        pin_cpu_platform(n_devices=8)
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    result = run(args.iters, tps, args.seq, args.layers, args.hidden,
                 args.engine_ticks, overlap_arm=args.tp_overlap)
    timer.cancel()

    if backend == "tpu" and result["backend"] == "tpu":
        line = apply_budgets(dict(result))
    else:
        line = cpu_contract_line(result, tag=EVIDENCE_TAG)
        line["metric"] = METRIC
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
