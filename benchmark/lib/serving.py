"""Serving runs (traffic kinds ``open_loop`` and ``closed_loop``).

The run's process holds the chip.  It builds tokenizer, weights, engine and
HTTP server the way ``tools/run_text_generation_server.py`` does — one
departure, stated in PERF.md: that CLI keeps random-init weights in float32
and casts them at every use, which does not fit a 16 GB chip at these
depths, so the weights are made in the dtype the configuration serves them
in (bf16), one layer at a time inside one jitted call.  A jax-free child
process (``lib/client.py``) is the load generator.

Order of a run: weights -> engine, server -> the probe prompts streamed
through the HTTP API (they warm up every tick shape and the copy-on-write
page copy, and their emitted tokens' log-probs are what ``correct``
compares: lib/check.py) -> the reference on prompts + emitted tokens ->
ramp -> the measured window -> drain -> reduce.
"""

from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Dict, Optional

from benchmark.lib import check as check_mod
from benchmark.lib import client as client_mod
from benchmark.lib import harness, readers, stats


def init_weights(cfg, key, dtype_name: str, shardings=None):
    """The program's own initialisers, layer by layer (``lax.map``) so that
    the float32 draws of one layer are the largest temporary, cast to the
    serving dtype as they are made."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.models.transformer import init_layer_params

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    one = copy.deepcopy(cfg)
    one.model.num_layers = 1

    def build(k):
        k_layers, k_rest = jax.random.split(k)
        rest = init_model_params(one, k_rest)      # embedding, norm, head
        rest = {n: v for n, v in rest.items() if n != "layers"}
        cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
        layers = jax.lax.map(
            lambda kk: cast(init_layer_params(cfg, kk)),
            jax.random.split(k_layers, cfg.model.num_layers))
        return {**cast(rest), "layers": layers}

    return jax.jit(build, out_shardings=shardings)(key)


def parse_prometheus(text: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^(\S+?)(\{.*\})?\s+(\S+)$", line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def _get(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _hit_tokens(url: str) -> float:
    return parse_prometheus(_get(url + "/metrics")).get(
        "mlt_engine_prefix_hit_tokens_total", 0.0)


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def run(cell, args, clock) -> harness.Run:
    import jax
    import numpy as np

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.tokenizer import build_tokenizer

    run_ = harness.Run(cell, args, clock)
    run_.stamp_device()
    compiles = harness.CompileCounter()
    mix = dict(cell.traffic)
    flags: Dict = {"seed": int(args.seed) % (2 ** 31 - 1)}
    if args.rehearsal:
        flags.update(cell.config.get("rehearsal", {}).get("flags", {}))
        cell.model.update(cell.config.get("rehearsal", {}).get("model", {}))
        mix.update(mix.get("rehearsal", {}))
    if args.rate is not None:
        mix["rate_per_s"] = args.rate
    cfg = parse_args(cell.flags(flags))
    tokenizer = build_tokenizer(cfg)
    vocab = cfg.model.vocab_size

    # as the server CLI: a mesh only when the layout asks for one
    mesh = shardings = None
    par = cfg.parallel
    if par.tensor_model_parallel_size > 1 or par.pipeline_model_parallel_size > 1:
        from megatron_llm_tpu.core.parallel_state import build_mesh, set_global_mesh
        from megatron_llm_tpu.parallel.tp import param_shardings

        mesh = build_mesh(
            tensor_model_parallel_size=par.tensor_model_parallel_size,
            pipeline_model_parallel_size=par.pipeline_model_parallel_size,
            data_parallel_size=1)
        set_global_mesh(mesh)
        shardings = param_shardings(mesh, jax.eval_shape(
            lambda k: init_model_params(cfg, k), jax.random.PRNGKey(0)))
    key = jax.random.PRNGKey(cfg.training.seed)
    params = init_weights(cfg, key, cell.config.get("weights_dtype", "bfloat16"),
                          shardings)

    engine = ContinuousBatchingEngine(cfg, params, tokenizer, mesh=mesh)
    run_.engine = {"max_slots": engine.max_slots, "page_size": engine.page_size,
                   "prefill_chunk": engine.prefill_chunk,
                   "max_seq": engine.max_seq}
    server = MegatronServer(engine)
    port = server.start_background("127.0.0.1", 0)
    url = f"http://127.0.0.1:{port}"
    child: Optional[subprocess.Popen] = None
    prof = harness.Profiler(run_) if args.trace else None
    try:
        sampling = mix.get("sampling", {})
        probes = check_mod.serve_probes(
            args.seed, vocab, tuple(mix.get("probe_lengths", (192, 256))),
            engine.page_size)
        hits_before = _hit_tokens(url)

        def ask(probe):
            s = client_mod.stream_request(
                "127.0.0.1", port, probe["prompt"], check_mod.PROBE_TOKENS,
                sampling, 600.0)
            if s["status"] != 200 or s["error"]:
                raise RuntimeError(f"probe {probe['name']} failed: "
                                   f"{s['status']} {s['error']}")
            probe.update(tokens=s["tokens"], logprobs=s["logprobs"])

        together = [threading.Thread(target=ask, args=(p,))
                    for p in probes if p["after"] is None]
        for th in together:
            th.start()
        for th in together:
            th.join(timeout=600)
        for p in probes:
            if p["after"] is not None:
                ask(p)
        probe_hits = _hit_tokens(url) - hits_before
        ref = check_mod.serve_against_reference(cell, params, probes)

        out = harness.out_dir(cell, "client")
        samples_path = os.path.join(out, "samples.json")
        ramp = float(mix.get("ramp_s", 0.0))
        t0 = time.monotonic() + 1.5
        traffic_path = os.path.join(out, "traffic.json")
        with open(traffic_path, "w") as f:
            json.dump(mix, f)
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = cell.root + os.pathsep + env.get("PYTHONPATH", "")
        child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.lib.client", "--url", url,
             "--traffic", traffic_path, "--seed", str(args.seed),
             "--seconds", str(run_.seconds), "--vocab", str(vocab),
             "--t0", repr(t0), "--out", samples_path],
            cwd=cell.root, env=env)
        run_.t_open, run_.t_close = t0 + ramp, t0 + ramp + run_.seconds
        _sleep_until(run_.t_open)
        before = parse_prometheus(_get(url + "/metrics"))
        run_.setup_s = run_.t_open - clock.process_start
        if prof:
            span = float(mix.get("trace_seconds", 3.0))
            _sleep_until(run_.t_open + (run_.seconds - span) / 2)
            prof.start()
            time.sleep(span)
            prof.stop()
        _sleep_until(run_.t_close)
        after = parse_prometheus(_get(url + "/metrics"))
        run_.counters = {k: after[k] - before.get(k, 0.0) for k in after}
        run_.compiles_in_window = compiles.inside(run_.t_open, run_.t_close)
        limit = float(mix.get("drain_s", 10.0)) + 60.0
        try:
            child.wait(timeout=limit)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(timeout=30)
            raise RuntimeError("the load generator did not finish in time")
        if child.returncode != 0:
            raise RuntimeError(f"the load generator exited {child.returncode}")
        with open(samples_path) as f:
            run_.all_samples = json.load(f)["samples"]
        health = json.loads(_get(url + "/health"))
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait(timeout=30)
        server.stop()

    _reduce_samples(run_, mix)
    # every log-prob the engine streamed: the probes' and the window's
    streamed = [lp for p in probes for lp in p["logprobs"]]
    streamed += [lp for s in run_.samples for lp in s["logprobs"]]
    finite = bool(np.isfinite(np.asarray(streamed, np.float64)).all())
    cached = engine.cache is not None
    run_.checks = {"compiles_in_window": run_.compiles_in_window,
                   "logprobs_finite": finite, "logprobs_streamed": len(streamed),
                   "engine_failures": health.get("engine_failures"),
                   "probe_prefix_hit_tokens": probe_hits if cached else None,
                   "prefix_hit_tokens": run_.counters.get(
                       "mlt_engine_prefix_hit_tokens_total"), **ref}
    run_.correct = (run_.compiles_in_window == 0 and finite
                    and ref["reference_ok"] and run_.attempted > 0
                    and health.get("engine_failures") == 0
                    and (probe_hits > 0 or not cached))
    if prof:
        prof.reduce()
    return run_


def _reduce_samples(run_: harness.Run, mix: Dict) -> None:
    """Pick the measured requests, count failures, print the medians and
    sample counts the contract's last line has no room for."""
    t_open, t_close = run_.t_open, run_.t_close
    open_loop = mix["kind"] == "open_loop"
    if open_loop:
        measured = [s for s in run_.all_samples
                    if t_open <= s["due_t"] <= t_close]
    else:
        # closed loop: every request that held a client during the window
        measured = [s for s in run_.all_samples
                    if s.get("sent_t") is not None
                    and (s.get("done_t") or t_close) >= t_open
                    and s["sent_t"] <= t_close]
    abandoned = client_mod.ABANDONED
    # a closed-loop request cut off at the window's end before its first
    # token was neither answered nor refused: it is not part of the run
    measured = [s for s in measured
                if s["error"] != abandoned or s["token_t"]]
    run_.samples = measured
    ok, bad = readers.ok_samples(run_), readers.failed_samples(run_)
    run_.attempted, run_.failed = len(measured), len(bad)
    late = [s["sent_t"] - s["due_t"] for s in measured
            if open_loop and s.get("sent_t") is not None]
    ttft = [s["token_t"][0] - s["due_t"] for s in ok]
    gaps = readers.token_gaps(run_)
    early = [s for s in ok if s.get("done_t") and len(s["token_t"]) < s["n_out"]]
    in_window = readers.tokens_in_window(run_)
    fmt = lambda v: "n/a" if v is None else f"{v * 1e3:.2f} ms"  # noqa: E731
    print(f"benchmark: {len(measured)} requests measured, {len(bad)} failed; "
          f"generator lateness median {fmt(stats.median(late))} worst "
          f"{fmt(max(late) if late else None)}; TTFT median "
          f"{fmt(stats.median(ttft))} over {len(ttft)} requests; token gap "
          f"median {fmt(stats.median(gaps))} over {len(gaps)} gaps; "
          f"{in_window} tokens received in the window; streams ended early "
          f"on end-of-document: {len(early)} of {len(ok)}", flush=True)
    if open_loop and ok:
        # the knee's test: does the backlog grow through the window?
        third = (t_close - t_open) / 3.0
        first = [s["token_t"][0] - s["due_t"] for s in ok
                 if s["due_t"] < t_open + third]
        last = [s["token_t"][0] - s["due_t"] for s in ok
                if s["due_t"] >= t_close - third]
        print(f"benchmark: offered {float(mix['rate_per_s']):g} req/s; mean TTFT of "
              f"requests due in the first third {fmt(stats.mean(first))} "
              f"({len(first)}), in the last third {fmt(stats.mean(last))} "
              f"({len(last)}); unanswered {len(bad)}", flush=True)
    for s in bad[:5]:
        print(f"benchmark: failed request {s['id']}: status {s['status']} "
              f"{s['error']}", flush=True)
