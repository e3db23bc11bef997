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
priming, where the mix asks for it -> ramp -> the measured window -> drain
-> reduce.

Priming.  A mix whose ``shared_prefix`` holds a ``prime`` group (``together``:
how many prefixes are sent at a time; ``min_hit_share`` with its reason)
has every prefix of the run's own plan streamed once for one token before
the client starts, so that the window opens on a warm prefix cache, as a
deployment's warm-up does; the time is part of ``setup_s``.  Such a run is
``correct`` only if the prefix cache then served, inside the window, at
least ``min_hit_share`` of the prefix tokens that the window's requests
carried (``mlt_engine_prefix_hit_tokens_total``): a prefix evicted before
its requests came is the configuration's pool being too small, and the
run says so.  Nothing is retried.
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
from typing import Dict, List, Optional

from benchmark.lib import check as check_mod
from benchmark.lib import client as client_mod
from benchmark.lib import harness, readers, stats
from benchmark.lib import traffic as traffic_mod


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


def _stream_all(port: int, jobs: List[Dict], n_out: int, sampling: Dict,
                together: int) -> None:
    """Stream ``jobs`` (each a dict with ``name`` and ``prompt``) through the
    HTTP API, ``together`` at a time, and leave each its ``tokens`` and
    ``logprobs``; a refusal or an error ends the run."""
    def ask(job):
        got = client_mod.stream_request("127.0.0.1", port, job["prompt"], n_out,
                                        sampling, 600.0)
        if got["status"] != 200 or got["error"]:
            job["failure"] = f"{got['status']} {got['error']}"
        job.update(tokens=got["tokens"], logprobs=got["logprobs"])

    for i in range(0, len(jobs), together):
        threads = [threading.Thread(target=ask, args=(j,))
                   for j in jobs[i:i + together]]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
    for job in jobs:
        if "tokens" not in job or job.get("failure"):
            raise RuntimeError(f"request {job['name']} failed: "
                               f"{job.get('failure', 'no answer')}")


def prime(url: str, port: int, mix: Dict, seed: int, seconds: float,
          vocab: int) -> Dict:
    """Stream every shared prefix of this run's plan once for one token (see
    the module's docstring).  The plan is the one the client child derives:
    same mix, seed, seconds and vocabulary."""
    spec = (mix.get("shared_prefix") or {}).get("prime")
    if not spec:
        return {}
    plan = traffic_mod.request_plan(mix, seed, seconds, vocab)
    jobs = [{"name": f"prefix {i}", "prompt": ids}
            for i, ids in enumerate(plan["prefixes"])]
    t = time.monotonic()
    _stream_all(port, jobs, 1, mix.get("sampling", {}), int(spec["together"]))
    out = {"primed_prefixes": len(jobs),
           "primed_tokens": sum(len(j["prompt"]) for j in jobs),
           "prime_s": time.monotonic() - t}
    print(f"benchmark: primed {out['primed_prefixes']} shared prefixes, "
          f"{out['primed_tokens']} tokens, in {out['prime_s']:.2f} s", flush=True)
    return out


def prefix_hit_share(url: str, port: int, mix: Dict, run_: harness.Run,
                     seed: int, vocab: int) -> Dict:
    """Of the prefix tokens that the requests sent inside the window carried,
    the share the prefix cache served inside the window.  Where that is
    under the mix's ``min_hit_share`` each prefix is asked for once more,
    alone, and those the cache no longer holds are printed."""
    spec = (mix.get("shared_prefix") or {}).get("prime")
    if not spec:
        return {}
    n_prefix = int(mix["shared_prefix"]["tokens"])
    carried = sum(min(n_prefix, s["n_prompt"]) for s in run_.all_samples
                  if s.get("prefix") is not None and s.get("sent_t") is not None
                  and run_.t_open <= s["sent_t"] <= run_.t_close)
    hit = run_.counters.get("mlt_engine_prefix_hit_tokens_total", 0.0)
    share = hit / carried if carried else None
    ok = share is not None and share >= float(spec["min_hit_share"])
    out = {"prefix_tokens_carried": carried, "prefix_hit_share": share,
           "min_hit_share": float(spec["min_hit_share"]), "prefixes_hit": ok}
    if not ok:
        prefixes = traffic_mod.request_plan(mix, seed, run_.seconds,
                                            vocab)["prefixes"]
        page = run_.engine["page_size"]
        gone = []
        for i, ids in enumerate(prefixes):
            before = _hit_tokens(url)
            _stream_all(port, [{"name": f"prefix {i}", "prompt": ids}], 1,
                        mix.get("sampling", {}), 1)
            if _hit_tokens(url) - before < (len(ids) - 1) // page * page:
                gone.append(i)
        out["prefixes_gone_after_window"] = gone
        print(f"benchmark: the prefix cache served {hit:.0f} of the {carried} "
              f"prefix tokens that the window's requests carried (the mix "
              f"wants a share of {spec['min_hit_share']}); asked again one by "
              f"one after the drain, the cache no longer held prefixes {gone} "
              f"of {len(prefixes)}: the pool does not keep what this mix "
              f"shares", flush=True)
    return out


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


class Served:
    """The system under test as one serving run holds it: weights, engine
    and HTTP server on a local port, with the run's mix and vocabulary."""

    def __init__(self, cell, args):
        import jax

        from megatron_llm_tpu.config.arguments import parse_args
        from megatron_llm_tpu.generation import ContinuousBatchingEngine
        from megatron_llm_tpu.generation.server import MegatronServer
        from megatron_llm_tpu.models import init_model_params
        from megatron_llm_tpu.tokenizer import build_tokenizer

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
        self.mix, self.vocab = mix, cfg.model.vocab_size
        self.params = init_weights(
            cfg, key, cell.config.get("weights_dtype", "bfloat16"), shardings)
        self.engine = ContinuousBatchingEngine(cfg, self.params, tokenizer, mesh=mesh)
        self.server = MegatronServer(self.engine)
        self.port = self.server.start_background("127.0.0.1", 0)
        self.url = f"http://127.0.0.1:{self.port}"

    def stream_probes(self, seed: int):
        """The probes of lib/check.py streamed through the HTTP API, each
        left with its ``tokens`` and ``logprobs``; and how many of their
        prompt tokens the prefix cache served."""
        mix = self.mix
        probes = check_mod.serve_probes(
            seed, self.vocab, tuple(mix.get("probe_lengths", (192, 256))),
            self.engine.page_size)
        hits_before = _hit_tokens(self.url)
        for group, together in (
                ([p for p in probes if p["after"] is None], len(probes)),
                ([p for p in probes if p["after"] is not None], 1)):
            _stream_all(self.port, group, check_mod.PROBE_TOKENS,
                        mix.get("sampling", {}), together)
        return probes, _hit_tokens(self.url) - hits_before


def run(cell, args, clock) -> harness.Run:
    import numpy as np

    run_ = harness.Run(cell, args, clock)
    run_.stamp_device()
    compiles = harness.CompileCounter()
    served = Served(cell, args)
    mix, vocab, params, engine = served.mix, served.vocab, served.params, served.engine
    server, port, url = served.server, served.port, served.url
    run_.engine = {"max_slots": engine.max_slots, "page_size": engine.page_size,
                   "prefill_chunk": engine.prefill_chunk,
                   "max_seq": engine.max_seq}
    child: Optional[subprocess.Popen] = None
    prof = harness.Profiler(run_) if args.trace else None
    try:
        probes, probe_hits = served.stream_probes(args.seed)
        ref = check_mod.serve_against_reference(cell, params, probes)
        primed = prime(url, port, mix, args.seed, run_.seconds, vocab)

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
        hits = prefix_hit_share(url, port, mix, run_, args.seed, vocab)
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
                       "mlt_engine_prefix_hit_tokens_total"),
                   **primed, **hits, **ref}
    run_.correct = (run_.compiles_in_window == 0 and finite
                    and ref["reference_ok"] and run_.attempted > 0
                    and health.get("engine_failures") == 0
                    and (probe_hits > 0 or not cached)
                    and hits.get("prefixes_hit", True))
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
