#!/usr/bin/env python
"""Does the system still start on the chip?  One trainer run and one server
run through the normal entry points, at Mistral-7B widths cut by depth only,
random weights from a seed.

    python chip_smoke.py              # on a TPU host: 1 chip, or 4 (tp2 x dp2)
    python chip_smoke.py --rehearsal [1|4]   # CPU, tiny shapes, never a pass

This process never imports jax (a parent that touched the chip would hold
it).  Each phase is one child process that exits before the next starts:

    probe    jax.devices() — anything but a TPU ends the run
    kernels  tools/tpu_kernel_check.py --quick: every compiled Pallas kernel
             against the repo's jnp implementation of the same op
    data     integer-string documents from a seed -> tools/preprocess_data.py
    train    finetune.py --model_name mistral-7b, ~10 steps
    serve    tools/run_text_generation_server.py --random_init, driven over
             HTTP by this (jax-free) process

A phase fails the run when its child exits non-zero, an answer is not a 200,
or a line of evidence is missing from its log (the attention path each
compiled program took, falling finite losses, token counts, finite
log-probs, a prefix-cache hit, zero engine failures).  Nothing is retried.

Every phase prints one JSON line: the device as jax reports it, set-up
(compile) seconds and wall seconds.  These say the program starts; they are
set-up and liveness figures, not performance.  The last line of stdout is
``{"ok": true, "device": {...}}`` and the exit code 0 only if every phase
passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
NEEDS = ("finetune.py", "tools/run_text_generation_server.py",
         "tools/preprocess_data.py", "tools/tpu_kernel_check.py",
         "megatron_llm_tpu/__init__.py")
DEADLINE_S = 1150.0   # the whole run, compilation included
SEED = 1234

# compile-only predictions of the train step's per-chip peak for the two
# layouts below (XLA buffer assignment for a v5e topology, ISSUE 21)
PREDICTED_PEAK_GIB = {1: 12.06, 4: 8.32}

PROBE = ("import json, jax; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))")


class SmokeFailure(Exception):
    pass


def need(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


class Run:
    """The children this run started, its clock and its output directory."""

    def __init__(self, out: str, rehearsal: int):
        self.out = out
        self.rehearsal = bool(rehearsal)
        self.t0 = time.monotonic()
        self.children: list[subprocess.Popen] = []
        self.device: dict = {}
        self.env = dict(os.environ)
        if rehearsal:
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={rehearsal}")

    def left(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def spawn(self, name: str, argv: list[str]) -> tuple[subprocess.Popen, str]:
        """Start one child in its own process group, output to a log."""
        log = os.path.join(self.out, f"{name}.log")
        with open(log, "w") as f:
            p = subprocess.Popen(argv, cwd=ROOT, env=self.env, stdout=f,
                                 stderr=subprocess.STDOUT,
                                 start_new_session=True)
        self.children.append(p)
        return p, log

    def run(self, name: str, argv: list[str], limit: float) -> str:
        """One child to completion; returns its log.  Non-zero is fatal."""
        p, log = self.spawn(name, argv)
        try:
            rc = p.wait(timeout=max(1.0, min(limit, self.left())))
        except subprocess.TimeoutExpired:
            self.stop(p)
            raise SmokeFailure(f"{name}: no exit within its time limit "
                               f"(see {log})\n{tail(log)}")
        text = open(log).read()
        need(rc == 0, f"{name}: child exited {rc}\n{tail(log)}")
        return text

    def stop(self, p: subprocess.Popen) -> None:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGTERM)
                p.wait(timeout=20)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait(timeout=20)

    def stop_all(self) -> None:
        for p in self.children:
            self.stop(p)

    def report(self, phase: str, t_start: float, setup_s, **evidence) -> None:
        line = {"phase": phase, **self.device,
                "setup_s": None if setup_s is None else round(setup_s, 1),
                "wall_s": round(time.monotonic() - t_start, 1), **evidence}
        if self.rehearsal:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)


def tail(path: str, n: int = 40) -> str:
    try:
        return "".join(open(path).readlines()[-n:])
    except OSError:
        return ""


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_probe(run: Run) -> None:
    t = time.monotonic()
    text = run.run("probe", [sys.executable, "-c", PROBE], 180)
    run.device = json.loads(text.strip().splitlines()[-1])
    want = "cpu" if run.rehearsal else "tpu"
    need(run.device["platform"] == want,
         f"probe: jax found platform {run.device['platform']!r}, not {want!r}"
         " — chip_smoke.py passes only on a TPU")
    need(run.device["count"] in (1, 4),
         f"probe: {run.device['count']} devices; layouts exist for 1 and 4")
    run.report("probe", t, None)


def phase_kernels(run: Run) -> None:
    t = time.monotonic()
    text = run.run("kernels", [sys.executable, "tools/tpu_kernel_check.py",
                               "--quick"], 500)
    lines = text.splitlines()
    passed = [l for l in lines if l.startswith("PASS ")]
    need(not any(l.startswith("FAIL") for l in lines), "kernels: FAIL line")
    for kernel in ("flash fwd", "flash bwd", "paged decode", "paged prefill",
                   "paged ragged"):
        need(any(l.startswith(f"PASS {kernel}") for l in passed),
             f"kernels: no PASS line for {kernel!r}")
    need(any("kv_dtype=int8" in l for l in passed),
         "kernels: no quantized-pool case passed")
    run.report("kernels", t, None, checks_passed=len(passed))


def phase_data(run: Run, seq: int) -> str:
    """Documents with learnable structure: each walks a 64-token alphabet
    with its own stride, so the next token follows from the last two."""
    t = time.monotonic()
    rng = random.Random(SEED)
    src = os.path.join(run.out, "docs.jsonl")
    with open(src, "w") as f:
        for _ in range(64):
            start, stride = rng.randrange(64), rng.choice((1, 2, 3, 5))
            toks = [2 + (start + stride * i) % 64 for i in range(seq + 100)]
            f.write(json.dumps({"text": " ".join(map(str, toks))}) + "\n")
    prefix = os.path.join(run.out, "corpus")
    run.run("data", [sys.executable, "tools/preprocess_data.py",
                     "--input", src, "--output_prefix", prefix,
                     "--tokenizer_type", "NullTokenizer", "--append_eod"], 180)
    for ext in (".bin", ".idx"):
        need(os.path.getsize(prefix + ext) > 0, f"data: empty corpus{ext}")
    run.report("data", t, None, documents=64)
    return prefix


def phase_train(run: Run, shapes: dict, corpus: str) -> None:
    t = time.monotonic()
    iters = 10
    text = run.run("train", [
        sys.executable, "finetune.py", "--model_name", "mistral-7b",
        *shapes["model"], *shapes["train"],
        "--micro_batch_size", "1", "--params_dtype", "bfloat16",
        "--tokenizer_type", "NullTokenizer", "--vocab_size", "32000",
        "--data_path", corpus, "--split", "100,0,0", "--eval_iters", "0",
        "--train_iters", str(iters), "--log_interval", "1",
        "--lr", "1e-4", "--lr_decay_style", "constant",
        "--seed", str(SEED)], 600)
    losses = [float(x) for x in re.findall(r"lm loss: (\S+)", text)]
    need(len(losses) == iters, f"train: {len(losses)} loss lines, not {iters}")
    need(all(math.isfinite(x) for x in losses), f"train: losses {losses}")
    # the first loss is the untrained model's (about ln 32000 = 10.4); the
    # corpus uses 64 tokens, so at full width a model that learns anything
    # is nats below within ten steps (the rehearsal's toy width is slower)
    need(max(losses[-3:]) < losses[0] - (0.3 if run.rehearsal else 2.0),
         f"train: losses did not fall: {losses}")
    need(f"training done: {iters} iterations" in text, "train: no done line")
    check_paths(run, "train", text, ["dense"])
    m = re.search(r"first step \(compile \+ warmup\): ([0-9.]+)s", text)
    need(m, "train: no first-step line")
    placement = re.search(r"^placement: (.*)$", text, re.M)
    need(placement, "train: no placement line")
    # the allocator's view after the last step (device.memory_stats()); on
    # this runtime its peak counts live buffers only, not a program's
    # temporaries, so it stands beside the compiler's figure, not for it
    peaks = [float(x) for x in re.findall(r"peak ([0-9.]+) GiB", placement[1])]
    if not run.rehearsal:   # XLA:CPU reports no memory_stats
        need(len(peaks) == run.device["count"],
             f"train: memory_stats for {len(peaks)} devices: {placement[1]}")
    mesh = re.search(r"^mesh: (.*)$", text, re.M)
    run.report("train", t, float(m[1]), losses=losses,
               mesh=mesh[1] if mesh else None, allocator_peak_gib=peaks,
               compiler_predicted_step_peak_gib=PREDICTED_PEAK_GIB[
                   run.device["count"]],
               placement=placement[1])


def check_paths(run: Run, phase: str, text: str, ops: list[str]) -> None:
    """Every attention op of the phase's compiled programs took the Pallas
    kernel (ops/attention.announce_path prints one line per op and path)."""
    if run.rehearsal:
        return  # a CPU target takes the jnp paths by design
    for op in ops:
        need(f"[attention] {op}: pallas" in text,
             f"{phase}: no '[attention] {op}: pallas' line")
    bad = re.findall(r"^\[attention\] .*: (?:xla|jnp).*$", text, re.M)
    need(not bad, f"{phase}: a program left the kernel path: {bad}")


def put(url: str, body: dict, timeout: float):
    req = urllib.request.Request(
        url + "/api", data=json.dumps(body).encode(), method="PUT",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:500]}


def get_json(url: str, timeout: float = 30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def prompt(rng: random.Random, n: int) -> str:
    return " ".join(str(rng.randrange(2, 32000)) for _ in range(n))


def ask(url: str, text: str, new: int, logprobs: bool, timeout: float) -> list:
    """One greedy request; returns the generated token strings."""
    status, body = put(url, {"prompts": [text], "tokens_to_generate": new,
                             "top_k": 1, "logprobs": logprobs}, timeout)
    need(status == 200, f"serve: status {status}: {body}")
    n_prompt = len(text.split())
    toks = body["segments"][0]
    need(len(toks) == n_prompt + new,
         f"serve: {len(toks) - n_prompt} tokens generated, asked for {new}")
    if logprobs:
        lps = body["logprobs"][0]
        need(len(lps) == len(toks) - 1 and all(
            isinstance(x, float) and math.isfinite(x) for x in lps),
            f"serve: log-probs not finite: {lps}")
    return toks[n_prompt:]


def phase_serve(run: Run, shapes: dict) -> None:
    t = time.monotonic()
    p, log = run.spawn("serve", [
        sys.executable, "tools/run_text_generation_server.py",
        "--random_init", "--model_name", "mistral-7b",
        *shapes["model"], *shapes["serve"],
        "--params_dtype", "bfloat16", "--tokenizer_type", "NullTokenizer",
        "--vocab_size", "32000", "--seed", str(SEED),
        "--host", "127.0.0.1", "--port", "0"])
    try:
        url = None
        while url is None:
            need(p.poll() is None,
                 f"serve: server exited {p.returncode}\n{tail(log)}")
            need(run.left() > 0, f"serve: not ready in time\n{tail(log)}")
            m = re.search(r"serving \(.*\) on (http://\S+)/api", open(log).read())
            url = m[1] if m else None
            time.sleep(0.5)
        ready_s = time.monotonic() - t
        limit = max(30.0, min(500.0, run.left()))
        rng = random.Random(SEED)

        # 1. greedy, then the same again: identical tokens, from the cache
        first = prompt(rng, 40)
        t_req = time.monotonic()
        a = ask(url, first, 16, False, limit)
        first_request_s = time.monotonic() - t_req
        b = ask(url, first, 16, False, limit)
        need(a == b, f"serve: repeat differs: {a} vs {b}")
        hits = get_json(url + "/health")["prefix_hit_tokens"]
        need(hits > 0, "serve: repeated prompt hit no prefix-cache page")

        # 2. one prompt longer than --prefill_chunk, with prompt log-probs
        # (the teacher-forced prefill-chunk program)
        ask(url, prompt(rng, 150), 8, True, limit)

        # 3. a burst of eight, mixed lengths: later arrivals prefill while
        # earlier ones decode, so ticks carry both kinds of row
        lengths = (3, 130, 9, 70, 20, 100, 33, 50)
        errors: list[str] = []

        def one(i: int, text: str):
            try:
                ask(url, text, 12, i % 2 == 0, limit)
            except Exception as e:  # noqa: BLE001 — reported, then fatal
                errors.append(f"burst request {i}: {e}")

        threads = [threading.Thread(target=one, args=(i, prompt(rng, n)))
                   for i, n in enumerate(lengths)]
        for th in threads:
            th.start()
            time.sleep(0.05)
        for th in threads:
            th.join(timeout=limit)
        need(not any(th.is_alive() for th in threads), "serve: burst hung")
        need(not errors, "serve: " + "; ".join(errors))

        health = get_json(url + "/health")
        need(health["engine_failures"] == 0,
             f"serve: {health['engine_failures']} engine failures")
        need(health["peak_active_slots"] >= 2,
             "serve: the burst never decoded two requests together")
        need(p.poll() is None, f"serve: server died\n{tail(log)}")
    finally:
        run.stop(p)
    text = open(log).read()
    check_paths(run, "serve", text, ["paged_ragged", "paged_prefill"])
    placement = re.search(r"^placement: (.*)$", text, re.M)
    if run.device["count"] > 1:
        need(placement, "serve: no placement line")
        need(health["tp"] == run.device["count"], f"serve: tp {health['tp']}")
    run.report("serve", t, ready_s + first_request_s,
               ready_s=round(ready_s, 1),
               first_request_s=round(first_request_s, 1),
               requests_ok=11, prefix_hit_tokens=hits, ticks=health["ticks"],
               mesh=health["mesh"],
               placement=placement[1] if placement else None)


# ---------------------------------------------------------------------------


def shapes_for(count: int, rehearsal: bool) -> dict:
    """Depth and sequence cut to the device count; widths stay Mistral-7B's
    (h 4096, 32 query / 8 KV heads of 128, ffn 14336, window 4096) except
    in the rehearsal."""
    seq = 256 if rehearsal else {1: 4096, 4: 2048}[count]
    model = ["--hidden_size", "256", "--num_attention_heads", "8",
             "--num_attention_heads_kv", "4",
             "--ffn_hidden_size", "512"] if rehearsal else []
    if count == 1:
        train = ["--num_layers", "2"]
        serve = ["--num_layers", "4"]
    else:
        train = ["--num_layers", "4", "--tensor_model_parallel_size", "2",
                 "--data_parallel_size", "2",
                 "--use_distributed_optimizer", "true"]
        serve = ["--num_layers", "8",
                 "--tensor_model_parallel_size", str(count)]
    return {"seq": seq, "model": model, "serve": serve,
            "train": train + ["--seq_length", str(seq)]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", type=int, nargs="?", const=4, default=0,
                    choices=(1, 4), metavar="DEVICES",
                    help="debug the control flow on 1 or 4 (default) virtual "
                         "CPU devices at tiny shapes; prints 'rehearsal' and "
                         "always exits non-zero")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "smoke"),
                    help="logs and the generated corpus land here")
    args = ap.parse_args()

    missing = [f for f in NEEDS if not os.path.isfile(os.path.join(ROOT, f))]
    if missing:
        print(f"chip_smoke: not a checkout of the repo: {missing} missing "
              f"beside {__file__}", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    run = Run(args.out, args.rehearsal)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    print("chip_smoke: set-up and liveness figures, not performance"
          + (" — REHEARSAL on the CPU, not a chip result"
             if args.rehearsal else ""), flush=True)
    try:
        phase_probe(run)
        shapes = shapes_for(run.device["count"], args.rehearsal)
        if args.rehearsal:
            print("rehearsal: kernels phase skipped (compiles for a TPU only)")
        else:
            phase_kernels(run)
        corpus = phase_data(run, shapes["seq"])
        phase_train(run, shapes, corpus)
        phase_serve(run, shapes)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED after {time.monotonic() - run.t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        run.stop_all()
    if args.rehearsal:
        print("rehearsal complete: the control flow ran on the CPU; "
              "this is not a pass")
        return 3
    print(json.dumps({"ok": True, "device": run.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
