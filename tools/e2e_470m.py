"""End-to-end language-quality run of the 470M bench model (VERDICT r3 item 8,
extended per r4 item 8 to a staged full-epoch run with resume exercised).

One command: corpus -> preprocess -> train the bench.py model shape
(24 x h1024 x ffn4096, the "470M" config, vocab from the corpus) ->
WIKITEXT-adjusted perplexity on held-out paragraphs through tasks/main.py.
Prints ONE bench.py-style JSON line and persists E2E_470M.json (evidence
iff ``backend`` is a TPU).

The corpus is tools/make_e2e_corpus.py --rich (~2M tokens of genuine
English prose from installed-package docs, zero egress, reproducible).
A FULL epoch is ~2M tokens; at gbs 16 x seq 256 (TPU) that is ~500
iters (minutes), at gbs 4 (the CPU plan-B recipe) ~2000 iters (~32 h of
single-core time). ``--stage_iters N`` therefore runs the training in
stages of N iters, each stage a separate finetune.py process resuming
from the previous stage's checkpoint (real resume through the tracker
file + consumed_samples fast-forward), with a WIKITEXT eval after every
stage and E2E_470M.json rewritten incrementally — a run killed at any
point still leaves the best-so-far trajectory as evidence, and restarts
of this script continue from the checkpoint instead of from scratch.

Backend: this orchestrator stays off jax (a parent that touched the chip
would hold it from the training children), so the platform is asked of a
child that exits first.  On TPU train bf16 (the bench dtype); the CPU
recipes (``JAX_PLATFORMS=cpu``) are the early-exit contract line or, with
``--force_cpu_full``, the documented plan-B (fp32, gbs 4 — a day of
single-core time otherwise).  No accelerator without ``JAX_PLATFORMS=cpu``
is a non-zero exit.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import cpu_requested  # noqa: E402

OUT_PATH = os.path.join(REPO, "E2E_470M.json")
METRIC = "e2e_470m_wikitext_adjusted_ppl"


def probe_backend() -> str:
    """'tpu' or 'cpu', from a child process (module docstring)."""
    if cpu_requested():
        return "cpu"
    r = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True)
    lines = r.stdout.strip().splitlines()
    if r.returncode != 0 or not lines or lines[-1] == "cpu":
        print(json.dumps({"metric": METRIC, "value": 0, "unit": "ppl",
                          "error": "no accelerator and JAX_PLATFORMS=cpu was "
                                   "not asked for: " + r.stderr[-300:]}),
              flush=True)
        sys.exit(1)
    return "tpu"


def cpu_contract_record() -> dict:
    """The off-TPU early-exit line (also asserted by test_bench_contract)."""
    return {
        "metric": METRIC, "value": 0, "unit": "ppl", "vs_baseline": 0,
        "backend": "cpu",
        "note": "off-TPU: full run is a day of single-core time; "
                "use --force_cpu_full or the documented plan-B recipe "
                "(docs/guide/e2e_smoke.md)"}


def run(cmd, env=None, tail=4000):
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, env=env)
    if r.returncode != 0:
        label = next((c for c in cmd if c.endswith(".py")), cmd[0])
        raise RuntimeError(
            f"{os.path.basename(label)} "
            f"rc={r.returncode}: {(r.stderr or r.stdout)[-tail:]}")
    return r.stdout or ""


def run_logged(cmd, log_path, env=None, tail=8000):
    """Like run() but streams stdout+stderr to ``log_path`` (append) — an
    hours-long background training stage must not hold its progress in a
    pipe that dies with the process. Returns the log tail for parsing."""
    with open(log_path, "a") as lf:
        lf.write(f"\n==== {time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}"
                 f" {' '.join(os.path.basename(c) for c in cmd[:3])} ====\n")
        lf.flush()
        r = subprocess.run(cmd, cwd=REPO, stdout=lf,
                           stderr=subprocess.STDOUT, text=True, env=env)
    with open(log_path) as lf2:
        out_tail = lf2.read()[-tail:]
    if r.returncode != 0:
        label = next((c for c in cmd if c.endswith(".py")), cmd[0])
        raise RuntimeError(
            f"{os.path.basename(label)} rc={r.returncode}: {out_tail[-4000:]}")
    return out_tail


def parse_train_loss(out: str):
    """Last "lm loss: X" on a training-iteration line; None when the log
    format drifts — this is metadata, never worth discarding the run over
    (ADVICE r4: an uncaught ValueError here threw away hours of training)."""
    loss = None
    for line in out.splitlines():
        if "lm loss:" in line and "iteration" in line:
            try:
                loss = float(line.split("lm loss:")[1].split("|")[0])
            except (ValueError, IndexError):
                pass
    return loss


def done_iters(ckpt: str) -> int:
    """Completed iterations per the checkpoint tracker (0 = fresh start)."""
    try:
        with open(os.path.join(
                ckpt, "latest_checkpointed_iteration.txt")) as f:
            txt = f.read().strip()
        return 0 if txt == "release" else int(txt)
    except (OSError, ValueError):
        return 0


def model_flags(seq, dtype, mbs, gbs, iters, vocab_file, flash):
    f = ["--model_name", "gpt",
         "--num_layers", "24", "--hidden_size", "1024",
         "--num_attention_heads", "16", "--ffn_hidden_size", "4096",
         "--seq_length", str(seq), "--max_position_embeddings", str(seq),
         "--params_dtype", dtype,
         "--micro_batch_size", str(mbs), "--global_batch_size", str(gbs),
         "--train_iters", str(iters),
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", vocab_file]
    if not flash:
        f.append("--no_use_flash_attn")
    return f


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default="/tmp/e2e470m_auto")
    ap.add_argument("--iters", type=int, default=300,
                    help="total training iterations (the epoch is ~2M "
                         "tokens: ~500 iters at gbs 16, ~2000 at gbs 4)")
    ap.add_argument("--stage_iters", type=int, default=0,
                    help="train in resume-exercising stages of this many "
                         "iters, WIKITEXT eval + E2E_470M.json rewrite "
                         "after each (0 = single shot)")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--watchdog", type=float, default=7200.0,
                    help="clean-exit guard")
    ap.add_argument("--force_cpu_full", action="store_true",
                    help="run the full recipe even on CPU (hours)")
    args = ap.parse_args()
    if args.force_cpu_full:
        # the CPU-full path is ~a day of single-core time; the default
        # guard would discard hours of training at the 2h mark
        args.watchdog = max(args.watchdog, 172800.0)

    def on_timeout():
        print(json.dumps({"metric": METRIC, "value": 0, "unit": "ppl",
                          "vs_baseline": 0,
                          "error": f"watchdog: exceeded {args.watchdog}s"}),
              flush=True)
        os._exit(3)

    dog = threading.Timer(args.watchdog, on_timeout)
    dog.daemon = True
    dog.start()

    t0 = time.time()
    backend = probe_backend()
    on_tpu = backend != "cpu"
    if not on_tpu and not args.force_cpu_full:
        print(json.dumps(cpu_contract_record()), flush=True)
        return
    wd = args.workdir
    os.makedirs(wd, exist_ok=True)
    train_log = os.path.join(wd, "train.log")

    cpu_env = dict(os.environ)
    cpu_env["JAX_PLATFORMS"] = "cpu"
    # corpus + preprocess always on CPU (pure host work)
    if not os.path.exists(os.path.join(wd, "corpus.bin")):
        run([sys.executable, "tools/make_e2e_corpus.py", "--out", wd,
             "--rich", "--rich_max_mb", "8", "--vocab_words", "8000"],
            env=cpu_env)
        run([sys.executable, "tools/preprocess_data.py",
             "--input", os.path.join(wd, "train.jsonl"),
             "--output_prefix", os.path.join(wd, "corpus"),
             "--tokenizer_type", "BertWordPieceLowerCase",
             "--vocab_file", os.path.join(wd, "vocab.txt"),
             "--append_eod"], env=cpu_env)

    if on_tpu:
        dtype, mbs, gbs, flash, env = "bfloat16", 16, 16, True, dict(os.environ)
        total = args.iters
    else:  # --force_cpu_full
        dtype, mbs, gbs, flash, env = "float32", 4, 4, False, cpu_env
        total = args.iters if args.stage_iters else max(args.iters // 2, 100)

    vocab = os.path.join(wd, "vocab.txt")
    ckpt = os.path.join(wd, "ckpt")
    stage = args.stage_iters or total

    def lr_flags(train_iters, save_interval):
        # --lr_decay_iters=total: each stage sees train_iters=<its target>,
        # so without the explicit decay horizon the cosine would complete
        # per-stage and the LR would sawtooth across resumes instead of
        # following ONE schedule over the whole run
        return ["--lr", "3e-4", "--lr_decay_style", "cosine",
                "--lr_warmup_iters", str(max(total // 10, 10)),
                "--lr_decay_iters", str(total),
                "--data_path", os.path.join(wd, "corpus"),
                "--split", "98,2,0",
                "--save", ckpt, "--save_interval", str(save_interval),
                "--log_interval", "50",
                "--eval_interval", str(train_iters), "--eval_iters", "20"]

    def wikitext_eval():
        eval_out = run(
            [sys.executable, "tasks/main.py", "--task", "WIKITEXT103",
             "--valid_data", os.path.join(wd, "valid.txt"), "--load", ckpt,
             *model_flags(args.seq, dtype, mbs, gbs, total, vocab, flash)],
            env=env)
        for line in eval_out.splitlines():
            if "WIKITEXT103" in line:
                return ast.literal_eval(line.strip())["WIKITEXT103"]
        raise RuntimeError(f"no WIKITEXT103 result in: {eval_out[-2000:]}")

    def write_record(result, train_loss, done, resumes, final):
        rec = {
            "metric": METRIC, "value": round(result["ppl"], 2), "unit": "ppl",
            "vs_baseline": 0,  # no reference number for this corpus —
                               # evidence, not a comparison
            "backend": backend,
            "timestamp_utc": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "train": {"iters": done, "target_iters": total, "gbs": gbs,
                      "seq": args.seq, "dtype": dtype,
                      "final_lm_loss": train_loss,
                      "tokens_seen": done * gbs * args.seq,
                      "resumes": resumes, "complete": final},
            "eval": {k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in result.items()},
            "trajectory": trajectory,
            "wall_s": round(time.time() - t0, 1),
        }
        with open(OUT_PATH + ".tmp", "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(OUT_PATH + ".tmp", OUT_PATH)
        return rec

    trajectory, resumes = [], 0
    if os.path.exists(OUT_PATH) and done_iters(ckpt) > 0:
        try:  # script restart mid-run: keep the earlier stages' points and
            # the resume count (each stage after the first IS a resume; a
            # record that said "resumes: 0" after a restart would deny the
            # property this staged design exists to prove)
            with open(OUT_PATH) as f:
                prior = json.load(f)
            trajectory = prior.get("trajectory", [])
            resumes = prior.get("train", {}).get("resumes", 0)
        except (OSError, ValueError):
            pass

    rec = None
    while True:
        done = done_iters(ckpt)
        if done >= total:
            break
        # final-stage alignment: a save only fires when iteration %
        # save_interval == 0, so a partial last stage (e.g. 500 -> 550)
        # must shrink the interval or the tracker never advances and the
        # loop would respawn the same stage forever
        target = min(done + stage, total)
        save_every = min(stage, target - done)
        cmd = [sys.executable, "-u", "finetune.py",
               *model_flags(args.seq, dtype, mbs, gbs, target, vocab, flash),
               *lr_flags(target, save_every)]
        if done > 0:
            cmd += ["--load", ckpt]
            resumes += 1
        out_tail = run_logged(cmd, train_log, env=env)
        train_loss = parse_train_loss(out_tail)
        now_done = done_iters(ckpt)
        if now_done <= done:  # progress guard: never spin on a stage that
            raise RuntimeError(  # exits without advancing the tracker
                f"stage made no checkpoint progress (tracker {done} -> "
                f"{now_done}, target {target}); see {train_log}")
        done = now_done
        result = wikitext_eval()
        trajectory.append({
            "iters": done, "tokens": done * gbs * args.seq,
            "ppl": round(result["ppl"], 2), "train_loss": train_loss})
        rec = write_record(result, train_loss, done, resumes, done >= total)
        print(json.dumps({"stage_done": done, "target": total,
                          "ppl": rec["value"]}), flush=True)

    if rec is None:  # training already complete on entry: eval only
        result = wikitext_eval()
        rec = write_record(result, None, done_iters(ckpt), resumes, True)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
