"""Resilience chaos smoke: kill -9 / corrupt / hang round-trips, with the
bench.py evidence contract.

Phases (each a bounded subprocess; the orchestrator never imports jax, so
it cannot hold the chip a child needs):

  1. **chaos** (forced CPU): an uninterrupted baseline run, then the same
     run under the supervisor with the child SIGKILLing itself mid-run;
     auto-resume must reproduce the baseline loss trajectory **bitwise**
     on every post-resume iteration.
  2. **corrupt** (forced CPU): bit-flip + truncate the latest checkpoint;
     load must quarantine it (``*.corrupt``) and fall back to the previous
     verified checkpoint.
  3. **hang** (forced CPU): a child whose data generator stalls forever;
     the step watchdog must dump stacks and exit with code 43 within the
     configured deadline.
  4. **tpu** (unless ``JAX_PLATFORMS=cpu`` was asked for): a save ->
     corrupt -> verified-fallback -> resume round-trip ON HARDWARE; a
     child that comes up on the CPU fails the phase.  The kill/hang chaos
     stays on CPU by design and the TPU evidence is the integrity+resume
     path.

Headline metric: aggregate goodput fraction (%) of the supervised
kill/resume run — the number this subsystem exists to keep high.  Off-TPU
the bench contract zeroes the headline and the measurements ride under
``cpu_sanity``.

The ``--child*`` modes are the training/corruption workloads themselves;
tests/test_resilience.py reuses them so the chaos recipe is tested code.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CHILD_ITERS = 8
KILL_AT = 5          # self-SIGKILL while pulling the batch for step 5
SAVE_INTERVAL = 2
HANG_AT = 3


def cpu_env() -> dict:
    """Hermetic CPU env for chaos children (never overwrite PYTHONPATH,
    pin the platform)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def inherit_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# child mode: tiny real pretrain() run with fault injection
# ---------------------------------------------------------------------------


def _child_cfg(args):
    from megatron_llm_tpu.config import Config, apply_architecture

    cfg = Config()
    apply_architecture(cfg, "llama2")
    cfg.model.num_layers = 2
    cfg.model.hidden_size = 64
    cfg.model.num_attention_heads = 4
    cfg.model.num_attention_heads_kv = 2
    cfg.model.vocab_size = 512
    cfg.model.max_position_embeddings = 64
    cfg.data.seq_length = 32
    cfg.data.data_path = [args.corpus]
    cfg.data.tokenizer_type = "NullTokenizer"
    cfg.training.params_dtype = "float32"
    cfg.training.use_flash_attn = False
    cfg.training.micro_batch_size = 2
    cfg.training.global_batch_size = 4
    cfg.training.train_iters = args.iters
    cfg.training.eval_interval = 0
    cfg.optimizer.lr = 1e-3
    cfg.checkpoint.save = args.save
    cfg.checkpoint.load = args.save
    cfg.checkpoint.save_interval = args.save_interval
    cfg.logging.log_interval = 1  # progress high-water mark every step
    if args.watchdog:
        cfg.resilience.watchdog = True
        cfg.resilience.watchdog_multiplier = 3.0
        cfg.resilience.watchdog_min_deadline = args.watchdog_min_deadline
        cfg.resilience.watchdog_first_deadline = args.watchdog_first_deadline
        cfg.resilience.emergency_save_timeout = 5.0
    cfg.finalize(n_devices=1)
    return cfg


def run_child(args) -> int:
    """One supervised training attempt over the toy corpus, with optional
    fault injection (self-SIGKILL / hang) driven from the data stream."""
    import jax

    from megatron_llm_tpu.training import build_data_iterators, pretrain

    cfg = _child_cfg(args)
    gbs = cfg.training.global_batch_size

    def provider(cfg, tokenizer, consumed_samples):
        loader, (train_ds, _valid, _test) = build_data_iterators(
            cfg, tokenizer)
        inner = loader(train_ds, consumed_samples)

        def stream():
            from megatron_llm_tpu.checkpointing import read_tracker

            step = consumed_samples // gbs  # 0-based step this batch feeds
            marker = args.save + ".killed"
            for batch in inner:
                step += 1
                # kill at the first pull >= kill9_at once a checkpoint is
                # COMMITTED (tracker present), so the resumed attempt
                # demonstrably restarts from the checkpoint, not from
                # scratch; once only — the resumed attempt replays these
                # very step numbers and must survive them
                if (args.kill9_at and step >= args.kill9_at
                        and not os.path.exists(marker)
                        and read_tracker(args.save)[0]):
                    open(marker, "w").close()
                    os.kill(os.getpid(), signal.SIGKILL)  # abrupt death
                if args.hang_at and step == args.hang_at:
                    time.sleep(10 ** 6)  # silent stall: watchdog's case
                yield batch

        return stream(), None

    result = pretrain(cfg, data_iterators_provider=provider)
    if args.losses:
        with open(args.losses, "a") as f:  # append: one block per attempt
            for it, loss in result["loss_series"]:
                f.write(json.dumps(
                    {"iteration": it, "loss_hex": float(loss).hex()}) + "\n")
    if args.result:
        with open(args.result, "w") as f:
            json.dump({
                "backend": jax.devices()[0].platform,
                "iteration": result["iteration"],
                "exit_reason": result["exit_reason"],
                "goodput": result["goodput"],
            }, f)
    return 0


def run_child_corrupt(args) -> int:
    """Corruption round-trip: two verified saves, flip a byte in the
    newest, assert load quarantines it and falls back; then resume
    training from the fallback.  Prints one JSON result line."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.checkpointing import (
        checkpoint_dir,
        load_checkpoint,
        read_tracker,
        save_checkpoint,
    )
    from megatron_llm_tpu.config import Config
    from megatron_llm_tpu.resilience.integrity import CORRUPT_SUFFIX

    cfg = Config()
    cfg.finalize(n_devices=1)
    save_dir = args.save
    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    save_checkpoint(cfg, save_dir, 2, params, consumed_samples=8)
    save_checkpoint(cfg, save_dir, 4, params, consumed_samples=16)

    # flip one byte in a manifested file of the newest checkpoint
    newest = checkpoint_dir(save_dir, 4)
    victim = None
    for dirpath, _d, files in os.walk(newest):
        for name in files:
            p = os.path.join(dirpath, name)
            if name != "MANIFEST.json" and os.path.getsize(p) > 8:
                victim = p
                break
        if victim:
            break
    with open(victim, "r+b") as f:
        f.seek(4)
        b = f.read(1)
        f.seek(4)
        f.write(bytes([b[0] ^ 0xFF]))

    _p, _o, it, consumed, _meta = load_checkpoint(cfg, save_dir, params)
    quarantined = any(d.startswith("iter_0000004" + CORRUPT_SUFFIX)
                      for d in os.listdir(save_dir))
    ok = (it == 2 and consumed == 8 and quarantined
          and read_tracker(save_dir)[0] == 4)  # tracker untouched by load
    print(json.dumps({"corrupt_ok": ok, "fallback_iteration": it,
                      "quarantined": quarantined,
                      "backend": jax.devices()[0].platform}))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def build_corpus(workdir: str) -> str:
    import numpy as np

    from megatron_llm_tpu.data.indexed_dataset import make_builder

    prefix = os.path.join(workdir, "corpus_text_document")
    rng = np.random.RandomState(0)
    builder = make_builder(prefix + ".bin", vocab_size=500)
    for _ in range(120):
        builder.add_doc(rng.randint(1, 500, size=rng.randint(40, 120)))
    builder.finalize(prefix + ".idx")
    return prefix


def read_losses(path: str) -> dict:
    """iteration -> loss hex; later attempts overwrite earlier ones."""
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                out[rec["iteration"]] = rec["loss_hex"]
    return out


def child_cmd(corpus, save, losses=None, result=None, iters=CHILD_ITERS,
              save_interval=SAVE_INTERVAL, kill9_at=0, hang_at=0,
              watchdog=False, watchdog_min_deadline=2.0,
              watchdog_first_deadline=300.0):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--corpus", corpus, "--save", save,
           "--iters", str(iters), "--save_interval", str(save_interval)]
    if losses:
        cmd += ["--losses", losses]
    if result:
        cmd += ["--result", result]
    if kill9_at:
        cmd += ["--kill9_at", str(kill9_at)]
    if hang_at:
        cmd += ["--hang_at", str(hang_at)]
    if watchdog:
        cmd += ["--watchdog",
                "--watchdog_min_deadline", str(watchdog_min_deadline),
                "--watchdog_first_deadline", str(watchdog_first_deadline)]
    return cmd


def phase_chaos(workdir: str, corpus: str) -> dict:
    """Baseline vs. supervised-kill-resume; bitwise trajectory compare."""
    from megatron_llm_tpu.resilience.supervisor import (
        RestartPolicy,
        Supervisor,
    )

    base_losses = os.path.join(workdir, "baseline_losses.jsonl")
    r = subprocess.run(
        child_cmd(corpus, os.path.join(workdir, "ckpt_base"), base_losses),
        env=cpu_env(), capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        return {"ok": False, "error": f"baseline rc {r.returncode}: "
                                      f"{r.stderr[-500:]}"}
    sup_losses = os.path.join(workdir, "supervised_losses.jsonl")
    state_dir = os.path.join(workdir, "resil")
    sup = Supervisor(
        child_cmd(corpus, os.path.join(workdir, "ckpt_sup"), sup_losses,
                  kill9_at=KILL_AT),
        state_dir,
        policy=RestartPolicy(max_restarts=3, backoff_base=0.2,
                             backoff_max=1.0),
        env=cpu_env(), install_signal_handlers=False,
    )
    rc = sup.run()
    state = sup.load_state()
    base = read_losses(base_losses)
    got = read_losses(sup_losses)
    overlap = sorted(set(base) & set(got))
    bitwise = bool(overlap) and all(base[i] == got[i] for i in overlap)
    classes = [a["class"] for a in state["attempts"]]
    agg = state.get("aggregate_goodput", {})
    # the resumed attempt's first logged iteration proves where it picked
    # up: > 1 means it restarted from a checkpoint, not from scratch
    resumed_after = min(got) - 1 if got else None
    return {
        "ok": rc == 0 and bitwise and "signal" in classes
              and len(state["attempts"]) >= 2
              and resumed_after is not None and resumed_after >= 2,
        "rc": rc,
        "bitwise_identical": bitwise,
        "compared_iterations": overlap,
        "resumed_after_iteration": resumed_after,
        "attempt_classes": classes,
        "goodput_fraction": agg.get("goodput_fraction", 0.0),
    }


def phase_corrupt(workdir: str) -> dict:
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child_corrupt",
         "--save", os.path.join(workdir, "ckpt_corrupt")],
        env=cpu_env(), capture_output=True, text=True, timeout=300)
    try:
        rec = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        rec = {}
    return {"ok": r.returncode == 0 and rec.get("corrupt_ok", False), **rec}


def phase_hang(workdir: str, corpus: str) -> dict:
    t0 = time.time()
    r = subprocess.run(
        child_cmd(corpus, os.path.join(workdir, "ckpt_hang"),
                  hang_at=HANG_AT, watchdog=True),
        env=cpu_env(), capture_output=True, text=True, timeout=600)
    took = time.time() - t0
    return {
        "ok": r.returncode == 43 and "WATCHDOG" in r.stderr,
        "rc": r.returncode,
        "stack_dump": "dumping" in r.stderr,
        "seconds_to_trip": round(took, 1),
    }


def phase_tpu(workdir: str) -> dict:
    """Integrity + resume round-trip on hardware (no mid-step kills)."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child_corrupt",
         "--save", os.path.join(workdir, "ckpt_tpu")],
        env=inherit_env(), capture_output=True, text=True, timeout=900)
    try:
        rec = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        rec = {}
    return {"ok": (r.returncode == 0 and rec.get("corrupt_ok", False)
                   and rec.get("backend") not in (None, "cpu")), **rec}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--child_corrupt", action="store_true")
    ap.add_argument("--corpus")
    ap.add_argument("--save")
    ap.add_argument("--losses")
    ap.add_argument("--result")
    ap.add_argument("--iters", type=int, default=CHILD_ITERS)
    ap.add_argument("--save_interval", type=int, default=SAVE_INTERVAL)
    ap.add_argument("--kill9_at", type=int, default=0)
    ap.add_argument("--hang_at", type=int, default=0)
    ap.add_argument("--watchdog", action="store_true")
    ap.add_argument("--watchdog_min_deadline", type=float, default=2.0)
    ap.add_argument("--watchdog_first_deadline", type=float, default=300.0)
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    if args.child:
        return run_child(args)
    if args.child_corrupt:
        return run_child_corrupt(args)

    import tempfile

    import bench

    workdir = args.workdir or tempfile.mkdtemp(prefix="resilience_smoke_")
    corpus = build_corpus(workdir)
    chaos = phase_chaos(workdir, corpus)
    corrupt = phase_corrupt(workdir)
    hang = phase_hang(workdir, corpus)
    # decided from the environment alone: probing in this process would
    # take the chip the hardware child needs
    on_cpu = bench.cpu_requested()
    tpu = None if on_cpu else phase_tpu(workdir)
    backend = "cpu" if on_cpu else tpu.get("backend")

    all_ok = (chaos["ok"] and corrupt["ok"] and hang["ok"]
              and (tpu is None or tpu["ok"]))
    result = {
        "metric": "resilience_chaos_goodput_1chip",
        "value": round(chaos.get("goodput_fraction", 0.0) * 100, 1),
        "unit": "%goodput",
        "backend": backend if (tpu and tpu["ok"]) else "cpu",
        "chaos_backend": "cpu",
        "passed": all_ok,
        "chaos": chaos, "corrupt": corrupt, "hang": hang,
        **({"tpu_roundtrip": tpu} if tpu else {}),
    }
    if result["backend"] not in (None, "cpu"):
        bench.emit(result)
    else:
        bench.emit(bench.cpu_contract_line(result, tag="resilience"))
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
