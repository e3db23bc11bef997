"""Single-chip MFU push sweep (round-4: drive 40.0% -> >=45%).

The mbs 24/32 full-remat points and the policy sweep that PERF.md's
round-2 sweep never ran, plus the round-3 candidates: chunked head-fused
CE, the XLA latency-hiding scheduler, and a Pallas-vs-XLA RMSNorm
micro-comparison at the bench model's width (the kernel is
numerics-validated but NOT wired into the model path — this measurement
decides whether it should be).

Each candidate is one ``bench.py`` subprocess (inheriting its watchdog);
rows are written to ``MFU_SWEEP.json`` in candidate order, with the
winner named under the ``best`` key.  One process holds the chip at a
time: this parent stays off jax until every child has exited, and only
then runs the in-process RMSNorm micro.  A child that finds no
accelerator exits non-zero (bench.probe_backend) and ends the sweep; a
candidate-specific failure like an OOM does NOT.

Usage:  python tools/mfu_sweep.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import cpu_requested, probe_backend  # noqa: E402

OUT_PATH = os.path.join(REPO, "MFU_SWEEP.json")

# (name, bench.py args, extra env) — priority order: the interrupted
# round-2 points first, then the CE/scheduler candidates, then combos.
CANDIDATES = [
    ("mbs24_full", ["--mbs", "24"], {}),
    ("mbs32_full", ["--mbs", "32"], {}),
    ("mbs16_full_ce8", ["--ce_chunks", "8"], {}),
    # the roofline argument for >=45%: full remat caps useful/executed
    # FLOPs at 3/4 = 75%, so measured 40% implies ~53% hw efficiency;
    # selective remat raises the cap to ~95%, and chunked CE frees the
    # ~2 GiB fp32 logit buffer that made selective OOM at mbs 16 —
    # 0.53 x 0.95 ~= 50% MFU if it fits
    ("mbs16_sel_attn_ce8",
     ["--mbs", "16", "--recompute", "selective",
      "--policy", "save_dots_and_attn", "--ce_chunks", "8"], {}),
    ("mbs12_sel_attn_ce8",
     ["--mbs", "12", "--recompute", "selective",
      "--policy", "save_dots_and_attn", "--ce_chunks", "8"], {}),
    # save_attn_only: near-full-remat memory (only the flash outputs kept)
    # with the backward spared the whole kernel re-run — the policy the
    # round-2 outage cut from the sweep (PERF.md measurement record note);
    # should fit larger mbs than save_dots_and_attn
    ("mbs16_attnonly_ce8",
     ["--mbs", "16", "--recompute", "selective",
      "--policy", "save_attn_only", "--ce_chunks", "8"], {}),
    ("mbs24_attnonly_ce8",
     ["--mbs", "24", "--recompute", "selective",
      "--policy", "save_attn_only", "--ce_chunks", "8"], {}),
    ("mbs24_full_ce8", ["--mbs", "24", "--ce_chunks", "8"], {}),
    ("mbs16_full_lhs",
     [], {"XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}),
    ("mbs8_sel_attn",
     ["--mbs", "8", "--recompute", "selective",
      "--policy", "save_dots_and_attn"], {}),
    ("mbs16_full_ce4", ["--ce_chunks", "4"], {}),
    # flash block-size retune at the bench shape (VERDICT r3 item 2): the
    # auto choice is 1024x1024 at seq 1024; smaller Q blocks trade grid
    # iterations for VMEM pressure / pipelining overlap
    ("mbs16_full_bq512", [], {"MLT_FLASH_BLOCK_Q": "512"}),
    ("mbs16_full_bq512_bkv512",
     [], {"MLT_FLASH_BLOCK_Q": "512", "MLT_FLASH_BLOCK_KV": "512"}),
    ("mbs16_full_bq256", [], {"MLT_FLASH_BLOCK_Q": "256"}),
    # everything-on combo: if the single-knob rows each help, their sum is
    # the 45% candidate
    ("mbs24_full_ce8_lhs", ["--mbs", "24", "--ce_chunks", "8"],
     {"XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}),
    # at mbs 32 the fp32 logit buffer alone is ~4.2 GiB — chunked CE is
    # what makes the point fit, so sweep them together too
    ("mbs32_full_ce8", ["--mbs", "32", "--ce_chunks", "8"], {}),
]


def run_candidate(name: str, args: list, env_extra: dict) -> dict:
    env = dict(os.environ)
    for k, v in env_extra.items():
        if k == "XLA_FLAGS":
            # APPEND, never clobber (platform.py convention: later flag
            # wins within XLA_FLAGS) — a clobber would make this row
            # differ from the others by more than the candidate flag
            env[k] = (env.get(k, "") + " " + v).strip()
        else:
            env[k] = v
    t0 = time.time()
    # no subprocess timeout: bench.py exits via its own watchdog
    r = subprocess.run([sys.executable, "bench.py", *args], cwd=REPO,
                       capture_output=True, text=True, env=env)
    row = {"name": name, "args": args, "env": env_extra,
           "seconds": round(time.time() - t0, 1)}
    for line in reversed((r.stdout or "").strip().splitlines()):
        try:
            row.update(json.loads(line))
            break
        except ValueError:
            continue
    if r.returncode != 0:
        row["rc"] = r.returncode
        row["stderr_tail"] = (r.stderr or "")[-300:]
    return row


def rmsnorm_micro(shape=(16, 1024, 1024), iters=50) -> dict:
    """Pallas fused_rms_norm vs the XLA-fused rms_norm at the bench
    model's hot shape ([mbs, seq, h1024] bf16), fwd+bwd, one jitted scan
    per variant (same single-dispatch discipline as bench.py)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.ops.norms import rms_norm
    from megatron_llm_tpu.ops.pallas.rmsnorm import fused_rms_norm

    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    w = jnp.ones((shape[-1],), jnp.bfloat16)

    def timed(fn):
        def loss(x, w):
            return fn(x, w).astype(jnp.float32).sum()

        g = jax.grad(loss, argnums=(0, 1))

        def multi(x, w):
            def body(c, _):
                dx, dw = g(c, w)
                return c + dx.astype(c.dtype) * 0, dw.sum()

            return jax.lax.scan(body, x, jnp.arange(iters))[1]

        m = jax.jit(multi)
        out = m(x, w)
        jax.block_until_ready(out)  # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(m(x, w))
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    t_xla = timed(lambda x, w: rms_norm(x, w))
    try:
        t_pallas = timed(lambda x, w: fused_rms_norm(x, w))
    except Exception as e:
        return {"rmsnorm_xla_us": round(t_xla * 1e6, 1),
                "rmsnorm_pallas_error": f"{type(e).__name__}: {e}"[:200]}
    return {
        "shape": list(shape),
        "rmsnorm_xla_us": round(t_xla * 1e6, 1),
        "rmsnorm_pallas_us": round(t_pallas * 1e6, 1),
        "pallas_speedup": round(t_xla / t_pallas, 3),
        "verdict": ("wire pallas rmsnorm into the model path"
                    if t_pallas < 0.95 * t_xla else
                    "XLA fusion wins or ties - keep the XLA path"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="first three candidates + the rmsnorm micro only")
    ap.add_argument("--watchdog", type=float, default=10800.0,
                    help="clean-exit guard for the WHOLE sweep")
    args = ap.parse_args()

    import threading

    def on_timeout():
        print(json.dumps({"sweep_done": False,
                          "error": f"watchdog: exceeded {args.watchdog}s"}),
              flush=True)
        os._exit(3)

    dog = threading.Timer(args.watchdog, on_timeout)
    dog.daemon = True
    dog.start()

    if cpu_requested():
        print(json.dumps({"sweep_done": False,
                          "error": "JAX_PLATFORMS=cpu: off-TPU sweep numbers "
                                   "are meaningless (bench.py contract)"}),
              flush=True)
        sys.exit(1)
    summary = {"timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime()),
               "rows": []}

    cands = CANDIDATES[:3] if args.quick else CANDIDATES
    for name, cargs, cenv in cands:
        row = run_candidate(name, cargs, cenv)
        summary["rows"].append(row)
        print(json.dumps(row), flush=True)
        if "no accelerator" in str(row.get("error", "")):
            print(json.dumps({"sweep_done": False, "error": row["error"]}),
                  flush=True)
            sys.exit(1)

    # every child has exited: only now may this process take the chip
    summary["backend"] = probe_backend()
    try:
        summary["rmsnorm_micro"] = dict(rmsnorm_micro(), backend="tpu")
        print(json.dumps({"rmsnorm_micro": summary["rmsnorm_micro"]}),
              flush=True)
    except Exception as e:
        summary["rmsnorm_micro"] = {
            "error": f"{type(e).__name__}: {e}"[:200]}

    tpu_rows = [r for r in summary["rows"]
                if r.get("backend") not in (None, "cpu") and r.get("value")]
    if tpu_rows:
        best = max(tpu_rows, key=lambda r: r["value"])
        summary["best"] = {"name": best["name"], "value": best["value"],
                           "args": best["args"], "env": best["env"]}
    with open(OUT_PATH, "w") as f:
        json.dump(summary, f, indent=1)
        f.write("\n")
    print(json.dumps({"sweep_done": True,
                      "best": summary.get("best")}), flush=True)


if __name__ == "__main__":
    main()
