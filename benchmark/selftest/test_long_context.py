"""What a long-context serving cell needs of the harness, on the CPU at the
``--rehearsal`` widths: shared prefixes primed in set-up and hit in the
window (and a pool too small to keep them caught by the hit share), the two
length limits of a mix, the reference's attention in blocks of queries, and
the comparison at the emitted positions.  The rehearsals skip ``run.py``'s
look for a chip and drive ``lib/serving.run`` itself on a throw-away cell
added by files and entries only."""

import copy
import hashlib
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells, check, harness, serving, traffic
from benchmark.reference import common, falcon_block, joyai_block

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PRIMED_MIX = {
    "kind": "closed_loop", "clients": 6, "ramp_s": 1, "draw_seed": 11,
    "plan_requests": 200, "probe_lengths": [40, 56], "trace_seconds": 1,
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 8, "max": 40},
    "prompt_max": 120,
    "output_len": {"dist": "uniform", "min": 4, "max": 10},
    "shared_prefix": {"share": 1.0, "count": 3, "tokens": 64,
                      "prime": {"together": 2, "min_hit_share": 0.8,
                                "why": "every request carries a prefix"}},
    "sampling": {"top_k": 1},
}


def _root_with_cell(tmp_path, mix, flags=None):
    """A copy of the benchmark with the cell ``primed`` added: the JoyAI
    configuration (rehearsal flags overridden by ``flags``) under ``mix``."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    cfg["rehearsal"]["flags"].update(flags or {})
    with open(os.path.join(base, "configs", "joyai-long.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(base, "traffic", "primed.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "joyai-long", "source": cfg["source"],
                             "file": "benchmark/configs/joyai-long.json",
                             "reduced": cfg["reduced"], "why": "throw-away"})
    bench["workloads"].append({"name": "primed", "config": "joyai-long",
                               "traffic": "primed", "chips": 1, "why": "throw-away"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cells.Cell("primed", root=root)


def _rehearse(cell, seed=2 ** 31 + 77, seconds=3.0):
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 rehearsal=1, rate=None)
    return serving.run(cell, args, harness.Clock(harness.Clock.now()))


def test_primed_prefixes_are_hit_in_the_window(tmp_path, monkeypatch, capsys):
    # the probes (40 + 32 and 56 + 32 tokens) are longer than one block of
    # the reference's attention, so the comparison crosses blocks too
    monkeypatch.setattr(common, "QUERY_BLOCK", 32)
    run = _rehearse(_root_with_cell(tmp_path, PRIMED_MIX))
    c = run.checks
    assert c["primed_prefixes"] == 3 and c["primed_tokens"] == 192
    assert c["prime_s"] > 0 and "primed 3 shared prefixes" in capsys.readouterr().out
    assert c["prefix_tokens_carried"] > 0 and c["prefixes_hit"]
    assert c["prefix_hit_share"] >= 0.8
    assert c["reference_ok"] and c["reference_tokens"] == 4 * check.PROBE_TOKENS
    assert run.correct and run.attempted > 0 and run.failed == 0
    # the whole prompt is cut at prompt_max, the body alone at prompt_len.max
    assert max(s["n_prompt"] for s in run.all_samples) > 64 + 8


def test_the_line_ends_with_each_number_beside_its_limit():
    cell = cells.Cell("joyai_flash_batch_decode")
    checks = {"compiles_in_window": 0, "reference_mean_abs_diff": 0.03,
              "reference_max_abs_diff": 0.4, "engine_failures": 0,
              "prefix_hit_share": 1.01, "min_hit_share": 0.9, "reference_ok": True}
    tol = cell.config["tolerance"]
    assert harness.compared(cell, checks) == {
        "compiles_in_window": {"value": 0, "limit": 0},
        "reference_mean": {"value": 0.03, "limit": tol["mean_abs_nats"]},
        "reference_max": {"value": 0.4, "limit": tol["max_abs_nats"]},
        "engine_failures": {"value": 0, "limit": 0},
        "prefix_hit_share": {"value": 1.01, "limit": 0.9}}
    args = types.SimpleNamespace(seed=1, seconds=1.0, trace=0, rehearsal=0, rate=None)
    run = harness.Run(cell, args, harness.Clock(0.0))
    run.checks, run.memory_peak = checks, lambda: 0
    line = harness.result_line(cell, args, run)
    assert list(line)[-1] == "compared" and "prefix_hit_share" in line["compared"]


def test_a_pool_too_small_for_its_prefixes_is_not_correct(tmp_path, capsys):
    mix = copy.deepcopy(PRIMED_MIX)
    mix["shared_prefix"].update(count=12)      # 48 pages of prefixes ...
    run = _rehearse(_root_with_cell(tmp_path, mix, {"kv_pool_pages": 33}))
    c = run.checks                             # ... and 32 pages to serve from
    assert c["primed_prefixes"] == 12
    assert c["prefix_hit_share"] < 0.8 and not c["prefixes_hit"]
    assert c["reference_ok"] and c["compiles_in_window"] == 0
    assert not run.correct                     # by the hit share alone
    assert c["prefixes_gone_after_window"]
    out = capsys.readouterr().out
    assert "the cache no longer held prefixes [" in out


def test_a_mix_without_priming_runs_as_before(tmp_path):
    mix = copy.deepcopy(PRIMED_MIX)
    del mix["shared_prefix"]["prime"]
    run = _rehearse(_root_with_cell(tmp_path, mix), seconds=2.0)
    assert not {"primed_prefixes", "prefix_hit_share"} & set(run.checks)
    assert run.correct


# -- lengths ---------------------------------------------------------------

PARENT_PLANS = {   # sha256 of request_plan(batch_closed, seed, 40 s, vocab) at 23a016b
    (2147485053, 65024): "d6cb3053fe869f2136949496bc76e1b1f100fd135c31280efb28bed0ce211815",
    (7, 129280): "19f60e448c0152a448be58e4349de82905368e8a8d4cc8098f4a96851feb7a08",
    (2147495993, 129280): "35b538ad048b02aa4cb9edfa0d2710a5949f96ea2e86d66cf276980b57c217b4",
}


@pytest.mark.parametrize("seed,vocab", sorted(PARENT_PLANS))
def test_batch_closed_plan_is_the_parents(seed, vocab):
    plan = traffic.request_plan(traffic.load("batch_closed"), seed, 40.0, vocab)
    digest = hashlib.sha256(json.dumps(plan, sort_keys=True).encode()).hexdigest()
    assert digest == PARENT_PLANS[(seed, vocab)]


def test_prompt_max_cuts_the_whole_prompt_and_prompt_len_the_body():
    mix = copy.deepcopy(PRIMED_MIX)
    plan = traffic.request_plan(mix, 5, 3.0, 512)
    lens = [len(r["prompt"]) for r in plan["requests"]]
    assert all(64 + 8 <= n <= 64 + 40 for n in lens)
    for r in plan["requests"]:
        assert r["prompt"][:64] == plan["prefixes"][r["prefix"]]
    mix["prompt_max"] = 80
    assert max(len(r["prompt"]) for r in
               traffic.request_plan(mix, 5, 3.0, 512)["requests"]) == 80
    del mix["prompt_max"]              # as before the key: one limit for both
    assert max(len(r["prompt"]) for r in
               traffic.request_plan(mix, 5, 3.0, 512)["requests"]) == 40


# -- the reference in blocks, and at the emitted positions -------------------

def _plain_attention(q, k, v, window):
    """The form ``common.causal_attention`` had before it learnt blocks."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    qg = q.reshape(b, s, nkv, n // nkv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(jnp.float32(d))
    qpos, kpos = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ok = qpos >= kpos
    if window:
        ok &= (qpos - kpos) < window
    p = jax.nn.softmax(jnp.where(ok[None, None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, s, n * d)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("s", [64, 75])        # whole blocks, and a padded last one
def test_blocked_attention_equals_the_plain_form(monkeypatch, window, s):
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(keys[0], (2, s, 4, 8), jnp.float32)
    k, v = (jax.random.normal(kk, (2, s, 2, 8), jnp.float32) for kk in keys[1:])
    plain = _plain_attention(q, k, v, window)
    np.testing.assert_allclose(common.causal_attention(q, k, v, window), plain,
                               rtol=0, atol=0)          # one block: the same code
    monkeypatch.setattr(common, "QUERY_BLOCK", 16)
    assert common.query_block(s, 4) == 16
    np.testing.assert_allclose(common.causal_attention(q, k, v, window), plain,
                               rtol=1e-6, atol=1e-6)


def test_query_block_keeps_the_scores_under_their_limit():
    assert common.query_block(512, 128) == 512            # the plain form
    assert common.query_block(4640, 32) == 256            # 0.15 GB of scores
    assert common.query_block(32768, 128) == 16
    for s, heads in ((4640, 32), (16384, 28), (32768, 128), (131072, 128)):
        block = common.query_block(s, heads)
        assert block == 8 or 4 * heads * block * s <= common.SCORE_BYTES


def _tiny(workload):
    """A serving cell at its rehearsal widths with float32 weights."""
    from megatron_llm_tpu.config.arguments import parse_args

    cell = cells.Cell(workload)
    cell.model.update(cell.config["rehearsal"]["model"])
    cfg = parse_args(cell.flags({**cell.config["rehearsal"]["flags"], "seed": 3}))
    params = serving.init_weights(cfg, jax.random.PRNGKey(3), "float32")
    return cell, params, cfg.model.vocab_size


@pytest.mark.parametrize("workload,module", [
    ("falcon7b_batch_decode", falcon_block),
    ("joyai_flash_batch_decode", joyai_block)])
def test_emitted_positions_equal_the_gather_from_whole_logits(
        workload, module, monkeypatch):
    cell, params, vocab = _tiny(workload)
    probes = check.serve_probes(9, vocab, (40, 56), 16)
    rng = np.random.default_rng(0)
    for p in probes:
        p["tokens"] = rng.integers(1, vocab, check.PROBE_TOKENS).tolist()
        seq = jnp.asarray([p["prompt"] + p["tokens"]], jnp.int32)
        whole = common.token_log_probs(module.logits(params, seq, cell.model), seq)
        start = len(p["prompt"]) - 1
        p["logprobs"] = np.asarray(whole)[0, start:start + len(p["tokens"])].tolist()
    for block in (512, 32):              # the plain form, then across blocks
        monkeypatch.setattr(common, "QUERY_BLOCK", block)
        out = check.serve_against_reference(cell, params, probes)
        assert out["reference_tokens"] == 4 * check.PROBE_TOKENS
        assert out["reference_max_abs_diff"] < 2e-5, out


def test_control_reads_the_three_comparisons():
    """``control.py`` at the rehearsal widths: the bfloat16 reference reads
    further from the float32 one than the program does, and a reference that
    forgets keys a page behind its query fails the cell's limits."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "control.py"),
         "--workload", "joyai_flash_batch_decode", "--seed", "2147485999",
         "--rehearsal", "1", "--drop-keys-older-than", "16"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    program, control, fault = line["program"], line["control"], line["dropped_keys"]
    assert program["reference_ok"] and program["reference_tokens"] == 128
    assert (control["reference_mean_abs_diff"]
            > 3 * program["reference_mean_abs_diff"])
    assert not fault["reference_ok"]
    assert fault["reference_mean_abs_diff"] > 10 * program["reference_mean_abs_diff"]


# -- a late step no longer decides a train cell's `correct` ------------------

def _train_run(gaps, losses, compiles=0, ref_ok=True):
    from benchmark.lib import kind_train

    cell = cells.Cell("mistral7b_train_4k")
    args = types.SimpleNamespace(seed=1, seconds=40.0, trace=0, rehearsal=0, rate=None)
    run = harness.Run(cell, args, harness.Clock(0.0))
    run.compiles_in_window = compiles
    kind_train.judge(run, gaps, losses, {"reference_ok": ref_ok})
    return run


def test_one_late_step_is_recorded_and_decides_nothing():
    gaps = [0.173] * 100 + [1.73] + [0.173] * 100
    run = _train_run(gaps, [10.7, 10.6])
    assert run.correct
    c = run.checks
    assert c["window_steady"] is False and c["longest_gap_after_step"] == 100
    assert c["longest_gap_ms"] == pytest.approx(1730.0)
    assert c["median_gap_ms"] == pytest.approx(173.0)
    assert _train_run([0.173] * 50, [10.7]).checks["window_steady"] is True


@pytest.mark.parametrize("fault", [
    dict(compiles=1), dict(losses=[10.7, float("nan")]), dict(ref_ok=False),
    dict(gaps=[])])
def test_what_still_decides_a_train_cells_correct(fault):
    kw = {"gaps": [0.173] * 50, "losses": [10.7, 10.6], **fault}
    assert not _train_run(**kw).correct
