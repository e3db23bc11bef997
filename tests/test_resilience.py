"""Resilience subsystem (ISSUE 3): verified checkpoints + atomic commit,
corruption quarantine + fallback load, prune safety, hang watchdog,
supervised auto-restart with bitwise-identical resume, goodput accounting,
and prompt background-thread shutdown.  CPU-only, tier-1-fast."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_llm_tpu.config import Config
from megatron_llm_tpu.resilience import goodput as gp
from megatron_llm_tpu.resilience import integrity
from megatron_llm_tpu.resilience.supervisor import (
    RestartPolicy,
    Supervisor,
    classify_exit,
)
from megatron_llm_tpu.resilience.watchdog import EXIT_WATCHDOG, StepWatchdog


def _cfg(keep=None):
    cfg = Config()
    cfg.checkpoint.keep_last_n_checkpoints = keep
    cfg.finalize(n_devices=1)
    return cfg


def _params():
    import jax.numpy as jnp

    return {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.full((8,), 0.5, jnp.float32)}


def _save(cfg, d, it, consumed=None):
    from megatron_llm_tpu.checkpointing import save_checkpoint

    save_checkpoint(cfg, d, it, _params(),
                    consumed_samples=consumed if consumed is not None
                    else it * 4)


def _flip_byte(ckpt_dir, offset=4):
    """Corrupt one manifested file in place (size preserved -> sha catch)."""
    for dirpath, _d, files in os.walk(ckpt_dir):
        for name in files:
            p = os.path.join(dirpath, name)
            if name != integrity.MANIFEST_FILENAME and os.path.getsize(p) > 8:
                with open(p, "r+b") as f:
                    f.seek(offset)
                    b = f.read(1)
                    f.seek(offset)
                    f.write(bytes([b[0] ^ 0xFF]))
                return p
    raise AssertionError(f"no file to corrupt under {ckpt_dir}")


# ---------------------------------------------------------------------------
# integrity: manifest + verify + quarantine
# ---------------------------------------------------------------------------


def test_save_writes_verifying_manifest(tmp_path):
    from megatron_llm_tpu.checkpointing import checkpoint_dir

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 3)
    path = checkpoint_dir(d, 3)
    assert integrity.has_manifest(path)
    ok, problems = integrity.verify_checkpoint(path)
    assert ok, problems
    m = integrity.read_manifest(path)
    assert m["iteration"] == 3
    assert m["config_fingerprint"] == integrity.config_fingerprint(cfg)
    assert m["num_files"] == len(m["files"]) > 0
    # no tmp dir left behind
    assert not any(n.endswith(integrity.TMP_SUFFIX)
                   for n in os.listdir(d))


def test_verify_detects_bitflip_truncation_missing(tmp_path):
    from megatron_llm_tpu.checkpointing import checkpoint_dir

    d = str(tmp_path / "ckpt")
    _save(_cfg(), d, 1)
    path = checkpoint_dir(d, 1)

    victim = _flip_byte(path)
    ok, problems = integrity.verify_checkpoint(path)
    assert not ok and any("sha256 mismatch" in p for p in problems)

    with open(victim, "r+b") as f:  # truncate
        f.truncate(2)
    ok, problems = integrity.verify_checkpoint(path)
    assert not ok and any("size mismatch" in p for p in problems)

    os.remove(victim)
    ok, problems = integrity.verify_checkpoint(path)
    assert not ok and any("missing file" in p for p in problems)


def test_quarantine_and_listing(tmp_path):
    from megatron_llm_tpu.checkpointing import checkpoint_dir

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    for it in (1, 2):
        _save(cfg, d, it)
    bad = integrity.quarantine(checkpoint_dir(d, 1))
    assert bad.endswith(integrity.CORRUPT_SUFFIX)
    os.makedirs(checkpoint_dir(d, 5) + integrity.TMP_SUFFIX)
    # quarantined + tmp dirs never count as committed checkpoints
    assert integrity.list_checkpoint_iterations(d) == [2]
    # repeated quarantine of the same iteration gets a fresh name
    _save(cfg, d, 1)
    bad2 = integrity.quarantine(checkpoint_dir(d, 1))
    assert bad2 != bad and os.path.isdir(bad2)


def test_tracker_only_advances_past_verified_manifest(tmp_path, monkeypatch):
    """Commit-ordering satellite: a crash between the orbax write and the
    manifest leaves the tracker at the PREVIOUS checkpoint (no referenced
    torn checkpoint), and the half-written tmp dir is reclaimed by the
    next save."""
    import megatron_llm_tpu.checkpointing as ck

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 1)
    assert ck.read_tracker(d) == (1, False)

    def boom(*a, **k):
        raise OSError("simulated crash before manifest")

    monkeypatch.setattr(ck._integ, "write_manifest", boom)
    with pytest.raises(OSError, match="simulated crash"):
        _save(cfg, d, 2)
    monkeypatch.undo()
    assert ck.read_tracker(d) == (1, False)  # tracker never moved
    assert integrity.list_checkpoint_iterations(d) == [1]  # only .tmp for 2
    _save(cfg, d, 2)  # next save reclaims the stale tmp dir
    assert ck.read_tracker(d) == (2, False)
    assert integrity.verify_checkpoint(ck.checkpoint_dir(d, 2))[0]


def test_async_save_goes_through_manifest_commit(tmp_path):
    from megatron_llm_tpu.checkpointing import (
        AsyncCheckpointSaver,
        checkpoint_dir,
    )

    d = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver()
    saver.save(_cfg(), d, 7, _params(), consumed_samples=28)
    saver.wait()
    assert integrity.verify_checkpoint(checkpoint_dir(d, 7))[0]


# ---------------------------------------------------------------------------
# load: verified fallback walk + quarantine
# ---------------------------------------------------------------------------


def test_load_falls_back_to_previous_verified(tmp_path):
    from megatron_llm_tpu.checkpointing import (
        checkpoint_dir,
        load_checkpoint,
        read_tracker,
    )

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 2, consumed=8)
    _save(cfg, d, 4, consumed=16)
    _flip_byte(checkpoint_dir(d, 4))

    params, _opt, it, consumed, _meta = load_checkpoint(cfg, d, _params())
    assert (it, consumed) == (2, 8)
    np.testing.assert_array_equal(np.asarray(params["w"]),
                                  np.asarray(_params()["w"]))
    # the torn checkpoint is out of the resume path, bytes kept
    assert not os.path.isdir(checkpoint_dir(d, 4))
    assert os.path.isdir(checkpoint_dir(d, 4) + integrity.CORRUPT_SUFFIX)
    # load never rewrites the tracker; the next SAVE does
    assert read_tracker(d) == (4, False)


def test_load_survives_tracker_pointing_at_missing_dir(tmp_path):
    """The pre-resilience failure shape: tracker references bytes that
    never became durable.  Load must walk back instead of crashing."""
    import shutil

    from megatron_llm_tpu.checkpointing import (
        _write_tracker,
        checkpoint_dir,
        load_checkpoint,
    )

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 2, consumed=8)
    shutil.rmtree(checkpoint_dir(d, 4), ignore_errors=True)
    _write_tracker(d, 4)  # referenced checkpoint does not exist
    _p, _o, it, consumed, _m = load_checkpoint(cfg, d, _params())
    assert (it, consumed) == (2, 8)


def test_load_all_corrupt_raises(tmp_path):
    from megatron_llm_tpu.checkpointing import checkpoint_dir, load_checkpoint

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 2)
    _flip_byte(checkpoint_dir(d, 2))
    with pytest.raises(FileNotFoundError, match="failed manifest"):
        load_checkpoint(cfg, d, _params())
    assert os.path.isdir(checkpoint_dir(d, 2) + integrity.CORRUPT_SUFFIX)


def test_load_accepts_tracked_legacy_checkpoint(tmp_path):
    """Pre-manifest checkpoints (old repo state) still load when the
    tracker names them — the upgrade path must not strand existing runs."""
    from megatron_llm_tpu.checkpointing import checkpoint_dir, load_checkpoint

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 3, consumed=12)
    os.remove(integrity.manifest_path(checkpoint_dir(d, 3)))
    _p, _o, it, consumed, _m = load_checkpoint(cfg, d, _params())
    assert (it, consumed) == (3, 12)


def test_verify_on_load_off_restores_legacy_behavior(tmp_path):
    from megatron_llm_tpu.checkpointing import checkpoint_dir, load_checkpoint

    d = str(tmp_path / "ckpt")
    cfg = _cfg()
    _save(cfg, d, 2)
    _flip_byte(checkpoint_dir(d, 2))
    cfg.checkpoint.verify_on_load = False
    # no verification: the corrupt bytes load "successfully" (orbax may or
    # may not notice) or raise — but nothing is quarantined either way
    try:
        load_checkpoint(cfg, d, _params())
    except Exception:
        pass
    assert os.path.isdir(checkpoint_dir(d, 2))


# ---------------------------------------------------------------------------
# prune safety
# ---------------------------------------------------------------------------


def test_prune_skips_corrupt_and_protects_newest_verified(tmp_path):
    from megatron_llm_tpu.checkpointing import _prune_old, checkpoint_dir

    d = str(tmp_path / "ckpt")
    cfg = _cfg()  # no pruning during setup saves
    for it in (2, 4, 6, 8):
        _save(cfg, d, it)
    # a quarantined dir is present and must not crash the iteration parse
    # (the old split("_") did) nor be touched
    integrity.quarantine(checkpoint_dir(d, 8))
    # 4 and 6 rot on disk; 2 is the only good resume point left
    _flip_byte(checkpoint_dir(d, 4))
    _flip_byte(checkpoint_dir(d, 6))

    cfg.checkpoint.keep_last_n_checkpoints = 1
    _prune_old(cfg, d, latest=6)
    # keep=1 would normally leave only 6 — but 2 is the newest VERIFIED
    # checkpoint and must survive; 4 (corrupt, unquarantined) is fair game
    left = sorted(os.listdir(d))
    assert os.path.isdir(checkpoint_dir(d, 2)), left
    assert os.path.isdir(checkpoint_dir(d, 6)), left
    assert not os.path.isdir(checkpoint_dir(d, 4)), left
    assert any(n.startswith("iter_0000008" + integrity.CORRUPT_SUFFIX)
               for n in left)


def test_prune_normal_window(tmp_path):
    from megatron_llm_tpu.checkpointing import _prune_old, checkpoint_dir

    d = str(tmp_path / "ckpt")
    cfg = _cfg(keep=2)
    for it in (1, 2, 3):
        _save(cfg, d, it)  # save itself prunes: keep=2 -> {2, 3}
    assert integrity.list_checkpoint_iterations(d) == [2, 3]
    _prune_old(cfg, d, latest=3)  # idempotent
    assert integrity.list_checkpoint_iterations(d) == [2, 3]


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def _make_wd(**kw):
    import io

    stream = io.StringIO()
    exits = []
    calls = {"gauge": 0, "snapshot": 0}
    defaults = dict(
        multiplier=2.0, min_deadline=0.2, first_deadline=0.3,
        snapshot_timeout=1.0, stream=stream,
        exit_fn=lambda code: exits.append(code),
        gauge_fn=lambda: calls.__setitem__("gauge", calls["gauge"] + 1),
        snapshot_fn=lambda: calls.__setitem__(
            "snapshot", calls["snapshot"] + 1),
    )
    defaults.update(kw)
    wd = StepWatchdog(**defaults).start()
    return wd, stream, exits, calls


def test_watchdog_trips_with_dump_gauge_snapshot_and_code():
    wd, stream, exits, calls = _make_wd()
    wd.arm(first=True)  # 0.3s deadline
    deadline = time.time() + 10
    while not exits and time.time() < deadline:
        time.sleep(0.02)
    assert exits == [EXIT_WATCHDOG]
    assert wd.expired
    out = stream.getvalue()
    assert "WATCHDOG" in out and "thread stacks" in out
    assert "step-watchdog" in out or "MainThread" in out  # real stacks
    assert calls["gauge"] == 1 and calls["snapshot"] == 1


def test_watchdog_disarm_prevents_trip_and_feeds_ema():
    wd, _stream, exits, _calls = _make_wd(min_deadline=0.2)
    for _ in range(3):
        wd.arm()
        wd.disarm(step_time=0.01)
    time.sleep(0.6)
    assert exits == [] and not wd.expired
    # EMA fed with 10ms steps: steady deadline floors at min_deadline
    assert wd.current_deadline() == pytest.approx(0.2)
    wd._ema = 1.0
    assert wd.current_deadline() == pytest.approx(2.0)  # multiplier x EMA
    assert wd.current_deadline(first=True) == pytest.approx(0.3)
    wd.stop()
    assert not wd._thread.is_alive()


def test_watchdog_snapshot_timeout_still_exits():
    """An emergency snapshot that hangs (wedged device) must not block the
    exit — that would recreate the hang the watchdog exists to break."""
    wd, stream, exits, _calls = _make_wd(
        snapshot_fn=lambda: time.sleep(60), snapshot_timeout=0.2)
    t0 = time.time()
    wd.arm()  # no EMA -> first/min deadline
    deadline = time.time() + 10
    while not exits and time.time() < deadline:
        time.sleep(0.02)
    assert exits == [EXIT_WATCHDOG]
    assert time.time() - t0 < 5.0
    assert "did not finish" in stream.getvalue()


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------


def test_goodput_report_math():
    t0 = 1000.0
    g = gp.GoodputTracker(t0)
    g.run_started(resumed_iteration=10, prev_progress_iteration=14)
    assert g.replayed_steps == 4
    g.record_compile(5.0)
    g.record_productive(steps=20, seconds=40.0)  # 2s/step
    rep = g.report(now=t0 + 60.0)
    assert rep["lost_replay_seconds"] == pytest.approx(8.0)  # 4 x 2s
    assert rep["productive_seconds"] == pytest.approx(32.0)
    assert rep["productive_steps"] == 16
    assert rep["lost_compile_seconds"] == 5.0
    assert rep["other_seconds"] == pytest.approx(15.0)  # 60 - 40 - 5
    assert rep["goodput_fraction"] == pytest.approx(32.0 / 60.0, abs=1e-3)


def test_goodput_progress_roundtrip_and_aggregate(tmp_path):
    d = str(tmp_path)
    assert gp.read_progress(d) is None
    gp.write_progress(d, 42)
    assert gp.read_progress(d) == 42
    gp.write_progress(d, 43)
    assert gp.read_progress(d) == 43
    assert gp.read_progress(None) is None

    agg = gp.aggregate_reports([
        {"wall_seconds": 100.0, "productive_seconds": 80.0,
         "productive_steps": 40, "lost_compile_seconds": 10.0,
         "lost_replay_seconds": 4.0},
        {"wall_seconds": 50.0, "productive_seconds": 45.0,
         "productive_steps": 20, "lost_compile_seconds": 5.0,
         "lost_replay_seconds": 0.0},
        None,
    ], downtime_seconds=10.0)
    assert agg["wall_seconds"] == pytest.approx(160.0)
    assert agg["productive_seconds"] == pytest.approx(125.0)
    assert agg["productive_steps"] == 60
    assert agg["lost_restart_seconds"] == 10.0
    assert agg["goodput_fraction"] == pytest.approx(125.0 / 160.0, abs=1e-3)


def test_pretrain_result_carries_goodput(tmp_path):
    """The driver reports goodput on every run and persists it next to the
    checkpoints (save/resilience) for the supervisor."""
    from test_training_driver import small_cfg

    from megatron_llm_tpu.training import pretrain

    corpus = tmp_path / "corpus_text_document"
    rng = np.random.RandomState(0)
    from megatron_llm_tpu.data.indexed_dataset import make_builder

    builder = make_builder(str(corpus) + ".bin", vocab_size=500)
    for _ in range(50):
        builder.add_doc(rng.randint(1, 500, size=rng.randint(40, 120)))
    builder.finalize(str(corpus) + ".idx")

    cfg = small_cfg(str(corpus), tmp_path, train_iters=4)
    result = pretrain(cfg)
    rep = result["goodput"]
    assert rep["wall_seconds"] > 0
    assert rep["productive_steps"] == 3  # 4 steps minus the compile step
    assert 0.0 <= rep["goodput_fraction"] <= 1.0
    resil = os.path.join(cfg.checkpoint.save, "resilience")
    assert gp.read_report(resil)["productive_steps"] == 3
    assert gp.read_progress(resil) == 4  # log_interval=4 high-water mark


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


def test_classify_exit_taxonomy():
    assert classify_exit(0) == "clean"
    assert classify_exit(EXIT_WATCHDOG) == "hang"
    assert classify_exit(-9) == "signal"
    assert classify_exit(-15) == "signal"
    assert classify_exit(1) == "crash"
    assert classify_exit(77) == "crash"


def test_restart_policy_backoff():
    p = RestartPolicy(backoff_base=2.0, backoff_max=30.0)
    assert [p.next_delay(n) for n in (1, 2, 3, 4, 5)] == [
        2.0, 4.0, 8.0, 16.0, 30.0]  # capped


def test_supervisor_restarts_until_clean(tmp_path):
    """Two crashes, then success (a counter file drives the script); the
    state json records the attempt history and aggregate goodput."""
    counter = tmp_path / "n"
    script = (
        "import sys, pathlib; p = pathlib.Path(r'%s');"
        "n = int(p.read_text()) if p.exists() else 0;"
        "p.write_text(str(n + 1));"
        "sys.exit(0 if n >= 2 else 7)" % counter
    )
    sup = Supervisor([sys.executable, "-c", script], str(tmp_path / "resil"),
                     policy=RestartPolicy(max_restarts=5, backoff_base=0.05,
                                          backoff_max=0.1),
                     install_signal_handlers=False)
    assert sup.run() == 0
    state = sup.load_state()
    assert [a["class"] for a in state["attempts"]] == [
        "crash", "crash", "clean"]
    assert state["restarts_used"] == 2
    assert state["final"] == "clean exit"
    assert "aggregate_goodput" in state


def test_supervisor_budget_exhausted(tmp_path):
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(3)"],
                     str(tmp_path / "resil"),
                     policy=RestartPolicy(max_restarts=2, backoff_base=0.02,
                                          backoff_max=0.05),
                     install_signal_handlers=False)
    rc = sup.run()
    assert rc == 3
    state = sup.load_state()
    assert len(state["attempts"]) == 3  # initial + 2 restarts
    assert "budget exhausted" in state["final"]


def test_supervisor_sigterm_forwarding_no_restart(tmp_path):
    """Graceful preemption: SIGTERM forwards to the child (which exits
    cleanly here) and the supervisor does NOT restart."""
    # the child says so, in a line, once its handler is installed: the
    # supervisor hands its stdout on, so the line goes to a file
    ready = tmp_path / "ready"
    script = ("import signal, sys, time;"
              "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
              f"print('handler installed', file=open({str(ready)!r}, 'w'),"
              " flush=True);"
              "time.sleep(60)")
    sup = Supervisor([sys.executable, "-c", script], str(tmp_path / "resil"),
                     policy=RestartPolicy(max_restarts=5, backoff_base=0.05),
                     install_signal_handlers=False, term_grace=10.0)
    out = {}

    def run():
        out["rc"] = sup.run()

    t = threading.Thread(target=run)
    t.start()
    deadline = time.time() + 15
    while sup.child_pid is None and time.time() < deadline:
        time.sleep(0.05)
    assert sup.child_pid is not None
    deadline = time.time() + 30     # an interpreter's start on shared cores
    while time.time() < deadline and not (
            ready.exists() and ready.read_text().strip()):
        time.sleep(0.02)
    assert ready.read_text().strip() == "handler installed"
    sup.request_stop()
    t.join(timeout=15)
    assert not t.is_alive()
    assert out["rc"] == 0
    assert len(sup.load_state()["attempts"]) == 1  # no restart


# ---------------------------------------------------------------------------
# prompt shutdown of background data threads
# ---------------------------------------------------------------------------


def test_prefetcher_close_unblocks_source_pull():
    """A worker blocked inside next(source) — a loader stalled forever —
    must not wedge close(): close propagates to the source and the join
    stays bounded (the satellite fix; the watchdog abort path relies on
    teardown never hanging)."""
    from megatron_llm_tpu.data.prefetch import BatchPrefetcher
    from megatron_llm_tpu.data.samplers import DataIterator

    class SlowDataset:
        def __len__(self):
            return 10**6

        def __getitem__(self, i):
            if i >= 4:
                time.sleep(3600)  # dead filesystem
            return {"x": np.full((2,), i, np.int32)}

    class Seq:
        def __iter__(self):
            for i in range(10**6):
                yield [i]

    src = DataIterator(SlowDataset(), Seq(), prefetch=2)
    pf = BatchPrefetcher(src, depth=2)
    assert next(pf)[1]["x"].flat[0] == 0  # stream is live
    t0 = time.time()
    pf.close()
    assert time.time() - t0 < 10.0
    assert pf.closed
    assert not pf._thread.is_alive()  # worker unblocked via source close
    with pytest.raises(StopIteration):
        next(pf)


def test_dataiterator_close_idempotent_and_consumer_safe():
    from megatron_llm_tpu.data.samplers import DataIterator

    class DS:
        def __len__(self):
            return 100

        def __getitem__(self, i):
            return {"x": np.full((2,), i, np.int32)}

    class Seq:
        def __iter__(self):
            for i in range(100):
                yield [i]

    it = DataIterator(DS(), Seq(), prefetch=2)
    assert next(it)["x"].flat[0] == 0
    it.close()
    it.close()  # idempotent
    assert not it._thread.is_alive()
    with pytest.raises(StopIteration):  # consumer never blocks after close
        next(it)


def test_sampler_resume_exact_and_end_of_data():
    from megatron_llm_tpu.data.samplers import (
        MegatronPretrainingRandomSampler,
        MegatronPretrainingSampler,
    )

    full = list(MegatronPretrainingSampler(40, 0, 4))
    resumed = list(MegatronPretrainingSampler(40, 16, 4))
    assert resumed == full[4:]  # identical batch sequence after resume

    # cyclic sampler: resume mid-epoch and across the epoch boundary
    ref = MegatronPretrainingRandomSampler(20, 0, 4, seed=7)
    it = iter(ref)
    stream = [next(it) for _ in range(9)]  # crosses into epoch 2
    res = iter(MegatronPretrainingRandomSampler(20, 16, 4, seed=7))
    assert [next(res) for _ in range(5)] == stream[4:]

    # resume AT data end is a valid state, not an assert crash
    done = MegatronPretrainingSampler(40, 40, 4)
    assert len(done) == 0 and list(done) == []


# ---------------------------------------------------------------------------
# chaos round-trips (acceptance): subprocess children via the smoke tool
# ---------------------------------------------------------------------------


def _smoke():
    import tools.resilience_smoke as rs

    return rs


@pytest.fixture(scope="module")
def chaos_corpus(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("chaos"))
    return workdir, _smoke().build_corpus(workdir)


def test_chaos_kill9_resume_bitwise(chaos_corpus):
    """ISSUE 3 acceptance: a supervisor-managed run SIGKILLed mid-training
    auto-resumes from the newest verified checkpoint and reproduces the
    uninterrupted run's loss trajectory bitwise on every post-resume
    iteration."""
    workdir, corpus = chaos_corpus
    out = _smoke().phase_chaos(workdir, corpus)
    assert out["ok"], out
    assert out["bitwise_identical"]
    assert out["attempt_classes"][0] == "signal"  # the SIGKILL
    assert out["attempt_classes"][-1] == "clean"
    # the resumed attempt restarted from a committed checkpoint (not from
    # scratch) and re-ran the killed step and everything after it
    assert out["resumed_after_iteration"] >= 2
    assert len(out["compared_iterations"]) >= 3
    assert 0.0 < out["goodput_fraction"] <= 1.0
    # state file survives for post-mortem
    state_path = os.path.join(workdir, "resil", "resilience_state.json")
    with open(state_path) as f:
        state = json.load(f)
    assert state["final"] == "clean exit"


def test_chaos_hang_trips_watchdog(chaos_corpus):
    """A silently hung step exits with the distinct watchdog code and a
    stack dump, within the configured deadline."""
    workdir, corpus = chaos_corpus
    out = _smoke().phase_hang(workdir, corpus)
    assert out["ok"], out
    assert out["rc"] == EXIT_WATCHDOG
    assert out["stack_dump"]
