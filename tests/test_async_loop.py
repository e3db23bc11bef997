"""Async training loop (ISSUE 2): bitwise loss-trajectory parity of the
overlapped loop vs the blocking loop, prefetch-stage determinism and
shutdown, async-vs-sync checkpoint equivalence + exit barrier, and the
bench_train_loop.py evidence contract (mirroring test_bench_contract.py)."""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_llm_tpu.config import Config, apply_architecture
from megatron_llm_tpu.data.indexed_dataset import make_builder
from megatron_llm_tpu.data.prefetch import BatchPrefetcher, concat_chunks


@pytest.fixture
def toy_corpus(tmp_path):
    prefix = str(tmp_path / "corpus_text_document")
    rng = np.random.RandomState(0)
    builder = make_builder(prefix + ".bin", vocab_size=500)
    for _ in range(80):
        builder.add_doc(rng.randint(1, 500, size=rng.randint(40, 120)))
    builder.finalize(prefix + ".idx")
    return prefix


def small_cfg(toy_corpus, tmp_path, train_iters=6, *, dispatch_depth=2,
              prefetch_depth=2, rampup=None, save=None):
    cfg = Config()
    apply_architecture(cfg, "llama2")
    cfg.model.num_layers = 2
    cfg.model.hidden_size = 64
    cfg.model.num_attention_heads = 4
    cfg.model.num_attention_heads_kv = 2
    cfg.model.vocab_size = 512
    cfg.model.max_position_embeddings = 64
    cfg.data.seq_length = 32
    cfg.data.data_path = [toy_corpus]
    cfg.data.tokenizer_type = "NullTokenizer"
    cfg.training.params_dtype = "float32"
    cfg.training.use_flash_attn = False
    cfg.training.micro_batch_size = 2
    cfg.training.global_batch_size = 4
    cfg.training.train_iters = train_iters
    cfg.training.eval_iters = 2
    cfg.training.eval_interval = 0
    cfg.training.rampup_batch_size = rampup
    cfg.training.async_dispatch_depth = dispatch_depth
    cfg.training.prefetch_depth = prefetch_depth
    cfg.optimizer.lr = 1e-3
    cfg.checkpoint.save = save
    cfg.logging.log_interval = 2
    cfg.finalize(n_devices=1)
    return cfg


# ---------------------------------------------------------------------------
# (a) bitwise trajectory parity
# ---------------------------------------------------------------------------


def _series(result):
    return [(it, loss) for it, loss in result["loss_series"]]


def test_overlapped_trajectory_bitwise_identical(toy_corpus, tmp_path, capsys):
    """Deferred metrics + prefetch + async dispatch change WHEN the host
    observes results, never what the device computes: the fetched
    (iteration, lm loss) series must match the blocking loop bit for bit."""
    from megatron_llm_tpu.training import pretrain

    sync = pretrain(small_cfg(toy_corpus, tmp_path, 6,
                              dispatch_depth=0, prefetch_depth=0))
    async_ = pretrain(small_cfg(toy_corpus, tmp_path, 6,
                                dispatch_depth=2, prefetch_depth=2))
    assert len(_series(sync)) == 6
    assert _series(sync) == _series(async_)  # exact float equality
    assert float(sync["last_metrics"]["lm loss"]) == float(
        async_["last_metrics"]["lm loss"])

    out = capsys.readouterr().out
    # satellite: compile step fenced out of throughput reporting
    assert "first step (compile + warmup)" in out


def test_overlapped_trajectory_bitwise_identical_rampup(toy_corpus, tmp_path):
    """Same parity under a batch-size ramp: the prefetch worker replicates
    the chunked pulls + concatenation + post-ramp loader switch exactly."""
    from megatron_llm_tpu.training import pretrain

    # gbs ramps 2 -> 4 over 8 samples: iters at gbs 2, then the switch
    ramp = (2, 2, 8)
    sync = pretrain(small_cfg(toy_corpus, tmp_path, 5, dispatch_depth=0,
                              prefetch_depth=0, rampup=ramp))
    async_ = pretrain(small_cfg(toy_corpus, tmp_path, 5, dispatch_depth=2,
                                prefetch_depth=2, rampup=ramp))
    assert sync["consumed_samples"] == async_["consumed_samples"]
    assert len(_series(sync)) == 5
    assert _series(sync) == _series(async_)


# ---------------------------------------------------------------------------
# (b) prefetch stage: determinism, shutdown, errors
# ---------------------------------------------------------------------------


def _dict_stream(n, key="x"):
    for i in range(n):
        yield {key: np.full((2,), i, np.int32)}


def test_prefetch_deterministic_order_and_exhaustion():
    pf = BatchPrefetcher(_dict_stream(20), depth=3)
    got = [int(batch["x"][0]) for _, batch in pf]
    assert got == list(range(20))
    # exhaustion is terminal and repeatable
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):
        next(pf)
    assert pf.batches_out == 20


def test_prefetch_rampup_chunks_and_full_switch():
    """Chunked pulls follow the shadow gbs schedule; reaching full_gbs
    switches to the full-batch loader exactly once."""
    chunks = _dict_stream(4)  # 4 chunks of 2 rows while gbs == 4
    switched_with = []

    def switch(consumed):
        switched_with.append(consumed)
        return iter([{"x": np.full((4,), 100 + i, np.int32)}
                     for i in range(3)])

    pf = BatchPrefetcher(
        chunks, depth=2, chunk_size=2,
        gbs_fn=lambda consumed: 2 if consumed < 4 else 4,
        full_gbs=4, switch_source=switch,
    )
    items = list(pf)
    # two chunked steps at gbs 2 (one 2-row chunk each)...
    assert [g for g, _ in items[:2]] == [2, 2]
    assert [int(b["x"][0]) for _, b in items[:2]] == [0, 1]
    # ...then the switch (at consumed == 4) and full pass-through batches
    assert switched_with == [4]
    assert pf.switched_full
    assert [int(b["x"][0]) for _, b in items[2:]] == [100, 101, 102]
    assert all(b["x"].shape == (4,) for _, b in items[2:])


def test_prefetch_chunk_concat_token_idx():
    """Concatenation matches the driver loop: token_idx stays [s]."""
    src = iter([
        {"x": np.ones((2, 3), np.int32), "token_idx": np.arange(3)},
        {"x": 2 * np.ones((2, 3), np.int32), "token_idx": np.arange(3)},
    ])
    pf = BatchPrefetcher(src, depth=2, chunk_size=2,
                         gbs_fn=lambda consumed: 4)
    gbs, batch = next(pf)
    assert gbs == 4
    assert batch["x"].shape == (4, 3)
    assert batch["token_idx"].shape == (3,)  # batch-invariant, never stacked
    direct = concat_chunks([
        {"x": np.ones((2, 3), np.int32), "token_idx": np.arange(3)},
        {"x": 2 * np.ones((2, 3), np.int32), "token_idx": np.arange(3)},
    ])
    np.testing.assert_array_equal(batch["x"], direct["x"])


def test_prefetch_worker_exception_reraised_at_consumer():
    def bad_stream():
        yield {"x": np.zeros(1)}
        yield {"x": np.zeros(1)}
        raise ValueError("corrupt shard")

    pf = BatchPrefetcher(bad_stream(), depth=2)
    next(pf)
    next(pf)
    with pytest.raises(ValueError, match="corrupt shard"):
        next(pf)
    with pytest.raises(StopIteration):  # terminal after the error
        next(pf)


def test_prefetch_close_unblocks_full_queue():
    pf = BatchPrefetcher(_dict_stream(1000), depth=1)
    deadline = time.time() + 5.0
    while pf.qsize() < 1 and time.time() < deadline:
        time.sleep(0.01)  # worker now blocked on the full queue
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetch_place_fn_applied():
    pf = BatchPrefetcher(_dict_stream(3), depth=2,
                         place_fn=lambda b: {k: v + 100 for k, v in b.items()})
    vals = [int(b["x"][0]) for _, b in pf]
    assert vals == [100, 101, 102]


# ---------------------------------------------------------------------------
# (c) async checkpointing
# ---------------------------------------------------------------------------


def _ckpt_cfg():
    cfg = Config()
    cfg.finalize(n_devices=1)
    return cfg


def test_async_checkpoint_identical_to_sync(tmp_path):
    """The async path writes the same logical checkpoint as the sync path:
    same entries (params / opt_state / meta / tracker), bitwise-identical
    restored arrays, same bookkeeping.  (Byte-level file names can't be
    compared: orbax's OCDBT store content-hashes its chunk files.)"""
    import jax.numpy as jnp

    from megatron_llm_tpu.checkpointing import (
        AsyncCheckpointSaver,
        load_checkpoint,
        read_tracker,
        save_checkpoint,
    )

    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
              "b": jnp.full((4,), 0.25, jnp.float32)}
    opt = {"m": jnp.ones((3, 4), jnp.float32) * 0.125}
    cfg = _ckpt_cfg()

    d_sync, d_async = str(tmp_path / "sync"), str(tmp_path / "async")
    save_checkpoint(cfg, d_sync, 7, params, opt, consumed_samples=28)
    saver = AsyncCheckpointSaver()
    saver.save(cfg, d_async, 7, params, opt, consumed_samples=28)
    saver.wait()
    assert not saver.pending

    metas = []
    for d in (d_sync, d_async):
        assert read_tracker(d) == (7, False)
        entries = set(os.listdir(os.path.join(d, "iter_0000007")))
        assert {"params", "opt_state", "meta.json"} <= entries
        p, o, it, consumed, meta = load_checkpoint(cfg, d, params, opt)
        assert it == 7 and consumed == 28
        for k in params:
            np.testing.assert_array_equal(np.asarray(p[k]),
                                          np.asarray(params[k]))
        np.testing.assert_array_equal(np.asarray(o["m"]), np.asarray(opt["m"]))
        metas.append(meta)
    assert metas[0] == metas[1]  # identical meta.json incl. saved config


def test_async_saver_single_inflight_barrier(tmp_path, monkeypatch):
    """A second save first JOINS the previous write — saves never overlap
    and never reorder."""
    import jax.numpy as jnp

    import megatron_llm_tpu.checkpointing as ck

    order = []
    real_save = ck.save_checkpoint

    def slow_save(cfg, d, it, *a, **k):
        order.append(("start", it))
        time.sleep(0.2)
        real_save(cfg, d, it, *a, **k)
        order.append(("end", it))

    monkeypatch.setattr(ck, "save_checkpoint", slow_save)
    saver = ck.AsyncCheckpointSaver()
    params = {"w": jnp.ones((2,))}
    saver.save(_ckpt_cfg(), str(tmp_path / "c"), 1, params)
    waited = saver.save(_ckpt_cfg(), str(tmp_path / "c"), 2, params)
    saver.wait()
    assert waited > 0.0  # the barrier actually waited for save #1
    assert order == [("start", 1), ("end", 1), ("start", 2), ("end", 2)]


def test_async_saver_error_surfaces_on_wait(tmp_path, monkeypatch):
    import jax.numpy as jnp

    import megatron_llm_tpu.checkpointing as ck

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(ck, "save_checkpoint", boom)
    saver = ck.AsyncCheckpointSaver()
    saver.save(_ckpt_cfg(), str(tmp_path / "c"), 1, {"w": jnp.ones(2)})
    with pytest.raises(OSError, match="disk full"):
        saver.wait()


def test_async_save_exit_midrun_lands_consistent_checkpoint(
        toy_corpus, tmp_path):
    """Acceptance: an exit mid-run (exit_interval — the same path a signal
    takes) with --async_save still lands a complete, loadable checkpoint:
    the exit barrier flushes the pending write before pretrain returns."""
    from megatron_llm_tpu.checkpointing import read_tracker
    from megatron_llm_tpu.training import pretrain

    cfg = small_cfg(toy_corpus, tmp_path, 8, save=str(tmp_path / "ckpt"))
    cfg.checkpoint.async_save = True
    cfg.checkpoint.save_interval = 2
    cfg.training.exit_interval = 3
    result = pretrain(cfg)
    assert result["exit_reason"] == "exit_interval"
    assert result["iteration"] == 3

    it, release = read_tracker(cfg.checkpoint.save)
    assert it == 3 and not release
    ckpt = os.path.join(cfg.checkpoint.save, "iter_0000003")
    assert os.path.isdir(os.path.join(ckpt, "params"))
    with open(os.path.join(ckpt, "meta.json")) as f:
        meta = json.load(f)
    assert meta["iteration"] == 3
    assert meta["consumed_samples"] == result["consumed_samples"]

    # and the checkpoint resumes cleanly
    cfg2 = small_cfg(toy_corpus, tmp_path, 5)
    cfg2.checkpoint.load = cfg.checkpoint.save
    result2 = pretrain(cfg2)
    assert result2["iteration"] == 5


# ---------------------------------------------------------------------------
# deferred metrics helpers: evaluate batching + timer gauges
# ---------------------------------------------------------------------------


def test_evaluate_batches_metric_fetch(toy_corpus, tmp_path, monkeypatch):
    """evaluate drains metric dicts through batched device_get calls — not
    one blocking float() per metric per iteration."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu import training as tr

    calls = []
    real_get = jax.device_get

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    monkeypatch.setattr(tr.jax, "device_get", counting_get)
    cfg = small_cfg(toy_corpus, tmp_path, 4)
    batches = iter([{"i": i} for i in range(5)])
    out = tr.evaluate(
        cfg, None, lambda params, b: {"lm loss": jnp.float32(b["i"])},
        batches, max_iters=5)
    assert out["lm loss"] == pytest.approx((0 + 1 + 2 + 3 + 4) / 5)
    assert len(calls) == 1  # 5 iterations, ONE batched fetch


def test_timer_gauges_log_and_reset():
    from megatron_llm_tpu.utils.timers import Timers

    timers = Timers(log_level=1)
    timers.gauge("in-flight-depth", 1)
    timers.gauge("in-flight-depth", 3)
    timers.gauge("data-wait-ms", 5.0)
    log = timers.log()
    assert "in-flight-depth: 2.00 (max 3.00)" in log
    assert "data-wait-ms: 5.00" in log
    assert timers.log() == ""  # reset started a new interval

    quiet = Timers(log_level=0)  # gauges default to log level 1: gated
    quiet.gauge("in-flight-depth", 9)
    assert quiet.log() == ""


def test_step_times_bounded(toy_corpus, tmp_path):
    """The unbounded step_times list is gone: the result's loss series (and
    every other per-step record) is a bounded window."""
    from megatron_llm_tpu import training as tr

    assert tr._LOSS_SERIES_MAXLEN < 10_000
    result = tr.pretrain(small_cfg(toy_corpus, tmp_path, 4))
    assert len(result["loss_series"]) == 4
    assert result["warmup_time"] > 0
    assert result["steady_steps_per_sec"] > 0


# ---------------------------------------------------------------------------
# (d) bench_train_loop.py evidence contract (mirrors test_bench_contract.py)
# ---------------------------------------------------------------------------


import bench  # noqa: E402


def test_train_loop_bench_cpu_contract():
    """Off-TPU: headline 0, the overlap measurement rides under
    cpu_sanity."""
    line = bench.cpu_contract_line({
        "metric": "train_loop_overlap_steps_s_1chip",
        "value": 6.9, "unit": "steps/s", "backend": "cpu",
        "speedup_vs_blocking": 2.14, "blocking_steps_per_sec": 3.2,
    }, tag="train_loop")
    assert line["value"] == 0.0 and line["unit"] == "steps/s"
    assert line["cpu_sanity"]["speedup_vs_blocking"] == 2.14


@pytest.mark.slow
def test_train_loop_overlap_gate(toy_corpus, tmp_path):
    """ISSUE 2 acceptance gate: overlapped >= 1.5x blocking steps/sec with
    simulated host-side data latency (run through bench_train_loop's
    measurement path on a tiny shape)."""
    from bench_train_loop import make_provider, run_mode

    from megatron_llm_tpu.models import make_config

    # conftest pins an 8-device virtual CPU mesh: gbs must split over dp=8
    vocab, seq, mbs, gbs = 256, 64, 1, 8

    def make_cfg(iters):
        return make_config(
            "llama2", num_layers=2, hidden_size=128, num_attention_heads=4,
            num_attention_heads_kv=4, ffn_hidden_size=256, vocab_size=vocab,
            seq_length=seq, max_position_embeddings=seq,
            params_dtype="float32", use_flash_attn=False,
            micro_batch_size=mbs, global_batch_size=gbs, train_iters=iters,
            log_interval=10 ** 6, eval_interval=0, tokenizer_type=None,
        )

    calib = run_mode(make_cfg, 0.0, vocab, seq, 0, 0, 6)
    step_s = 1.0 / max(calib["steps_per_sec"], 1e-9)
    latency = min(max(step_s, 0.02), 0.5)
    blocking = run_mode(make_cfg, latency, vocab, seq, 0, 0, 12)
    overlapped = run_mode(make_cfg, latency, vocab, seq, 2, 2, 12)
    speedup = overlapped["steps_per_sec"] / blocking["steps_per_sec"]
    assert speedup >= 1.5, (
        f"overlap gate: {speedup:.2f}x < 1.5x "
        f"(blocking {blocking['steps_per_sec']:.2f}/s, "
        f"overlapped {overlapped['steps_per_sec']:.2f}/s)")
