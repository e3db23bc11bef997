"""The program's spans laid over device idle time and tick executions
(lib/spans.py), on a hand-made capture whose figures are worked out by hand.

``spans_capture.textproto`` (microseconds from the lines' timestamp):

device 0 runs the tick program at 0-60 (launched before the capture opened),
100-200, 260-500 (the prefill program, another fingerprint), 540-640 and
700-800 (its fetch ends after the capture closes); idle 60-100, 200-260,
500-540, 640-700 = 200 us.  The scheduler thread (line 0) holds three whole
steps, each ``engine-step`` > admit, plan, ``engine-ragged-tick`` > (launch,
fetch), apply, then an ``engine-wait`` and the opening of a fourth step whose
``engine-step`` never closed; a Python-tracer event on the same line is not a
span.  Two handler threads hold ``serve-write`` spans inside
``serve-api-stream``.
"""

import os
import types
import warnings

import pytest

from benchmark.lib import cells, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
US = 1e-6


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def capture():
    with open(os.path.join(HERE, "spans_capture.textproto")) as f:
        profile = _profile(f.read())
    return trace.reduce_profile(profile, {}), spans.from_profile(profile)


def _reader(name):
    return cells.Cell.reader_at(os.path.join(LAYER_METRICS, name + ".py"))


def _run(reduced=None, program_spans=None, counters=None):
    """What a reader sees of a run: the trace, the spans (as lib/spans.py
    caches them) and the counters."""
    run = types.SimpleNamespace(trace=reduced, counters=counters or {})
    if program_spans is not None:
        run._program_spans = program_spans
    if reduced is not None:
        reduced.path = "unused: the spans are cached"
    return run


def test_spans_are_read_with_thread_arguments_and_nesting(capture):
    _, sp = capture
    assert len(sp) == 32 and {s.thread for s in sp} == {0, 1, 2}
    assert spans.scheduler_thread(sp) == 0
    assert not [s for s in sp if s.name.startswith("$")]
    steps = [s for s in sp if s.name == "engine-step"]
    assert [s.args["tick"] for s in steps] == [7, 8, 9]
    for step in steps:
        kids = [s for s in sp if s.parent is step]
        assert [k.name for k in kids] == [
            "engine-admit", "engine-plan", "engine-ragged-tick", "engine-apply"]
        tick = kids[2]
        assert [s.name for s in sp if s.parent is tick] == [
            "engine-launch", "engine-fetch"]
    launches = [s for s in sp if s.name == "engine-launch"]
    assert [s.args["prefill_rows"] for s in launches] == [0, 64, 0, 0]
    assert launches[1].args == {"prefill_rows": 64, "prefill_tokens": 50,
                                "decode_rows": 48}
    assert launches[3].parent is None      # its step never closed
    writes = [s for s in sp if s.name == "serve-write"]
    assert len(writes) == 5 and all(
        w.parent.name == "serve-api-stream" and w.parent.thread == w.thread
        for w in writes)


def test_idle_by_span_sums_to_the_idle_total(capture):
    reduced, sp = capture
    total = spans.idle_seconds(reduced)
    assert total == pytest.approx(200 * US)
    by = spans.idle_by_span(reduced, sp)
    assert sum(by.values()) == pytest.approx(total)
    assert by == pytest.approx({
        "engine-admit": 6 * US, "engine-plan": 14 * US,
        "engine-launch": 30 * US, "engine-fetch": 13 * US,
        "engine-apply": 89 * US, "engine-wait": 4 * US,
        "engine-step": 11 * US, "engine-ragged-tick": 6 * US, None: 27 * US})
    # by phase: a step's and a tick's own stretches are under no phase
    phases = spans.idle_by_span(reduced, sp, spans.PHASES)
    assert sum(phases.values()) == pytest.approx(total)
    assert phases[None] == pytest.approx(44 * US)
    assert phases["engine-apply"] == pytest.approx(89 * US)


def test_idle_under_counts_an_instant_once(capture):
    reduced, sp = capture
    # writes 110-120 (device busy), 210-230 and 220-240 (overlapping: 30 us
    # of idle, not 40), 505-512, 650-660
    assert spans.idle_under(reduced, sp, "serve-write") == pytest.approx(47 * US)
    assert spans.idle_under(reduced, sp, "no-such-span") is None


def test_launch_kinds_assigns_every_whole_execution_once(capture):
    reduced, sp = capture
    kinds = spans.launch_kinds(reduced, sp)
    # five executions; the first was launched before the capture opened, the
    # last one's fetch ended after it closed: three are whole
    assert len(kinds) == 3
    assert len({id(k["launch"]) for k in kinds}) == 3
    assert [k["prefill_rows"] for k in kinds] == [0, 64, 0]
    assert [(k["end"] - k["start"]) / 1e3 for k in kinds] == pytest.approx(
        [100, 240, 100])
    assert all(k["on_clock"] and not k["ambiguous"] for k in kinds)
    assert all(k["launch"].start <= k["start"] and k["fetch"].end >= k["end"]
               for k in kinds)


def test_a_traced_run_reports_the_clock_check_once(capture, capsys):
    reduced, sp = capture
    run = _run(reduced, sp)
    spans.phase_shares(run)
    spans.phase_shares(run)               # cached: no second report
    out = capsys.readouterr().out
    assert out.count("3 whole tick executions matched to a launch, 1 with "
                     "prefill rows; 0 ambiguous, 0 with the launch after") == 1


def test_span_readers_return_the_hand_computed_values(capture):
    reduced, sp = capture
    run = _run(reduced, sp)
    want = {"idle_apply_share.batch": 44.5,          # 89 / 200
            "idle_plan_share.batch": 25.0,           # (6 + 14 + 30) / 200
            "idle_unattributed_share.batch": 22.0,   # 44 / 200
            "idle_write_share.batch": 23.5}          # 47 / 200
    for name, value in want.items():
        assert _reader(name).reduce(run) == pytest.approx(value), name
    # with fetch 6.5% and wait 2% the attribution closes
    assert 44.5 + 25.0 + 22.0 + 6.5 + 2.0 == pytest.approx(100.0)


def test_counter_readers_return_the_hand_computed_values():
    phase = 'mlt_engine_tick_phase_seconds_sum{phase="%s"}'
    counters = {"mlt_engine_ticks_total": 200.0,
                phase % "admit": 0.2, phase % "plan": 0.6,
                phase % "launch": 1.0, phase % "apply": 2.2,
                phase % "fetch": 23.0,
                'mlt_engine_tick_kind_total{kind="prefill"}': 150.0,
                'mlt_engine_tick_kind_total{kind="decode"}': 50.0}
    run = _run(counters=counters)
    # (0.2 + 0.6 + 1.0 + 2.2) s over 200 ticks; the fetch is not host work
    assert _reader("host_work_ms.batch").reduce(run) == pytest.approx(20.0)
    assert _reader("prefill_tick_share.batch").reduce(run) == pytest.approx(75.0)


TRAIN = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
  event_metadata { key: 1 value { id: 1 name: "jit_train_step(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = bf16[4096,4096] fusion(%%p.1), kind=kOutput" } }
  event_metadata { key: 3 value { id: 3 name: "%%flash_fwd.13 = (bf16[1,32,4096,128]) custom-call(%%q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.7 = f32[4096] fusion(%%p.7), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%%flash_fwd.14 = (bf16[1,32,4096,128]) custom-call(%%q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 6 value { id: 6 name: "%%flash_bwd_dq.9 = bf16[1,32,4096,128] custom-call(%%q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 7 value { id: 7 name: "%%flash_bwd_dkv.9 = (bf16[1,8,4096,128]) custom-call(%%q), custom_call_target=\\"tpu_custom_call\\"" } }
}
"""
TRAIN_SCOPES = {
    "fusion.1": "jit(train_step)/jvp(forward)/while/body/closed_call/mlp/dot_general",
    "fusion.7": "jit(train_step)/transpose(jvp(forward))/lm_head_loss/reduce_sum",
    "flash_fwd.13": "jit(train_step)/jvp(forward)/while/body/closed_call/"
                    "attention/flash_fwd/pallas_call",
    "flash_fwd.14": "jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
                    "checkpoint/rematted_computation/attention/flash_fwd/pallas_call",
    "flash_bwd_dq.9": "jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
                      "checkpoint/attention/flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.9": "jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
                       "checkpoint/attention/flash_bwd_dkv/pallas_call"}


def _event(mid, start_us, end_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {(end_us - start_us) * 10**6} }}")


def test_train_readers_return_the_hand_computed_values():
    """One whole step (0-1000 us) and one the capture cut (1000-1300): the
    whole step's flash kernels take 40 (forward) + 38 (recomputed forward) +
    60 + 50 us; 80 us lie under scope lm_head_loss; the ops are busy 408 us
    in all (the cut step's 40 us of flash_fwd included in busy, not in the
    kernels' sum)."""
    ops = " ".join(_event(*e) for e in (
        (2, 0, 100), (3, 100, 140), (4, 400, 480), (5, 500, 538),
        (6, 540, 600), (7, 600, 650), (3, 1100, 1140)))
    text = TRAIN % (_event(1, 0, 1000), _event(1, 1000, 1300), ops)
    run = _run(trace.reduce_profile(_profile(text), TRAIN_SCOPES))
    assert run.trace.busy_s == pytest.approx(408 * US)
    assert _reader("flash_remat_share.train").reduce(run) == pytest.approx(
        100 * 38 / 188)
    assert _reader("head_loss_share.train").reduce(run) == pytest.approx(
        100 * 80 / 408)


def test_a_program_without_spans_or_names_gives_no_metric():
    """The parent of the PR that added them: the capture holds device events
    and Python-tracer events only, the counters lack the new families, the
    flash kernels are custom-call.N and no scope is called lm_head_loss.
    Every new reader returns None and none raises."""
    with open(os.path.join(HERE, "recorded_tick.textproto")) as f:
        profile = _profile(f.read())
    reduced = trace.reduce_profile(profile, {})
    assert spans.from_profile(profile) == []
    run = _run(reduced, [], {"mlt_engine_ticks_total": 300.0})
    for path in sorted(os.listdir(LAYER_METRICS)):
        mod = cells.Cell.reader_at(os.path.join(LAYER_METRICS, path))
        if "lib import spans" in open(os.path.join(LAYER_METRICS, path)).read() \
                or path.startswith(("host_work_ms", "prefill_tick_share",
                                    "flash_remat_share", "head_loss_share")):
            assert mod.reduce(run) is None, path
    assert _reader("flash_remat_share.train").reduce(_run()) is None
    assert spans.of(_run()) == [] and spans.phase_shares(_run()) is None
