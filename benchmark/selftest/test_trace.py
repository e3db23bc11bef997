"""The trace reduction on a hand-made trace (figures worked out by hand)
and on one small recorded trace: a whole tick of the 4-layer Mistral engine
probe on a TPU v5e (PR 23), cut from the profiler's own file with the
operation texts shortened; its figures were taken by an independent count
(marking 100 ns cells) when it was cut."""

import json
import os
import warnings

import pytest

from benchmark.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


def _event(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    %s %s %s %s %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%%while.1 = (s32[]) while(%%tuple.1), body=%%b" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.1 = bf16[8,128] fusion(%%p.1), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%%all-reduce.1 = bf16[8,128] all-reduce(%%fusion.1), replica_groups={}" } }
  event_metadata { key: 5 value { id: 5 name: "%%paged_attention.1 = bf16[8,128] custom-call(%%q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 6 value { id: 6 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000 %s %s }
  event_metadata { key: 1 value { id: 1 name: "$engine.py:1 schedule" } }
  event_metadata { key: 2 value { id: 2 name: "$threading.py:1 wait" } }
}
""" % (_event(1, 0, 100), _event(1, 200, 100),
       _event(2, 0, 100), _event(3, 10, 30), _event(4, 40, 30),
       _event(5, 70, 20), _event(6, 200, 100),
       _event(1, 110, 60), _event(2, 100, 100))


def test_hand_made_trace():
    names = {"fusion.2": "jit(train_step)/optimizer/add",
             "fusion.1": "jit(tick)/while/body/dot_general"}
    r = trace.reduce_profile(_profile(HAND), names)
    assert len(r.devices) == 1
    assert r.window_s == pytest.approx(300e-6)
    assert r.busy_s == pytest.approx(200e-6)          # busy share 2/3
    by_name = {o.name: o for o in r.ops()}
    assert by_name["while.1"].self_ns == pytest.approx(20e3)   # 100-30-30-20
    assert r.self_seconds(lambda o: o.is_pallas) == pytest.approx(20e-6)
    assert r.self_seconds(lambda o: "/optimizer/" in o.op_name) == pytest.approx(100e-6)
    assert r.collective_exposed_s() == pytest.approx(30e-6)
    assert r.module_durations(r"^jit_tick\(") == pytest.approx([100e-6, 100e-6])
    (start, gap), = r.idle_gaps(1)
    assert gap == pytest.approx(100e-6) and start == pytest.approx(1000 + 100e3)
    # the wait spans the gap but is a wait; the schedule call lies inside it
    assert r.labelled_gaps(1)[0][0].startswith("engine.py:1 schedule")
    top = r.top_ops(2)
    assert top[0][0].startswith("fusion.2") and top[0][1] == pytest.approx(100e-6)
    # self times add up to busy time: nothing is counted twice
    assert sum(o.self_ns for o in r.ops()) / 1e9 == pytest.approx(r.busy_s)


def test_recorded_tick():
    with open(os.path.join(HERE, "recorded_tick.textproto")) as f:
        text = f.read()
    assert len(text) < 200_000
    with open(os.path.join(HERE, "recorded_tick.op_names.json")) as f:
        names = json.load(f)
    r = trace.reduce_profile(_profile(text), names)
    assert r.window_s == pytest.approx(0.156198138, rel=1e-6)
    assert r.busy_s == pytest.approx(0.147888, rel=1e-5)     # 94.68% busy
    assert 100 * r.busy_s / r.window_s == pytest.approx(94.68, abs=0.01)
    kernel = r.self_seconds(lambda o: o.is_pallas and "paged_attention" in o.name)
    assert kernel == pytest.approx(0.116355198, rel=1e-6)    # 4 layers' calls
    assert len(r.ops(lambda o: o.is_pallas)) == 4
    assert r.module_durations(r"^jit_tick\(") == pytest.approx([0.147888042], rel=1e-6)
    assert r.idle_gaps(1)[0][1] == pytest.approx(0.008310157, rel=1e-6)
    assert sum(o.self_ns for o in r.ops()) / 1e9 == pytest.approx(r.busy_s)
    assert any("paged_attention" in n for n in
               (o.op_name for o in r.ops(lambda o: o.is_pallas)))


def test_wire_reader_finds_instruction_names(tmp_path):
    """An HloInstructionProto-shaped message nested three deep: field 1
    name, 2 opcode, 7 metadata{2 op_name}."""
    def field(no, payload):
        return bytes([(no << 3) | 2, len(payload)]) + payload
    meta = field(2, b"jit(f)/optimizer/mul")
    inst = field(1, b"fusion.7") + field(2, b"fusion") + field(7, meta)
    blob = field(1, field(3, field(2, inst)) + field(1, b"padding-bytes"))
    p = tmp_path / "x.pb"
    p.write_bytes(blob)
    assert trace.hlo_op_names(str(p)) == {"fusion.7": "jit(f)/optimizer/mul"}
