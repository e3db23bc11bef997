"""The five readers under `setup_s` (PR 55) on a fabricated run and a planted
compile log: which rows count (those that ended between the process's start
and the window's opening), which compiles count as cold (every one the cache
did not answer), and nothing where the program keeps no log or never
installed it."""

import collections
import os
import types

import pytest

from benchmark.lib import cells, harness, startup
from megatron_llm_tpu.observability import compiles

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("setup_wall_s", "setup_backend_s", "setup_compile_s",
         "setup_cold_compile_s", "setup_trace_lower_s")
READERS = {n: cells.Cell.reader_at(os.path.join(
    os.path.dirname(HERE), "layer_metrics", n + ".py")) for n in NAMES}
# the shape of the program's rows, made here: the readers go by field names
Row = collections.namedtuple("Row", "t_end stage fun_name seconds outcome")
START, INSTALLED, OPEN, CLOSE = 100.0, 107.5, 160.0, 200.0
PLANTED = [
    Row(99.0, "compile", "jit(before)", 50.0, "cold"),  # another process's clock
    Row(108.0, "trace", "build", 0.5, None),
    Row(109.0, "lower", "jit(build)", 0.25, None),
    Row(112.0, "compile", "jit(build)", 3.0, "cold"),    # cold
    Row(113.0, "compile", "jit(add)", 0.125, "cold"),    # cold, and too cheap to list
    Row(120.0, "trace", "tick", 2.0, None),
    Row(122.0, "lower", "jit(tick)", 4.0, None),
    Row(123.0, "compile", "jit(tick)", 1.5, "hit"),      # a load
    Row(150.0, "compile", "jit(tick)", 20.0, "cold"),    # a cold bucket
    Row(170.0, "compile", "jit(late)", 7.0, "cold"),     # inside the window
    Row(170.5, "trace", "late", 9.0, None),
    Row(210.0, "compile", "jit(after)", 11.0, "hit"),    # after it
]


def _run(t_open=OPEN):
    run = types.SimpleNamespace(clock=harness.Clock(START), t_open=t_open,
                                t_close=CLOSE, setup_s=None)
    if t_open is not None:
        run.setup_s = t_open - START
    return run


@pytest.fixture
def planted(monkeypatch):
    monkeypatch.setattr(compiles, "log", lambda: list(PLANTED))
    monkeypatch.setattr(compiles, "installed_at", lambda: INSTALLED)


def test_the_planted_sums(planted, capsys):
    run = _run()
    got = {n: READERS[n].reduce(run) for n in NAMES}
    assert got == {"setup_wall_s": 60.0, "setup_backend_s": 7.5,
                   "setup_compile_s": 3.0 + 0.125 + 1.5 + 20.0,
                   "setup_cold_compile_s": 3.0 + 0.125 + 20.0,
                   "setup_trace_lower_s": 0.5 + 0.25 + 2.0 + 4.0}
    out = capsys.readouterr().out
    assert out.count("benchmark: set-up's programs") == 1    # once a run
    assert "compiles 4 (1 hit, 3 cold) 24.62 s, trace 2.50 s, lower 4.25 s" in out
    assert ("tick x4 hit 1 cold 1 (20.00) trace 2.00 lower 4.00 compile 21.50 "
            "last +42.5") in out
    assert "add x1 hit 0 cold 1 trace" in out      # under a second: unlisted
    assert not any(n in out for n in ("before x", "late x", "after x"))


def test_a_warm_run_reads_what_the_cache_never_keeps(planted, monkeypatch):
    """Every program the cache keeps loaded: cold are the cheap ones alone,
    and zero where those are kept too."""
    warm = [r._replace(outcome="hit")
            if r.stage == "compile" and r.seconds >= 1.0 else r for r in PLANTED]
    monkeypatch.setattr(compiles, "log", lambda: warm)
    run = _run()
    assert READERS["setup_cold_compile_s"].reduce(run) == 0.125
    assert READERS["setup_compile_s"].reduce(run) == 24.625
    monkeypatch.setattr(compiles, "log", lambda: [
        r._replace(outcome="hit") if r.stage == "compile" else r
        for r in PLANTED])
    assert READERS["setup_cold_compile_s"].reduce(_run()) == 0.0


@pytest.mark.parametrize("missing", ["installed", "module", "window"])
def test_nothing_where_there_is_no_log(planted, monkeypatch, missing):
    run = _run(None if missing == "window" else OPEN)
    if missing == "installed":
        monkeypatch.setattr(compiles, "installed_at", lambda: None)
    if missing == "module":      # the parent's compiles.py: two counters, no log
        monkeypatch.delattr(compiles, "log")
    parts = {n: READERS[n].reduce(run) for n in NAMES[2:]}
    assert parts == dict.fromkeys(NAMES[2:])
    # the backend's reading needs the installation alone, the whole neither
    assert READERS["setup_backend_s"].reduce(run) == (
        7.5 if missing == "window" else None)
    assert READERS["setup_wall_s"].reduce(run) == run.setup_s


def test_readers_on_the_programs_own_rows(monkeypatch):
    """The program's Row has the fields the readers go by."""
    mine = [compiles.Row(110.0, "compile", "jit(f)", 2.0, "cold"),
            compiles.Row(111.0, "trace", "f", 0.5, None)]
    monkeypatch.setattr(compiles, "log", lambda: mine)
    monkeypatch.setattr(compiles, "installed_at", lambda: INSTALLED)
    run = _run()
    assert READERS["setup_compile_s"].reduce(run) == 2.0
    assert READERS["setup_trace_lower_s"].reduce(run) == 0.5
