"""A later PR adds a configuration, a traffic mix, a cell and a metric by
adding files and entries only.  This adds a throw-away cell, a throw-away
per-layer metric and a throw-away end-to-end metric in a temporary copy of
the benchmark and reads them back through the harness; and it holds
BENCHMARK.json and the files under benchmark/ to the contract's shape."""

import glob
import json
import os
import re
import shutil
import types

import pytest

from benchmark.lib import cells, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_add_a_cell_and_metrics_from_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    bench = _bench()
    base = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(base, "configs", "falcon-7b.json")))
    cfg["flags"]["max_batch_slots"] = 64
    json.dump(cfg, open(os.path.join(base, "configs", "falcon-7b.small.json"), "w"))
    mix = json.load(open(os.path.join(base, "traffic", "batch_closed.json")))
    mix.update(kind="open_loop", rate_per_s=3.0)
    json.dump(mix, open(os.path.join(base, "traffic", "chat_steady.json"), "w"))
    open(os.path.join(base, "layer_metrics", "answer.test.py"), "w").write(
        "LAYER = 'nowhere'\nUNIT = 'count'\nMOVES = 'requests_total'\n"
        "SOURCE = 'program_counter'\n\ndef reduce(run):\n    return run.attempted * 2\n")
    open(os.path.join(base, "end_to_end", "requests_total.py"), "w").write(
        "UNIT = 'count'\nSOURCE = 'host_clock'\n\ndef reduce(run):\n    return run.attempted\n")
    open(os.path.join(base, "layer_metrics", "nothing.test.py"), "w").write(
        "def reduce(run):\n    return None\n")
    bench["configs"].append({"name": "falcon-7b.small", "source": cfg["source"],
                             "file": "benchmark/configs/falcon-7b.small.json",
                             "reduced": ["num_hidden_layers"], "why": "throw-away"})
    bench["workloads"].append({"name": "falcon7b_chat_steady", "config": "falcon-7b.small",
                               "traffic": "chat_steady", "chips": 1, "why": "throw-away"})
    bench["end_to_end"].append({"name": "requests_total", "unit": "count",
                                "better": "higher", "bound": 0.01, "source": "host_clock",
                                "workloads": ["falcon7b_chat_steady"]})
    for n in ("answer.test", "nothing.test"):
        bench["per_layer"].append({"name": n, "unit": "count", "better": "higher",
                                   "source": "program_counter", "layer": "nowhere",
                                   "moves": "requests_total",
                                   "workloads": ["falcon7b_chat_steady"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = cells.Cell("falcon7b_chat_steady", root=root)
    assert cell.config["flags"]["max_batch_slots"] == 64
    assert cell.traffic["rate_per_s"] == 3.0 and cell.model["head_dim"] == 64
    assert "--max_batch_slots" in cell.flags()
    assert cells.load_kind(cell.traffic["kind"]).run       # the kind's runner exists
    assert [m["name"] for m in cell.per_layer] == ["answer.test", "nothing.test"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "requests_total"}

    args = types.SimpleNamespace(seed=1, seconds=1.0, trace=1, rehearsal=0, rate=None)
    run = harness.Run(cell, args, harness.Clock(0.0))
    run.attempted, run.setup_s = 21, 3.5
    run.memory_peak = lambda: 0
    line = harness.result_line(cell, args, run)
    # a reader that finds nothing is left out of the line
    assert line["metrics"] == {"answer.test": {"value": 42.0, "unit": "count"}}
    args.trace = 0
    line = harness.result_line(cell, args, run)
    assert line["metrics"] == {"requests_total": {"value": 21.0, "unit": "count"},
                               "setup_s": {"value": 3.5, "unit": "s"}}
    with pytest.raises(KeyError):
        cells.load_kind("no_such_kind")
    with pytest.raises(KeyError):
        cells.Cell("no_such_cell", root=root)


def test_run_py_names_no_cell_config_or_metric():
    src = open(os.path.join(ROOT, "benchmark", "run.py")).read()
    bench = _bench()
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[g]] + [w["traffic"] for w in bench["workloads"]]
    assert not [n for n in names if n in src]


def test_benchmark_json_holds_to_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    cfg_names = [c["name"] for c in b["configs"]]
    files = [c["file"] for c in b["configs"]]
    assert len(set(cfg_names)) == len(cfg_names) and len(set(files)) == len(files)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not re.search(
                r"(_dim|_rank|hidden_size|intermediate_size|head)", key)
            assert body[key] != body["published"][key]
        assert {"assumed", "deployment", "flags", "reference"} <= set(body)
        # a configuration gives a limit for each statistic it compares: the
        # mean always; the largest only where it has an upper reading
        tol = body["tolerance"]
        assert 0 < tol["mean_abs_nats"] < tol.get("max_abs_nats", float("inf"))
        assert 0 < tol.get("median_abs_nats", tol["mean_abs_nats"]) <= tol["mean_abs_nats"]
        assert c["name"] in [w["config"] for w in b["workloads"]]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    pairs = set()
    four = 0
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        cell = cells.Cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.per_layer:      # what it moves is reported where it is
            assert m["moves"] in names
    assert four <= max(1, len(b["workloads"]) // 4)
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "end_to_end",
                                           m["name"] + ".py"))
    layers = set()
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher") and m["moves"] in e2e
        mod = cells.Cell.reader_at(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        layers.add(m["layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    assert all(layer in perf for layer in layers)   # PERF.md's list of layers
    every = {m["name"] for m in b["end_to_end"] + b["per_layer"]}
    on_disk = {os.path.basename(p)[:-3] for d in ("end_to_end", "layer_metrics")
               for p in glob.glob(os.path.join(ROOT, "benchmark", d, "*.py"))}
    assert on_disk == every        # no reader, configuration or mix waits unread
    for d, used in (("configs", {os.path.basename(c["file"]) for c in b["configs"]}),
                    ("traffic", {w["traffic"] + ".json" for w in b["workloads"]})):
        assert set(os.listdir(os.path.join(ROOT, "benchmark", d))) == used
