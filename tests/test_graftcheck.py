"""tools/graftcheck — the AST invariant analyzer (tier-1 CI gate).

Four layers:

(a) per-rule fixtures — for every rule: a positive (the violation is
    found), a negative (the compliant twin is clean), a suppressed
    variant (``# graftcheck: noqa[rule]`` silences exactly that
    finding) and a baselined variant (a baseline entry absorbs it);
(b) the historical-bug fixtures — each new analyzer reproduces the real
    regression it exists to prevent (id-keyed cached_jit from PR 1, the
    direct shard_map import that cost 8 tests, a device-syncing
    instrument, unguarded shared state, pinned-key reuse);
(c) the CLI contract — JSON schema, exit codes 0/1/2 (a caller can tell
    an analyzer crash from findings), and the
    tools/linter.py shim's legacy surface;
(d) the full-repo sweep — zero non-baselined findings on this tree,
    every baseline entry explained, no stale entries, under the 30 s
    budget.  THIS is the gate: a PR that introduces a violation fails
    here with the exact finding text.

Note every forbidden spelling in the fixtures below is composed from
string fragments: the legacy lexical sweep (tools/linter.py
SHARD_MAP_RE, still pinned by older tests) scans raw test-file lines.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftcheck import core  # noqa: E402
from tools.graftcheck.rules import (  # noqa: E402
    ALL_RULES,
    DEFAULT_RULES,
    PROJECT_RULES,
    RULES_BY_ID,
)

_SM = "shard" + "_map"  # keep the spelling out of raw source lines
_DG = "device" + "_get"
_BUR = "block_until" + "_ready"


def findings_for(src: str, path: str = "fixture.py",
                 rules=None):
    fs = core.check_file(path, rules or ALL_RULES, source=src)
    return fs


def rules_hit(src: str, path: str = "fixture.py"):
    return sorted({f.rule for f in findings_for(src, path)})


# ---------------------------------------------------------------------------
# (a) per-rule positive / negative / suppressed / baselined
# ---------------------------------------------------------------------------

# rule id -> (positive source, negative twin).  The positive must yield
# at least one finding of that rule; the negative must yield none.
FIXTURES = {
    "todo-owner": (
        "x = 1  # TODO fix this\n",
        'x = 1  # TODO(mika) fix this\ns = "a TODO in a string is data"\n',
    ),
    "obs-no-sync": (
        f"import jax\nx = jax.{_DG}(y)\n",
        f'"""Docstring may say {_DG} and {_BUR} freely now."""\n'
        f"# prose comment about {_DG} is fine too\nx = 1\n",
    ),
    "no-direct-shard-map": (
        f"from jax import {_SM}\n",
        f'msg = "jax.{_SM} belongs in parallel/compat.py"\n'
        f"from megatron_llm_tpu.parallel.compat import {_SM}\n",
    ),
    "sync-in-jit": (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x)\n",
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x * 2\n"
        "def host(x):\n"
        "    return float(x)\n",
    ),
    "lock-discipline": (
        "import threading\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._free = []  # guarded by _lock\n"
        "    def take(self):\n"
        "        return self._free.pop()\n",
        "import threading\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._free = []  # guarded by _lock\n"
        "    def take(self):\n"
        "        with self._lock:\n"
        "            return self._free.pop()\n",
    ),
    "rng-key-reuse": (
        "import jax\n"
        "def sample(key):\n"
        "    a = jax.random.normal(key)\n"
        "    b = jax.random.uniform(key)\n"
        "    return a + b\n",
        "import jax\n"
        "def sample(key):\n"
        "    key, sub = jax.random.split(key)\n"
        "    a = jax.random.normal(sub)\n"
        "    key, sub = jax.random.split(key)\n"
        "    return a + jax.random.uniform(sub)\n",
    ),
    "recompile-hazard": (
        "import jax\n"
        "def make(cfg, build, cache):\n"
        "    k = (id(cfg), 'tick')\n"
        "    if k not in cache:\n"
        "        cache[k] = jax.jit(build())\n"
        "    return cache[k]\n",
        "import jax\n"
        "def make(cfg, build, cache, fingerprint):\n"
        "    k = (fingerprint(cfg), 'tick')\n"
        "    if k not in cache:\n"
        "        cache[k] = jax.jit(build())\n"
        "    return cache[k]\n",
    ),
    "span-device-attr": (
        # ISSUE 12: a jax array as a span/flight-event attr defers a
        # host sync to dump time — flagged whether passed directly or
        # through a name bound to a device-producing call
        "import jax.numpy as jnp\n"
        "from megatron_llm_tpu.observability import trace\n"
        "def tick(x, rec):\n"
        "    y = jnp.sum(x)\n"
        "    with trace.span('tick', val=y):\n"
        "        pass\n"
        "    rec.event('spec_tick', logits=jnp.exp(x))\n",
        "import jax.numpy as jnp\n"
        "from megatron_llm_tpu.observability import trace\n"
        "def tick(x, rec):\n"
        "    y = jnp.sum(x)\n"
        "    n = int(y)\n"
        "    with trace.span('tick', val=n):\n"
        "        pass\n"
        "    rec.event('spec_tick', emitted=len(x))\n",
    ),
    "line-length": (
        "x = 1  # " + "y" * 120 + "\n",
        "x = 1\n",
    ),
    "tabs": (
        "x = 1\t# tab\n",
        "x = 1  # spaces\n",
    ),
    "trailing-whitespace": (
        "x = 1   \n",
        "x = 1\n",
    ),
}


def test_every_rule_has_a_fixture():
    assert set(FIXTURES) | set(PROJECT_FIXTURES) == set(RULES_BY_ID), (
        "each rule needs positive/negative fixtures (per-file rules in "
        "FIXTURES, project rules in PROJECT_FIXTURES)")
    assert set(FIXTURES) == {r.id for r in ALL_RULES}
    assert set(PROJECT_FIXTURES) == {r.id for r in PROJECT_RULES}


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_positive(rule_id):
    bad, _good = FIXTURES[rule_id]
    path = ("observability/fixture.py" if rule_id == "obs-no-sync"
            else "fixture.py")
    hits = [f for f in findings_for(bad, path) if f.rule == rule_id]
    assert hits, f"{rule_id}: positive fixture produced no finding"


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_negative(rule_id):
    _bad, good = FIXTURES[rule_id]
    path = ("observability/fixture.py" if rule_id == "obs-no-sync"
            else "fixture.py")
    hits = [f for f in findings_for(good, path) if f.rule == rule_id]
    assert not hits, f"{rule_id}: negative fixture flagged: {hits}"


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_suppressed(rule_id):
    """Appending ``# graftcheck: noqa[rule]`` on each finding line
    silences exactly that rule's findings."""
    bad, _good = FIXTURES[rule_id]
    path = ("observability/fixture.py" if rule_id == "obs-no-sync"
            else "fixture.py")
    hits = [f for f in findings_for(bad, path) if f.rule == rule_id]
    lines = bad.splitlines()
    for ln in sorted({f.line for f in hits}):
        lines[ln - 1] += f"  # graftcheck: noqa[{rule_id}] — fixture"
    suppressed = "\n".join(lines) + "\n"
    left = [f for f in findings_for(suppressed, path)
            if f.rule == rule_id]
    assert not left, f"{rule_id}: noqa did not suppress: {left}"


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_baselined(rule_id):
    """A baseline entry keyed (path, rule, stripped line) absorbs the
    finding — it still appears, marked baselined, and does not fail."""
    bad, _good = FIXTURES[rule_id]
    path = ("observability/fixture.py" if rule_id == "obs-no-sync"
            else "fixture.py")
    fs = [f for f in findings_for(bad, path) if f.rule == rule_id]
    src_lines = bad.splitlines()
    entries = [{"path": path, "rule": rule_id,
                "line": src_lines[f.line - 1].strip(),
                "reason": "fixture grandfathering", "count": 99}
               for f in fs]
    all_fs = findings_for(bad, path)
    core.apply_baseline(
        all_fs, entries,
        lambda f: src_lines[f.line - 1]
        if 1 <= f.line <= len(src_lines) else "")
    for f in all_fs:
        if f.rule == rule_id:
            assert f.baselined, f"{rule_id}: baseline did not absorb {f}"


# ---------------------------------------------------------------------------
# (b) the historical bugs, reproduced
# ---------------------------------------------------------------------------


def test_historic_id_keyed_cached_jit():
    """PR 1: cached_jit keyed on id(cfg) — id recycling serves a stale
    program; rebuilt-but-equal configs recompile.  The recompile-hazard
    rule pins the pattern."""
    src = (
        "import jax\n"
        "_JIT_CACHE = {}\n"
        "def cached_jit(cfg, name, build, **kw):\n"
        "    key = (id(cfg), name)\n"
        "    fn = _JIT_CACHE.get(key)\n"
        "    if fn is None:\n"
        "        fn = jax.jit(build(), **kw)\n"
        "        _JIT_CACHE[key] = fn\n"
        "    return fn\n"
    )
    hits = [f for f in findings_for(src) if f.rule == "recompile-hazard"]
    assert len(hits) == 1 and hits[0].line == 4
    assert "id()" in hits[0].message


def test_ragged_metadata_in_cached_jit_statics_flagged():
    """ISSUE 11: per-tick ragged batch composition (spans / horizons /
    k_eff) in a cached_jit STATICS key compiles one executable per tick
    mix — the dispatch explosion the ragged kernel removes.  The
    recompile-hazard rule pins the pattern; composition must be a traced
    operand (generation/ragged.py contract)."""
    bad_inline = (
        "from megatron_llm_tpu.generation import generation as gen\n"
        "def tick_fn(self, spans, horizons):\n"
        "    return gen.cached_jit(\n"
        "        self.cfg, 'engine_ragged_tick',\n"
        "        ('engine_ragged_tick', self.max_slots, tuple(spans),\n"
        "         tuple(horizons)),\n"
        "        lambda: None)\n"
    )
    hits_inline = [f for f in findings_for(bad_inline)
                   if f.rule == "recompile-hazard"
                   and "ragged" in f.message]
    assert hits_inline, "ragged metadata in statics not flagged"
    # k_eff sneaking in as an attribute is caught too
    bad_attr = (
        "from megatron_llm_tpu.generation import generation as gen\n"
        "def tick_fn(self):\n"
        "    return gen.cached_jit(\n"
        "        self.cfg, 't', ('t', self.k_eff), lambda: None)\n"
    )
    assert [f for f in findings_for(bad_attr)
            if f.rule == "recompile-hazard" and "ragged" in f.message]
    # the engine's REAL statics (geometry capacities, dtypes, mesh) are
    # clean — capacities like prefill_rows are shapes, not composition
    good = (
        "from megatron_llm_tpu.generation import generation as gen\n"
        "def tick_fn(self, pre_rows):\n"
        "    return gen.cached_jit(\n"
        "        self.cfg, 'engine_ragged_tick',\n"
        "        ('engine_ragged_tick', self.max_slots, pre_rows,\n"
        "         self.pages_per_seq, str(self.pool.k.dtype)),\n"
        "        lambda: None)\n"
    )
    assert not [f for f in findings_for(good)
                if f.rule == "recompile-hazard"]


def test_historic_direct_shard_map_import():
    """Every direct spelling is caught, and compat.py itself is
    exempt."""
    spellings = [
        f"from jax import {_SM}\n",
        f"import jax.experimental.{_SM}\n",
        f"from jax.experimental.{_SM} import {_SM}\n",
        f"from jax.experimental import {_SM}\n",
        f"fn = jax.{_SM}(f, mesh=m)\n",
        f"fn = jax.experimental.{_SM}.{_SM}(f)\n",
        "from jax.sharding import get_" + "abstract_mesh\n",
    ]
    for src in spellings:
        hits = [f for f in findings_for(src)
                if f.rule == "no-direct-shard-map"]
        assert len(hits) == 1, f"missed: {src!r} -> {hits}"
    exempt = findings_for(f"from jax.experimental.{_SM} import {_SM}\n",
                          path="megatron_llm_tpu/parallel/compat.py")
    assert not [f for f in exempt if f.rule == "no-direct-shard-map"]


def test_historic_sync_in_instrument():
    """A 'metrics' helper that drains per-step values with device_get
    inside the jitted step — the exact overlap-destroying shape PR 2
    banished to log boundaries."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def train_step(state, batch):\n"
        "    loss = (batch * state).sum()\n"
        "    record(float(loss))\n"
        f"    record(np.asarray(jax.{_DG}(loss)))\n"
        "    return state, loss\n"
    )
    hits = [f for f in findings_for(src) if f.rule == "sync-in-jit"]
    assert {f.line for f in hits} == {6, 7}
    # and the shard_map-body route sees the same violation
    src2 = (
        "from megatron_llm_tpu.parallel.compat import "
        + _SM + "\n"
        "def body(x):\n"
        "    return int(x.sum())\n"
        + f"fn = {_SM}(body, mesh=None, in_specs=None, out_specs=None)\n"
    )
    hits2 = [f for f in findings_for(src2) if f.rule == "sync-in-jit"]
    assert [f.line for f in hits2] == [3]


def test_historic_unguarded_shared_state():
    """The AsyncCheckpointSaver shape: a writer thread publishing an
    error field the caller reads bare.  Both directions are checked:
    guarded-attr access outside the lock AND calling a '# holds' method
    without it."""
    src = (
        "import threading\n"
        "class Saver:\n"
        "    def __init__(self):\n"
        "        self._err_lock = threading.Lock()\n"
        "        self._error = None  # guarded by _err_lock\n"
        "    def _write(self, e):\n"
        "        self._error = e\n"
        "    def _clear(self):  # holds _err_lock\n"
        "        self._error = None\n"
        "    def wait(self):\n"
        "        self._clear()\n"
        "    def wait_ok(self):\n"
        "        with self._err_lock:\n"
        "            self._clear()\n"
    )
    hits = [f for f in findings_for(src) if f.rule == "lock-discipline"]
    assert {f.line for f in hits} == {7, 11}


def test_historic_pinned_key_reuse():
    """The engine's bitwise-resume contract pins one PRNG key per
    request; consuming it twice (here: in a decode loop without
    fold_in/split) silently correlates the sampling stream."""
    src = (
        "import jax\n"
        "def decode(key, steps):\n"
        "    toks = []\n"
        "    for _ in range(steps):\n"
        "        toks.append(jax.random.categorical(key, logits))\n"
        "    return toks\n"
    )
    hits = [f for f in findings_for(src) if f.rule == "rng-key-reuse"]
    assert [f.line for f in hits] == [5]
    # the engine's actual per-step shape (fold_in on the pinned key) is
    # the documented-legal idiom and stays clean
    ok = (
        "import jax\n"
        "def decode(key, steps):\n"
        "    toks = []\n"
        "    for i in range(steps):\n"
        "        k = jax.random.fold_in(key, i)\n"
        "        toks.append(jax.random.categorical(k, logits))\n"
        "    return toks\n"
    )
    assert not [f for f in findings_for(ok) if f.rule == "rng-key-reuse"]


def test_historic_spec_draft_verify_key_reuse():
    """ISSUE 9: the speculative tick derives draft-sampling keys, an
    acceptance-uniform key, and the rejection-residual key from one
    per-(request, step) base.  The buggy shape — the rejection sampler
    re-consuming the key the acceptance uniforms already consumed — makes
    the residual draw perfectly correlated with the accept/reject coin,
    which silently biases the 'lossless' output distribution.  The rule
    must flag the reuse; the shipped disjoint-fold_in fan-out
    (speculative/verify.py) must stay clean."""
    bad = (
        "import jax\n"
        "def accept_and_emit(base_key, k, resid_logits):\n"
        "    u = jax.random.uniform(base_key, (k,))\n"
        "    tok = jax.random.categorical(base_key, resid_logits)\n"
        "    return u, tok\n"
    )
    hits = [f for f in findings_for(bad) if f.rule == "rng-key-reuse"]
    assert [f.line for f in hits] == [4]
    # the shipped shape: one fold_in per stream, each derived key
    # consumed exactly once
    ok = (
        "import jax\n"
        "ACCEPT_STREAM, EMIT_STREAM = 2, 3\n"
        "def accept_and_emit(base_key, k, resid_logits):\n"
        "    u = jax.random.uniform("
        "jax.random.fold_in(base_key, ACCEPT_STREAM), (k,))\n"
        "    tok = jax.random.categorical("
        "jax.random.fold_in(base_key, EMIT_STREAM), resid_logits)\n"
        "    return u, tok\n"
    )
    assert not [f for f in findings_for(ok) if f.rule == "rng-key-reuse"]


def test_docstring_prose_never_false_positives():
    """The _strip_comment bug class, pinned: the old line scanner
    flagged forbidden spellings inside string literals and observability
    docstrings; the AST rules must not."""
    obs = (
        f'"""This instrument never calls {_DG} or {_BUR}:\n'
        "syncing the device would destroy the overlap it measures.\n"
        '"""\n'
        f'BANNED = ("{_DG}", "{_BUR}")  # data, not calls\n'
        "x = 1\n"
    )
    fs = findings_for(obs, path="megatron_llm_tpu/observability/doc.py")
    assert not [f for f in fs if f.rule == "obs-no-sync"], fs
    sm = (
        f'"""jax.{_SM} is spelled in one module only; use\n'
        "parallel/compat.py instead.\n"
        '"""\n'
        f'SPELLING = "jax.experimental.{_SM}"\n'
    )
    fs = findings_for(sm)
    assert not [f for f in fs if f.rule == "no-direct-shard-map"], fs


# ---------------------------------------------------------------------------
# (c) CLI contract: JSON schema, exit codes, linter shim
# ---------------------------------------------------------------------------


def test_json_output_schema(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("x = 1  # TODO fix\n")
    rc = core.main(["--json", "--no-baseline", str(bad)])
    out = capsys.readouterr().out.strip()
    assert rc == 1
    assert len(out.splitlines()) == 1, "JSON mode must emit ONE line"
    doc = json.loads(out)
    assert doc["graftcheck"] == 1
    assert doc["exit"] == 1
    assert doc["files"] == 1
    assert isinstance(doc["seconds"], float)
    assert doc["changed_only"] is False
    assert doc["stale_baseline"] == []
    assert set(doc["counts"]) == {"total", "active", "info", "baselined",
                                  "stale_baseline"}
    assert doc["counts"]["total"] == 1
    (f,) = doc["findings"]
    assert set(f) == {"path", "line", "col", "rule", "message",
                      "baselined", "severity"}
    assert f["rule"] == "todo-owner" and f["line"] == 1
    assert f["severity"] == "error"
    assert len(doc["rules"]) == len(DEFAULT_RULES)


def test_exit_codes(tmp_path, capsys, monkeypatch):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert core.main(["--no-baseline", str(clean)]) == 0
    dirty = tmp_path / "dirty.py"
    dirty.write_text("x = 1\t\n")
    assert core.main(["--no-baseline", str(dirty)]) == 1

    class Boom(core.Rule):
        id = "boom"
        summary = "always crashes"

        def check(self, ctx):
            raise RuntimeError("kaboom")

    import tools.graftcheck.rules as rules_mod

    monkeypatch.setattr(rules_mod, "DEFAULT_RULES", [Boom()])
    assert core.main(["--no-baseline", str(clean)]) == 2
    capsys.readouterr()


def test_syntax_error_is_a_finding_not_a_crash(tmp_path, capsys):
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    rc = core.main(["--no-baseline", str(broken)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "parse-error" in out


def test_linter_shim_legacy_surface(tmp_path, capsys):
    """The shim keeps the old entry points: lint_file counts + prints,
    main() exits 0/1, and the legacy regex exports survive."""
    from tools import linter

    assert linter.SHARD_MAP_RE.search("jax." + _SM)
    assert linter._strip_comment("x  # jax." + _SM) == "x  "

    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert linter.lint_file(str(ok)) == 0
    assert linter.main([str(ok)]) == 0
    capsys.readouterr()

    bad = tmp_path / "bad.py"
    bad.write_text(f"from jax import {_SM}\n")
    assert linter.lint_file(str(bad)) == 1
    assert "compat" in capsys.readouterr().out
    assert linter.main([str(bad)]) == 1
    capsys.readouterr()


def test_update_baseline_roundtrip(tmp_path, capsys):
    """--update-baseline writes entries that then absorb the findings;
    reasons survive a rewrite."""
    bad = tmp_path / "bad.py"
    bad.write_text("x = 1  # TODO fix\n")
    bl = tmp_path / "baseline.json"
    rc = core.main(["--update-baseline", "--baseline", str(bl), str(bad)])
    assert rc == 0
    doc = json.loads(bl.read_text())
    assert len(doc["entries"]) == 1
    entry = doc["entries"][0]
    assert entry["rule"] == "todo-owner" and entry["reason"] == ""
    # fill the reason in (the committed-baseline contract) and re-run
    entry["reason"] = "legacy comment, tracked elsewhere"
    bl.write_text(json.dumps(doc))
    assert core.main(["--baseline", str(bl), str(bad)]) == 0
    # rewriting preserves the hand-written reason
    rc = core.main(["--update-baseline", "--baseline", str(bl), str(bad)])
    assert rc == 0
    doc2 = json.loads(bl.read_text())
    assert doc2["entries"][0]["reason"] == "legacy comment, tracked elsewhere"
    capsys.readouterr()


# ---------------------------------------------------------------------------
# (e) project rules (ISSUE 14): multi-file fixtures + the fact cache
# ---------------------------------------------------------------------------

# rule id -> (positive file set, negative twin).  A file set maps
# relpath -> source; docs/guide/*.md entries feed the contract rules'
# documentation side.  The positive must yield >= 1 ERROR finding of
# the rule; the negative must yield none.
PROJECT_FIXTURES = {
    "lock-order": (
        {
            "pkg/cycle.py": (
                "import threading\n"
                "class Recorder:\n"
                "    def __init__(self, eng):\n"
                "        self._lock = threading.Lock()\n"
                "        self.eng = eng  # instance of Engine\n"
                "    def log(self):\n"
                "        with self._lock:\n"
                "            self.eng.poke()\n"
                "class Engine:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.rec = Recorder(self)\n"
                "    def step(self):\n"
                "        with self._lock:\n"
                "            self.rec.log()\n"
                "    def poke(self):\n"
                "        with self._lock:\n"
                "            pass\n"),
        },
        {
            "pkg/cycle.py": (
                "import threading\n"
                "class Recorder:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def log(self):\n"
                "        with self._lock:\n"
                "            pass\n"
                "class Engine:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.rec = Recorder()\n"
                "    def step(self):\n"
                "        with self._lock:\n"
                "            self.rec.log()\n"),
        },
    ),
    "wire-metrics": (
        {
            "megatron_llm_tpu/m.py": (
                "reg.counter('mlt_fix_undocumented_total')\n"
                "reg.gauge('mlt_fix_labeled_total',\n"
                "          labels={'right': 'x'})\n"),
            "docs/guide/fix.md": (
                "| metric | type | meaning |\n|---|---|---|\n"
                "| `mlt_fix_ghost_total` | counter | never registered |\n"
                "| `mlt_fix_labeled_total{wrong}` | gauge | bad labels |\n"),
        },
        {
            "megatron_llm_tpu/m.py":
                "reg.counter('mlt_fix_total', labels={'kind': 'a'})\n",
            "docs/guide/fix.md": (
                "| metric | type | meaning |\n|---|---|---|\n"
                "| `mlt_fix_total{kind}` | counter | fine |\n"),
        },
    ),
    "wire-health": (
        {
            "megatron_llm_tpu/server.py": (
                "class MegatronServer:\n"
                "    def health(self):\n"
                "        info = {'status': 'ok', 'extra': 1}\n"
                "        return info\n"),
            "megatron_llm_tpu/router.py": (
                "class ReplicaView:\n"
                "    @staticmethod\n"
                "    def parse(url, payload):\n"
                "        return (payload.get('status'),\n"
                "                payload.get('ghost'))\n"),
            "docs/guide/serving.md": (
                "### The /health payload\n\n"
                "| field | meaning |\n|---|---|\n"
                "| `status` | liveness |\n"
                "| `phantom` | stale row |\n"),
        },
        {
            "megatron_llm_tpu/server.py": (
                "class MegatronServer:\n"
                "    def health(self):\n"
                "        info = {'status': 'ok'}\n"
                "        return info\n"),
            "megatron_llm_tpu/router.py": (
                "class ReplicaView:\n"
                "    @staticmethod\n"
                "    def parse(url, payload):\n"
                "        return payload.get('status')\n"),
            "docs/guide/serving.md": (
                "### The /health payload\n\n"
                "| field | meaning |\n|---|---|\n"
                "| `status` | liveness |\n"),
        },
    ),
    "wire-flags": (
        {
            "pkg/arguments.py": (
                "from dataclasses import dataclass\n"
                "@dataclass\n"
                "class InferenceConfig:\n"
                "    undocumented_knob: int = 0\n"),
            "docs/guide/g.md": (
                "| knob | default |\n|---|---|\n"
                "| `--ghost_flag` | 0 |\n"),
        },
        {
            "pkg/arguments.py": (
                "from dataclasses import dataclass\n"
                "@dataclass\n"
                "class InferenceConfig:\n"
                "    real_knob: int = 0\n"),
            "docs/guide/g.md": (
                "| knob | default |\n|---|---|\n"
                "| `--real_knob` | 0 |\n"),
        },
    ),
}


def project_run(tmp_path, files, **kw):
    """Write a multi-file fixture under tmp_path and run the full
    two-pass analyzer over it (root = the fixture dir, no baseline)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    kw.setdefault("baseline_path", None)
    return core.run([str(tmp_path)], root=str(tmp_path), **kw)


@pytest.mark.parametrize("rule_id", sorted(PROJECT_FIXTURES))
def test_project_rule_positive(rule_id, tmp_path):
    pos, _neg = PROJECT_FIXTURES[rule_id]
    res = project_run(tmp_path, pos)
    hits = [f for f in res.findings
            if f.rule == rule_id and f.severity == "error"]
    assert hits, f"{rule_id}: positive fixture produced no error finding"


@pytest.mark.parametrize("rule_id", sorted(PROJECT_FIXTURES))
def test_project_rule_negative(rule_id, tmp_path):
    _pos, neg = PROJECT_FIXTURES[rule_id]
    res = project_run(tmp_path, neg)
    hits = [f for f in res.findings
            if f.rule == rule_id and f.severity == "error"]
    assert not hits, f"{rule_id}: negative fixture flagged: " + \
        "\n".join(f.text() for f in hits)


def test_lockorder_cycle_fixture_details(tmp_path):
    """The seeded two-class cycle is reported as ONE deadlock finding
    naming both lock nodes, and the artifact exposes the cycle."""
    pos, _ = PROJECT_FIXTURES["lock-order"]
    res = project_run(tmp_path, pos)
    hits = [f for f in res.findings if f.rule == "lock-order"]
    assert len(hits) == 1
    assert "deadlock" in hits[0].message
    assert "Engine._lock" in hits[0].message
    assert "Recorder._lock" in hits[0].message
    lo = res.artifacts["lockorder"]
    assert lo["cycles"] == [["Engine._lock", "Recorder._lock"]]
    assert lo["order"] == []  # no topological order through a cycle


def test_lockorder_negative_fixture_has_order(tmp_path):
    _pos, neg = PROJECT_FIXTURES["lock-order"]
    res = project_run(tmp_path, neg)
    lo = res.artifacts["lockorder"]
    assert lo["cycles"] == []
    # the one-way nesting is discovered and ordered
    assert ("Engine._lock", "Recorder._lock") in {
        (e["from"], e["to"]) for e in lo["edges"]}
    assert lo["order"].index("Engine._lock") \
        < lo["order"].index("Recorder._lock")


def test_health_severities(tmp_path):
    """parsed-but-never-produced is an ERROR (the router routes on a
    default); produced-but-never-parsed is INFO (operator-facing)."""
    pos, _ = PROJECT_FIXTURES["wire-health"]
    res = project_run(tmp_path, pos)
    by_msg = {(f.severity, "ghost" in f.message, "extra" in f.message)
              for f in res.findings if f.rule == "wire-health"}
    assert ("error", True, False) in by_msg, "parsed-not-produced"
    assert any(sev == "info" and extra
               for sev, _g, extra in by_msg), "produced-not-parsed"
    # doc-table drift both ways
    msgs = [f.message for f in res.findings if f.rule == "wire-health"
            and f.severity == "error"]
    assert any("phantom" in m for m in msgs), "stale schema row"
    assert any("missing from" in m and "'extra'" in m for m in msgs), \
        "undocumented produced field"


def test_metrics_label_mismatch_fixture(tmp_path):
    pos, _ = PROJECT_FIXTURES["wire-metrics"]
    res = project_run(tmp_path, pos)
    msgs = [f.message for f in res.findings if f.rule == "wire-metrics"]
    assert any("label" in m and "mlt_fix_labeled_total" in m
               for m in msgs), msgs
    assert any("mlt_fix_ghost_total" in m for m in msgs)
    assert any("mlt_fix_undocumented_total" in m for m in msgs)


def test_project_rule_noqa_suppression(tmp_path):
    """A pass-2 finding anchored in a .py file honors the same noqa
    grammar as pass-1 findings."""
    pos, _ = PROJECT_FIXTURES["wire-health"]
    files = dict(pos)
    files["megatron_llm_tpu/router.py"] = (
        "class ReplicaView:\n"
        "    @staticmethod\n"
        "    def parse(url, payload):\n"
        "        return (payload.get('status'),\n"
        "                payload.get('ghost'))"
        "  # graftcheck: noqa[wire-health] — fixture\n")
    res = project_run(tmp_path, files)
    assert not [f for f in res.findings
                if f.rule == "wire-health" and "ghost" in f.message]


def test_project_rule_baseline_absorbs(tmp_path):
    """Baseline entries absorb pass-2 findings too (same key grammar),
    including ones anchored in markdown files."""
    pos, _ = PROJECT_FIXTURES["wire-health"]
    res = project_run(tmp_path, pos)
    errors = [f for f in res.findings
              if f.rule == "wire-health" and f.severity == "error"]
    assert errors
    entries = []
    for f in errors:
        text = (tmp_path / f.path).read_text().splitlines()[f.line - 1]
        entries.append({"path": f.path, "rule": f.rule,
                        "line": text.strip(), "reason": "fixture",
                        "count": 9})
    bl = tmp_path / "baseline.json"
    core.save_baseline(str(bl), entries)
    res2 = project_run(tmp_path, pos, baseline_path=str(bl))
    left = [f for f in res2.findings
            if f.rule == "wire-health" and f.severity == "error"
            and not f.baselined]
    assert not left, left


def test_stale_baseline_distinguishes_renamed_rule(tmp_path):
    """A baseline entry orphaned by a rule rename reads 'unknown-rule';
    one whose code was fixed reads 'unmatched' — the regression pinned
    by ISSUE 14's small-fix satellite."""
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    bl = tmp_path / "baseline.json"
    core.save_baseline(str(bl), [
        {"path": "clean.py", "rule": "old-rule-name",
         "line": "x = 1", "reason": "r"},
        {"path": "clean.py", "rule": "todo-owner",
         "line": "x = 1  # TODO fix", "reason": "r"},
    ])
    res = core.run([str(f)], root=str(tmp_path), baseline_path=str(bl))
    kinds = {(e["rule"], e["stale_kind"]) for e in res.stale_baseline}
    assert ("old-rule-name", "unknown-rule") in kinds
    assert ("todo-owner", "unmatched") in kinds


def test_changed_only_scopes_pass1_not_pass2(tmp_path):
    """--changed-only: per-file findings only for changed files, but the
    cross-file analyses still see the WHOLE project through the fact
    cache; stale-baseline detection is off (absence proves nothing)."""
    pos, _ = PROJECT_FIXTURES["wire-health"]
    files = dict(pos)
    files["megatron_llm_tpu/todo.py"] = "x = 1  # TODO fix\n"
    cache = tmp_path / "cache.json"
    full = project_run(tmp_path, files, fact_cache_path=str(cache))
    assert any(f.rule == "todo-owner" for f in full.findings)
    assert any(f.rule == "wire-health" for f in full.findings)
    assert cache.exists()

    res = core.run([str(tmp_path)], root=str(tmp_path),
                   baseline_path=None, changed_files=[],
                   fact_cache_path=str(cache))
    assert res.changed_only
    assert not [f for f in res.findings if f.rule == "todo-owner"]
    assert [f for f in res.findings if f.rule == "wire-health"]
    assert res.stale_baseline == []

    res2 = core.run([str(tmp_path)], root=str(tmp_path),
                    baseline_path=None,
                    changed_files=["megatron_llm_tpu/todo.py"],
                    fact_cache_path=str(cache))
    assert [f for f in res2.findings if f.rule == "todo-owner"]
    assert [f for f in res2.findings if f.rule == "wire-health"]


def test_changed_only_cache_invalidates_on_content(tmp_path):
    """The cache-invalidation rule: entries are keyed by content sha256,
    so a file that changed WITHOUT being reported as changed is still
    re-collected — the cache can go stale, the analysis cannot."""
    pos, _ = PROJECT_FIXTURES["wire-health"]
    cache = tmp_path / "cache.json"
    project_run(tmp_path, pos, fact_cache_path=str(cache))
    # the producer starts emitting 'ghost' — but we *lie* and report
    # nothing changed; the sha mismatch must recollect anyway
    (tmp_path / "megatron_llm_tpu/server.py").write_text(
        "class MegatronServer:\n"
        "    def health(self):\n"
        "        info = {'status': 'ok', 'extra': 1, 'ghost': 2}\n"
        "        return info\n")
    res = core.run([str(tmp_path)], root=str(tmp_path),
                   baseline_path=None, changed_files=[],
                   fact_cache_path=str(cache))
    # the parsed-but-never-produced error is gone (facts recollected);
    # the new 'ghost missing from the schema table' finding replaces it
    assert not [f for f in res.findings
                if f.rule == "wire-health" and "ghost" in f.message
                and "parsed by ReplicaView" in f.message]
    assert [f for f in res.findings
            if f.rule == "wire-health" and "ghost" in f.message
            and "missing from" in f.message]


# ---------------------------------------------------------------------------
# (f) the full-repo sweep — tier-1 gate (+ anti-vacuity pins)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def repo_sweep():
    """ONE full two-pass sweep shared by the gate + anti-vacuity tests."""
    targets = [os.path.join(REPO, t)
               for t in ("megatron_llm_tpu", "tools", "tasks", "tests")]
    return core.run(targets, root=REPO)


def test_repo_sweep_clean(repo_sweep):
    """`python -m tools.graftcheck megatron_llm_tpu tools tasks tests`
    on this tree: zero non-baselined error findings, inside the 30 s
    budget, with the full two-pass rule set."""
    assert len(ALL_RULES) >= 7
    ported = {"todo-owner", "obs-no-sync", "no-direct-shard-map"}
    new = {"sync-in-jit", "lock-discipline", "rng-key-reuse",
           "recompile-hazard"}
    project = {"lock-order", "wire-metrics", "wire-health", "wire-flags"}
    assert ported | new | project <= set(RULES_BY_ID)
    result = repo_sweep
    active = result.active
    assert not active, "new findings (fix, noqa with a reason, or " \
        "baseline with a reason):\n" + "\n".join(f.text() for f in active)
    assert not result.stale_baseline, (
        "baseline entries whose code was fixed — delete them: "
        f"{result.stale_baseline}")
    assert result.seconds < 30, f"sweep took {result.seconds:.1f}s"
    assert result.files > 150  # really swept the tree


def test_lock_graph_engine_recorder_edge(repo_sweep):
    """Anti-vacuity: the PR 12 engine→recorder ordering is ANALYZED —
    the edge must exist in the derived graph, the graph must be
    cycle-free with a total order, and the shared-lock annotation must
    have merged every RequestRecord node into the recorder's."""
    lo = repo_sweep.artifacts["lockorder"]
    edges = {(e["from"], e["to"]) for e in lo["edges"]}
    assert ("ContinuousBatchingEngine._lock",
            "FlightRecorder._lock") in edges
    assert lo["cycles"] == []
    assert lo["order"], "acyclic graph must have a topological order"
    assert len(lo["nodes"]) >= 15, "lock model shrank — extraction bug?"
    assert not any("RequestRecord" in n["id"] for n in lo["nodes"])
    rec = next(n for n in lo["nodes"]
               if n["id"] == "FlightRecorder._lock")
    assert "RequestRecord._lock" in rec["aliases"]
    # engine _work is the Condition alias of _lock, merged
    eng = next(n for n in lo["nodes"]
               if n["id"] == "ContinuousBatchingEngine._lock")
    assert "ContinuousBatchingEngine._work" in eng["aliases"]


def test_lockorder_committed_evidence(repo_sweep):
    """tools/graftcheck/lockorder.json is reviewed evidence (like the
    BENCH files): it must equal the graph derived from THIS tree."""
    with open(os.path.join(REPO, "tools", "graftcheck",
                           "lockorder.json")) as f:
        committed = json.load(f)
    assert committed == repo_sweep.artifacts["lockorder"], (
        "lock graph drifted from the committed evidence — regenerate: "
        "python -m tools.graftcheck --lockorder-out "
        "tools/graftcheck/lockorder.json megatron_llm_tpu tools tasks "
        "tests")
    # an example names ``file:Class.method``, never a line: a PR that
    # moves a line of engine.py does not carry this file
    assert not [x for e in committed["edges"] for x in e["examples"]
                if x.rpartition(":")[2].isdigit()]


def test_contract_extractors_not_vacuous(repo_sweep):
    """An extraction regression must not pass as '0 findings': the
    sweep must actually SEE the repo's metric registrations, /health
    producer/consumer keys, and flag surfaces."""
    m = repo_sweep.artifacts["wire-metrics"]
    assert m["registered"] >= 60, m
    assert m["documented"] >= 55, m
    h = repo_sweep.artifacts["wire-health"]
    assert h["produced"] >= 35, h
    assert h["consumed"] >= 20, h
    assert h["documented"] >= 20, h
    fl = repo_sweep.artifacts["wire-flags"]
    assert fl["inference_fields"] >= 20, fl
    assert fl["code_flags"] >= 250, fl
    assert fl["doc_flags"] >= 80, fl


def test_baseline_entries_all_explained():
    """Zero unexplained entries: every committed baseline entry carries
    a nonempty human reason."""
    entries = core.load_baseline(core.BASELINE_DEFAULT)
    unexplained = [e for e in entries if not e.get("reason", "").strip()]
    assert not unexplained, unexplained


def test_lock_rule_verifies_engine_annotations():
    """The engine's 20-attribute lock model really is loaded (an empty
    model would make the repo sweep vacuously clean)."""
    import ast as ast_mod

    from tools.graftcheck.rules.locks import LockDisciplineRule

    path = os.path.join(REPO, "megatron_llm_tpu", "generation",
                        "engine.py")
    ctx = core.FileContext(path)
    rule = LockDisciplineRule()
    for node in ast_mod.walk(ctx.tree):
        if isinstance(node, ast_mod.ClassDef) \
                and node.name == "ContinuousBatchingEngine":
            model = rule._build(ctx, node)
            assert model is not None
            assert {"_queue", "_slots", "_classes",
                    "_stopping"} <= set(model.guards)
            assert "_retire" in model.holds
            assert "_work" in model.groups.get("_lock", set())
            return
    raise AssertionError("engine class not found")


def test_lock_rule_verifies_router_annotations():
    """ISSUE 10: the router's cross-thread state (breaker fields on
    Replica, the fleet dict on ReplicaRegistry, the server's /health seq
    counter) is lock-annotated and really modeled by the rule — the repo
    sweep's cleanliness over serving/router/ is not vacuous."""
    import ast as ast_mod

    from tools.graftcheck.rules.locks import LockDisciplineRule

    rule = LockDisciplineRule()
    expected = {
        os.path.join(REPO, "megatron_llm_tpu", "serving", "router",
                     "registry.py"): {
            "Replica": ({"_state", "_failures", "_view", "_draining"},
                        {"_advance_failure_locked"}),
            "ReplicaRegistry": ({"_replicas"}, set()),
        },
        os.path.join(REPO, "megatron_llm_tpu", "generation",
                     "server.py"): {
            "MegatronServer": ({"_health_seq"}, set()),
        },
    }
    for path, classes in expected.items():
        ctx = core.FileContext(path)
        found = set()
        for node in ast_mod.walk(ctx.tree):
            if isinstance(node, ast_mod.ClassDef) and node.name in classes:
                guards, holds = classes[node.name]
                model = rule._build(ctx, node)
                assert model is not None, f"{node.name}: no lock model"
                assert guards <= set(model.guards), (
                    f"{node.name} missing guards: "
                    f"{guards - set(model.guards)}")
                assert holds <= set(model.holds)
                found.add(node.name)
        assert found == set(classes), f"{path}: missing {set(classes) - found}"


def test_traced_functions_really_analyzed():
    """sync-in-jit resolves the engine's cached_jit builders — the
    programs built in engine.py itself are in the analyzed set (a
    resolution regression would silently stop checking the hot path; the
    tick's body lives in ragged.py and is reached across files, next)."""
    from tools.graftcheck.rules.sync import SyncInJitRule

    path = os.path.join(REPO, "megatron_llm_tpu", "generation",
                        "engine.py")
    ctx = core.FileContext(path)
    names = {getattr(n, "name", "<lambda>")
             for n in SyncInJitRule()._traced_nodes(ctx)}
    assert {"chunk", "chunk_spec", "copy", "copy_spec"} <= names


def test_ragged_builder_in_traced_set():
    """The builder-factory convention (module-level ``make_*_fn``)
    reaches the ragged tick's bodies, which live in ragged.py behind a
    cross-module thunk the per-file resolver cannot follow — the compiled
    tick really is sync-analyzed."""
    from tools.graftcheck.rules.sync import SyncInJitRule

    path = os.path.join(REPO, "megatron_llm_tpu", "generation",
                        "ragged.py")
    ctx = core.FileContext(path)
    names = {getattr(n, "name", "<lambda>")
             for n in SyncInJitRule()._traced_nodes(ctx)}
    assert {"tick", "spec_tick", "draft_step", "target_forward"} <= names, \
        names
    # the factory body itself runs at build time (host side) — exempt
    assert "make_ragged_tick_fn" not in names


def test_builder_factory_sync_flagged():
    """A tick builder hiding a host sync inside the compiled body is a
    finding; a jax-free host-side factory (REST client shape) is not
    traced at all."""
    bad = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def make_bad_tick_fn(cfg):\n"
        "    def tick(x):\n"
        "        return np.asarray(x) + jnp.ones(())\n"
        "    return tick\n"
    )
    hits = [f for f in core.check_file("fixture.py", ALL_RULES, source=bad)
            if f.rule == "sync-in-jit"]
    assert len(hits) == 1 and hits[0].line == 6, hits
    host = (
        "import requests\n"
        "def make_api_generate_fn(url):\n"
        "    def fn(text):\n"
        "        return float(requests.get(url).elapsed.total_seconds())\n"
        "    return fn\n"
    )
    assert not [f for f in core.check_file("fixture.py", ALL_RULES,
                                           source=host)
                if f.rule == "sync-in-jit"]
