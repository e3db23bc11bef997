"""graftcheck core: file model, rule protocol, baseline, runner, CLI.

The analyzer exists because this repo's expensive failures are *static*
properties: a shard_map spelled outside the one module that owns the
convention (parallel/compat.py); a compiled-program cache keyed on ``id()`` serves a stale
executable after GC recycles the id (PR 1); an instrument that syncs the
device destroys the PR-2/PR-4 overlap it measures; and unguarded shared
state races exactly once a quarter, in production.  A regex line scanner
(the old tools/linter.py) cannot see scope — it flagged spellings inside
docstrings and missed aliased calls — so every rule here works on the
``ast`` module's view of the file (stdlib only, no third-party deps).

Since ISSUE 14 the analyzer is TWO-PASS: per-file rules run as before,
and *project rules* collect per-file facts in pass 1 (JSON-serializable,
cacheable) and run cross-file analyses in pass 2 over the whole target
set — the lock-acquisition graph (rules/lockorder.py) and the
wire-contract checks (rules/contracts.py) live there, because no single
file contains a lock *order* or a producer/consumer pair.

Vocabulary:

* **Finding** — one (path, line, rule, message) diagnostic, with a
  ``severity``: ``error`` findings gate the exit code, ``info`` findings
  are advisory (by-design asymmetries like a /health field produced for
  operators but not parsed by the router) and never fail a run.
* **Rule** — a class with an ``id``, a one-line ``summary``, and
  ``check(ctx)`` yielding findings for one file.
* **ProjectRule** — additionally implements ``collect(ctx)`` (pass 1,
  returns JSON-serializable facts) and ``finalize(project)`` (pass 2,
  yields findings computed over every file's facts).
* **Suppression** — ``# graftcheck: noqa[rule-id]`` on the offending
  line (with a reason after it, by convention).  Bare
  ``# graftcheck: noqa`` suppresses every rule on that line.
* **Baseline** — ``tools/graftcheck/baseline.json``: grandfathered
  findings keyed by (path, rule, stripped source line) so they survive
  line-number drift.  Baselined findings don't fail the run; every entry
  carries a human reason.

Exit codes (the tools/resilience_smoke.py convention, so a caller can
tell an analyzer crash from real findings):

* 0 — clean (no findings outside the baseline)
* 1 — findings
* 2 — internal error (a rule crashed, bad arguments, unreadable file)

Usage::

    python -m tools.graftcheck megatron_llm_tpu tools tasks tests
    python -m tools.graftcheck --json <targets>
    python -m tools.graftcheck --update-baseline <targets>
    python -m tools.graftcheck --changed-only <targets>   # pre-commit
    python -m tools.graftcheck --lockorder-out tools/graftcheck/lockorder.json <targets>
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import subprocess
import sys
import time
import tokenize
import traceback
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set

BASELINE_DEFAULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")
FACT_CACHE_DEFAULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  ".factcache.json")
LOCKORDER_DEFAULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "lockorder.json")

#: Bump when the fact schema of any project rule changes shape — a
#: version mismatch discards the whole cache (the invalidation rule,
#: with the per-file sha256, documented in docs/guide/static-analysis.md).
FACTS_VERSION = 1

_NOQA_RE = re.compile(r"graftcheck:\s*noqa(?:\[([^\]]*)\])?")


@dataclasses.dataclass
class Finding:
    """One diagnostic.  ``path`` is the path as reported (relative to the
    invocation root when possible), ``line`` 1-based.  ``severity`` is
    ``"error"`` (gates the exit code) or ``"info"`` (advisory)."""

    path: str
    line: int
    col: int
    rule: str
    message: str
    baselined: bool = False
    severity: str = "error"

    def text(self) -> str:
        sev = "" if self.severity == "error" else f" {self.severity}:"
        return f"{self.path}:{self.line}: [{self.rule}]{sev} {self.message}"

    def json_obj(self) -> Dict:
        return {"path": self.path, "line": self.line, "col": self.col,
                "rule": self.rule, "message": self.message,
                "baselined": self.baselined, "severity": self.severity}


def qualname(node: ast.AST) -> Optional[str]:
    """Dotted name of a Name/Attribute chain ('jax.random.split'), else
    None — the single spelling-resolution helper every rule shares."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class FileContext:
    """Everything a rule needs about one file, computed once: source
    lines, the AST (or the syntax error), per-line comments (the ast
    module drops them — ``tokenize`` recovers them for the annotation
    grammars), per-line noqa sets, and a child->parent node map."""

    def __init__(self, path: str, source: Optional[str] = None,
                 relpath: Optional[str] = None):
        self.path = path
        self.relpath = relpath if relpath is not None else path
        if source is None:
            with open(path, encoding="utf-8", errors="replace") as f:
                source = f.read()
        self.source = source
        self.lines: List[str] = source.splitlines()
        self.syntax_error: Optional[SyntaxError] = None
        try:
            self.tree: Optional[ast.AST] = ast.parse(source)
        except SyntaxError as e:
            self.tree = None
            self.syntax_error = e
        # line -> comment text (without the leading '#', stripped)
        self.comments: Dict[int, str] = {}
        # line -> None (suppress all) or set of rule ids
        self.noqa: Dict[int, Optional[Set[str]]] = {}
        self._scan_comments()
        self._parents: Dict[ast.AST, ast.AST] = {}
        if self.tree is not None:
            for parent in ast.walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self._parents[child] = parent

    def _scan_comments(self) -> None:
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.source).readline)
            for tok in toks:
                if tok.type == tokenize.COMMENT:
                    text = tok.string.lstrip("#").strip()
                    line = tok.start[0]
                    # keep the first comment on a line (inline ones)
                    self.comments.setdefault(line, text)
                    m = _NOQA_RE.search(tok.string)
                    if m:
                        if m.group(1) is None:
                            self.noqa[line] = None  # suppress every rule
                        elif not (line in self.noqa
                                  and self.noqa[line] is None):
                            ids = {r.strip() for r in m.group(1).split(",")
                                   if r.strip()}
                            self.noqa[line] = \
                                (self.noqa.get(line) or set()) | ids
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass  # comments stay partial; AST rules still run if it parsed

    def comment_on(self, line: int) -> str:
        return self.comments.get(line, "")

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def ancestors(self, node: ast.AST) -> Iterator[ast.AST]:
        cur = self._parents.get(node)
        while cur is not None:
            yield cur
            cur = self._parents.get(cur)

    def suppressed(self, line: int, rule_id: str) -> bool:
        if line not in self.noqa:
            return False
        ids = self.noqa[line]
        return ids is None or rule_id in ids

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1]
        return ""


class Rule:
    """Base class: subclasses set ``id`` + ``summary`` and implement
    ``check``.  ``summary`` is the one-liner shown by ``--list-rules``;
    the *why* lives in docs/guide/static-analysis.md."""

    id: str = ""
    summary: str = ""

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, ctx: FileContext, node, message: str) -> Finding:
        if isinstance(node, int):
            line, col = node, 0
        else:
            line, col = node.lineno, getattr(node, "col_offset", 0)
        return Finding(path=ctx.relpath, line=line, col=col,
                       rule=self.id, message=message)


class ProjectContext:
    """Pass-2 state: every analyzed file's facts, keyed by rule id then
    relpath, plus the invocation root (project rules resolve docs and
    artifacts against it).  Facts are plain JSON values so
    ``--changed-only`` can cache them between runs."""

    def __init__(self, root: str, complete: bool = True):
        self.root = root
        # rule id -> relpath -> facts (JSON-serializable)
        self.facts: Dict[str, Dict[str, object]] = {}
        self.py_files: List[str] = []     # relpaths, analysis order
        # finalize() outputs worth persisting (the lock graph)
        self.artifacts: Dict[str, object] = {}
        # True when the target set plausibly covers the whole code
        # surface (the root itself, or the megatron_llm_tpu package
        # dir).  Absence-style checks ("documented but registered
        # nowhere") must consult this: a single-file run proves nothing
        # about what exists elsewhere.
        self.complete = complete

    def add_facts(self, rule_id: str, relpath: str, facts) -> None:
        if facts:
            self.facts.setdefault(rule_id, {})[relpath] = facts

    def facts_for(self, rule_id: str) -> Dict[str, object]:
        return self.facts.get(rule_id, {})

    def doc_paths(self) -> List[str]:
        """Relpaths of every docs/guide/*.md under the root (the contract
        rules' documentation side).  Docs are never fact-cached — pass 2
        reads them fresh each run."""
        doc_dir = os.path.join(self.root, "docs", "guide")
        if not os.path.isdir(doc_dir):
            return []
        return sorted(
            os.path.join("docs", "guide", n)
            for n in os.listdir(doc_dir) if n.endswith(".md"))

    def read_text(self, relpath: str) -> str:
        with open(os.path.join(self.root, relpath), encoding="utf-8",
                  errors="replace") as f:
            return f.read()


class ProjectRule(Rule):
    """Cross-file rule: ``collect(ctx)`` gathers one file's facts in
    pass 1 (must return a JSON-serializable value, or None for "nothing
    here" — facts are cached by content hash for ``--changed-only``);
    ``finalize(project)`` yields findings over the whole project in
    pass 2.  ``check`` is intentionally a no-op: a project rule has
    nothing to say about one file in isolation."""

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        return ()

    def collect(self, ctx: FileContext):
        raise NotImplementedError

    def finalize(self, project: ProjectContext) -> Iterable[Finding]:
        raise NotImplementedError

    def project_finding(self, relpath: str, line: int, message: str,
                        severity: str = "error") -> Finding:
        return Finding(path=relpath.replace(os.sep, "/"), line=line, col=0,
                       rule=self.id, message=message, severity=severity)


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------


def _baseline_key(path: str, rule: str, line_text: str):
    return (path.replace(os.sep, "/"), rule, line_text.strip())


def load_baseline(path: str) -> List[Dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return list(doc.get("entries", []))


def save_baseline(path: str, entries: List[Dict]) -> None:
    entries = sorted(entries, key=lambda e: (e["path"], e["rule"], e["line"]))
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=2,
                  sort_keys=True)
        f.write("\n")


def apply_baseline(findings: List[Finding], entries: List[Dict],
                   line_text_of,
                   known_rules: Optional[Set[str]] = None) -> List[Dict]:
    """Mark findings that match a baseline entry (by path + rule +
    stripped source line; each entry absorbs up to ``count`` findings,
    default 1).  Returns the STALE entries — present in the baseline but
    matching nothing.  Each returned entry carries a ``stale_kind``:
    ``"unknown-rule"`` when the entry's rule id is not in the active rule
    set (a rule was renamed or removed — re-key the entry), else
    ``"unmatched"`` (the underlying code was fixed — delete the entry).
    The distinction matters: without it a rule rename silently orphans
    its whole baseline and reads as "all fixed"."""
    remaining: Dict[tuple, int] = {}
    for e in entries:
        key = _baseline_key(e["path"], e["rule"], e["line"])
        remaining[key] = remaining.get(key, 0) + int(e.get("count", 1))
    for f in findings:
        key = _baseline_key(f.path, f.rule, line_text_of(f))
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
            f.baselined = True
    stale = []
    for e in entries:
        key = _baseline_key(e["path"], e["rule"], e["line"])
        if remaining.get(key, 0) > 0:
            remaining[key] = 0
            stale_e = dict(e)
            stale_e["stale_kind"] = (
                "unknown-rule" if known_rules is not None
                and e["rule"] not in known_rules else "unmatched")
            stale.append(stale_e)
    return stale


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

_SKIP_DIRS = {"__pycache__", ".git", ".claude", "node_modules"}


def iter_py_files(targets: Sequence[str]) -> Iterator[str]:
    for target in targets:
        if os.path.isfile(target):
            yield target
            continue
        if not os.path.isdir(target):
            # a typo'd target silently reporting "clean" would be the
            # worst kind of green CI — fail loudly (exit 2 via main)
            raise FileNotFoundError(f"target does not exist: {target}")
        for root, dirs, files in os.walk(target):
            dirs[:] = sorted(d for d in dirs if d not in _SKIP_DIRS
                             and not d.startswith("."))
            for name in sorted(files):
                if name.endswith(".py"):
                    yield os.path.join(root, name)


class RuleCrash(Exception):
    """A rule blew up on a file: the run is unsound, exit 2 — the watch
    predicate must see 'analyzer broken', not 'repo clean'."""


def _relpath_under(path: str, root: Optional[str]) -> str:
    if root is None:
        return path
    try:
        rel = os.path.relpath(path, root)
        if not rel.startswith(".."):
            return rel
    except ValueError:
        pass
    return path


def check_file(path: str, rules: Sequence[Rule], root: Optional[str] = None,
               source: Optional[str] = None,
               project: Optional[ProjectContext] = None) -> List[Finding]:
    """All (unsuppressed) findings for one file.  Raises RuleCrash when a
    rule raises — callers decide whether that is fatal (CLI: exit 2).
    With ``project``, project rules in ``rules`` also run their pass-1
    ``collect`` on the same parsed context (facts land in ``project``)."""
    relpath = _relpath_under(path, root)
    ctx = FileContext(path, source=source, relpath=relpath)
    findings: List[Finding] = []
    if ctx.syntax_error is not None:
        findings.append(Finding(
            path=ctx.relpath, line=ctx.syntax_error.lineno or 1, col=0,
            rule="parse-error",
            message=f"file does not parse: {ctx.syntax_error.msg}"))
        return findings
    for rule in rules:
        try:
            for f in rule.check(ctx):
                if not ctx.suppressed(f.line, rule.id):
                    findings.append(f)
            if project is not None and isinstance(rule, ProjectRule):
                project.add_facts(rule.id, ctx.relpath.replace(os.sep, "/"),
                                  rule.collect(ctx))
        except Exception as e:
            raise RuleCrash(
                f"rule {rule.id!r} crashed on {path}: "
                f"{type(e).__name__}: {e}") from e
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings


def collect_facts(path: str, rules: Sequence["ProjectRule"],
                  root: Optional[str] = None,
                  source: Optional[str] = None) -> Dict[str, object]:
    """Pass-1 facts only (no per-file findings): rule id -> facts.  The
    cache-refill path of ``--changed-only``."""
    relpath = _relpath_under(path, root)
    ctx = FileContext(path, source=source, relpath=relpath)
    out: Dict[str, object] = {}
    if ctx.syntax_error is not None:
        return out
    for rule in rules:
        try:
            facts = rule.collect(ctx)
        except Exception as e:
            raise RuleCrash(
                f"rule {rule.id!r} crashed collecting {path}: "
                f"{type(e).__name__}: {e}") from e
        if facts:
            out[rule.id] = facts
    return out


# ---------------------------------------------------------------------------
# Fact cache (--changed-only)
# ---------------------------------------------------------------------------


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 16), b""):
            h.update(chunk)
    return h.hexdigest()


def _cache_fingerprint(project_rules: Sequence["ProjectRule"]) -> List:
    return [FACTS_VERSION, sorted(r.id for r in project_rules)]


def load_fact_cache(path: str,
                    project_rules: Sequence["ProjectRule"]) -> Dict:
    """Cached per-file facts, or {} when absent/stale.  Invalidation
    rule: the whole cache is dropped when FACTS_VERSION or the project
    rule set changed; a single entry is dropped when its file's sha256
    changed.  Docs are never cached (pass 2 re-reads them each run)."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if doc.get("fingerprint") != _cache_fingerprint(project_rules):
        return {}
    files = doc.get("files")
    return files if isinstance(files, dict) else {}


def save_fact_cache(path: str, files: Dict,
                    project_rules: Sequence["ProjectRule"]) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"fingerprint": _cache_fingerprint(project_rules),
                   "files": files}, f)
    os.replace(tmp, path)


def git_changed_files(root: str) -> Optional[List[str]]:
    """Paths (relative to ``root``) touched vs HEAD — staged, unstaged,
    and untracked.  None when git is unavailable (the CLI then falls
    back to a full run rather than guessing)."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=all"],
            cwd=root, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    changed = []
    for line in out.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:]
        if " -> " in path:  # rename: take the new side
            path = path.split(" -> ", 1)[1]
        changed.append(path.strip().strip('"'))
    return changed


@dataclasses.dataclass
class RunResult:
    findings: List[Finding]
    stale_baseline: List[Dict]
    files: int
    seconds: float
    rules: List[str]
    artifacts: Dict[str, object] = dataclasses.field(default_factory=dict)
    changed_only: bool = False

    @property
    def active(self) -> List[Finding]:
        """Unbaselined error-severity findings — the ones that gate."""
        return [f for f in self.findings
                if not f.baselined and f.severity == "error"]

    @property
    def info(self) -> List[Finding]:
        return [f for f in self.findings
                if not f.baselined and f.severity != "error"]

    @property
    def baselined(self) -> List[Finding]:
        return [f for f in self.findings if f.baselined]

    @property
    def exit_code(self) -> int:
        return 1 if self.active else 0

    def json_obj(self) -> Dict:
        return {
            "graftcheck": 1,
            "rules": self.rules,
            "files": self.files,
            "seconds": round(self.seconds, 3),
            "changed_only": self.changed_only,
            "findings": [f.json_obj() for f in self.findings],
            "stale_baseline": self.stale_baseline,
            "counts": {"total": len(self.findings),
                       "active": len(self.active),
                       "info": len(self.info),
                       "baselined": len(self.baselined),
                       "stale_baseline": len(self.stale_baseline)},
            "exit": self.exit_code,
        }


def run(targets: Sequence[str], rules: Optional[Sequence[Rule]] = None,
        baseline_path: Optional[str] = BASELINE_DEFAULT,
        root: Optional[str] = None,
        changed_files: Optional[Sequence[str]] = None,
        fact_cache_path: Optional[str] = None) -> RunResult:
    """Analyze ``targets`` (files or directories) and apply the baseline.
    The library entry point — the CLI, the linter shim, and the tier-1
    sweep test all come through here.

    Two passes: per-file rules + project-rule fact collection over each
    file, then project-rule ``finalize`` over the whole fact set.  With
    ``changed_files`` (relpaths under ``root``), pass-1 findings are
    computed only for those files, while pass-2 facts still cover the
    WHOLE project — unchanged files' facts come from ``fact_cache_path``
    (keyed by content sha256) or are collected on a cache miss, so the
    cross-file analyses never narrow.  Stale-baseline detection is
    skipped in changed-only mode (pass-1 findings are incomplete, so
    absence proves nothing)."""
    from tools.graftcheck.rules import DEFAULT_RULES

    rules = list(rules if rules is not None else DEFAULT_RULES)
    project_rules = [r for r in rules if isinstance(r, ProjectRule)]
    root = root if root is not None else os.getcwd()
    t0 = time.perf_counter()
    findings: List[Finding] = []
    line_texts: Dict[str, List[str]] = {}
    complete = False
    for t in targets:
        if os.path.isdir(t):
            rel = _relpath_under(os.path.abspath(t), root).replace(
                os.sep, "/")
            if rel in (".", "", "megatron_llm_tpu"):
                complete = True
    project = ProjectContext(root, complete=complete)
    changed_set = None
    if changed_files is not None:
        changed_set = {c.replace(os.sep, "/") for c in changed_files}
    cache = {}
    if fact_cache_path and project_rules:
        cache = load_fact_cache(fact_cache_path, project_rules)
    nfiles = 0
    for path in iter_py_files(targets):
        nfiles += 1
        relpath = _relpath_under(path, root).replace(os.sep, "/")
        project.py_files.append(relpath)
        is_changed = changed_set is None or relpath in changed_set
        if is_changed:
            fs = check_file(path, rules, root=root, project=project)
            if fs:
                with open(path, encoding="utf-8", errors="replace") as f:
                    line_texts[fs[0].path] = f.read().splitlines()
            findings.extend(fs)
            if fact_cache_path and project_rules:
                cache[relpath] = {
                    "sha256": _sha256_file(path),
                    "facts": {r.id: project.facts_for(r.id).get(relpath)
                              for r in project_rules
                              if project.facts_for(r.id).get(relpath)}}
        elif project_rules:
            # unchanged file: facts from the cache, collected on miss
            entry = cache.get(relpath)
            sha = _sha256_file(path)
            if entry is None or entry.get("sha256") != sha:
                entry = {"sha256": sha,
                         "facts": collect_facts(path, project_rules,
                                                root=root)}
                cache[relpath] = entry
            for rid, facts in (entry.get("facts") or {}).items():
                project.add_facts(rid, relpath, facts)

    # ---- pass 2: cross-file rules over the whole fact set ----
    ctx_cache: Dict[str, Optional[FileContext]] = {}

    def _suppressed(f: Finding) -> bool:
        if f.path not in ctx_cache:
            full = os.path.join(root, f.path)
            if f.path.endswith(".py") and os.path.exists(full):
                try:
                    ctx_cache[f.path] = FileContext(full, relpath=f.path)
                except OSError:
                    ctx_cache[f.path] = None
            else:
                ctx_cache[f.path] = None
        ctx = ctx_cache[f.path]
        return ctx is not None and ctx.suppressed(f.line, f.rule)

    for rule in project_rules:
        try:
            for f in rule.finalize(project):
                if not _suppressed(f):
                    findings.append(f)
        except Exception as e:
            raise RuleCrash(
                f"project rule {rule.id!r} crashed in finalize: "
                f"{type(e).__name__}: {e}") from e

    def line_text_of(f: Finding) -> str:
        if f.path not in line_texts:
            full = os.path.join(root, f.path)
            if os.path.exists(full):
                with open(full, encoding="utf-8", errors="replace") as fh:
                    line_texts[f.path] = fh.read().splitlines()
            else:
                line_texts[f.path] = []
        lines = line_texts.get(f.path, [])
        return lines[f.line - 1] if 1 <= f.line <= len(lines) else ""

    entries = load_baseline(baseline_path) if baseline_path else []
    known = {r.id for r in rules} | {"parse-error"}
    stale = apply_baseline(findings, entries, line_text_of, known_rules=known)
    if changed_set is not None:
        stale = []  # incomplete pass-1 findings can't prove staleness
    if fact_cache_path and project_rules:
        try:
            save_fact_cache(fact_cache_path, cache, project_rules)
        except OSError:
            pass  # a read-only checkout still analyzes fine
    return RunResult(findings=findings, stale_baseline=stale, files=nfiles,
                     seconds=time.perf_counter() - t0,
                     rules=sorted(r.id for r in rules),
                     artifacts=project.artifacts,
                     changed_only=changed_set is not None)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _update_baseline(result: RunResult, baseline_path: str,
                     line_text_of=None) -> int:
    """Rewrite the baseline from the current findings, keeping the
    hand-written reasons of entries that still match.  New entries get an
    empty reason — the committer must fill it in (the tier-1 test refuses
    a baseline with unexplained entries)."""
    old = {}
    for e in load_baseline(baseline_path):
        old[_baseline_key(e["path"], e["rule"], e["line"])] = \
            e.get("reason", "")
    counts: Dict[tuple, int] = {}
    for f in result.findings:
        text = f.line_source if hasattr(f, "line_source") else ""
        key = (f.path, f.rule, text)
        counts[key] = counts.get(key, 0) + 1
    entries = []
    for (path, rule, text), n in sorted(counts.items()):
        entry = {"path": path.replace(os.sep, "/"), "rule": rule,
                 "line": text,
                 "reason": old.get((path.replace(os.sep, "/"), rule,
                                    text.strip()), "")}
        if n > 1:
            entry["count"] = n
        entries.append(entry)
    save_baseline(baseline_path, entries)
    print(f"graftcheck: baseline updated: {len(entries)} entr"
          f"{'y' if len(entries) == 1 else 'ies'} -> {baseline_path}")
    missing = sum(1 for e in entries if not e["reason"])
    if missing:
        print(f"graftcheck: {missing} entr"
              f"{'y needs' if missing == 1 else 'ies need'} a reason "
              f"before committing")
    return 0


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="graftcheck",
        description="AST-based invariant analyzer "
                    "(docs/guide/static-analysis.md)")
    ap.add_argument("targets", nargs="*", default=["megatron_llm_tpu"],
                    help="files or directories (default: megatron_llm_tpu)")
    ap.add_argument("--json", action="store_true",
                    help="one-line JSON summary on stdout")
    ap.add_argument("--baseline", default=BASELINE_DEFAULT,
                    help="baseline file (default: tools/graftcheck/"
                         "baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report everything)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(preserves reasons of surviving entries)")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--changed-only", action="store_true",
                    help="pass-1 findings for git-changed files only; "
                         "pass-2 cross-file facts still cover the whole "
                         "project via the fact cache (fast pre-commit)")
    ap.add_argument("--fact-cache", default=FACT_CACHE_DEFAULT,
                    help="per-file fact cache for --changed-only "
                         "(default: tools/graftcheck/.factcache.json)")
    ap.add_argument("--lockorder-out", default=None, metavar="PATH",
                    help="write the discovered lock-acquisition graph "
                         "(nodes, edges, topological order) as JSON")
    ap.add_argument("--info", action="store_true",
                    help="also print info-severity (advisory) findings")
    ap.add_argument("--list-rules", action="store_true")
    return ap


def _main(argv: Optional[Sequence[str]]) -> int:
    from tools.graftcheck.rules import DEFAULT_RULES

    args = make_parser().parse_args(argv)
    if args.list_rules:
        for rule in DEFAULT_RULES:
            kind = "project" if isinstance(rule, ProjectRule) else "file"
            print(f"{rule.id:24s} [{kind:7s}] {rule.summary}")
        return 0
    rules = DEFAULT_RULES
    if args.select:
        wanted = {r.strip() for r in args.select.split(",") if r.strip()}
        known = {r.id for r in DEFAULT_RULES}
        unknown = wanted - known
        if unknown:
            print(f"graftcheck: unknown rule(s): {sorted(unknown)}; "
                  f"known: {sorted(known)}", file=sys.stderr)
            return 2
        rules = [r for r in DEFAULT_RULES if r.id in wanted]
    baseline = None if args.no_baseline else args.baseline
    changed = None
    if args.changed_only:
        changed = git_changed_files(os.getcwd())
        if changed is None:
            print("graftcheck: --changed-only needs git; running full",
                  file=sys.stderr)
    fact_cache = args.fact_cache if args.changed_only else None

    if args.update_baseline:
        # findings need their source line for stable keys
        result = run(args.targets, rules=rules, baseline_path=None)
        texts: Dict[str, List[str]] = {}
        for f in result.findings:
            if f.path not in texts:
                path = f.path if os.path.exists(f.path) else None
                if path is None:
                    texts[f.path] = []
                else:
                    with open(path, encoding="utf-8",
                              errors="replace") as fh:
                        texts[f.path] = fh.read().splitlines()
            lines = texts[f.path]
            f.line_source = (lines[f.line - 1].strip()
                             if 1 <= f.line <= len(lines) else "")
        return _update_baseline(result, args.baseline)

    result = run(args.targets, rules=rules, baseline_path=baseline,
                 changed_files=changed, fact_cache_path=fact_cache)
    if args.lockorder_out and "lockorder" in result.artifacts:
        with open(args.lockorder_out, "w", encoding="utf-8") as f:
            json.dump(result.artifacts["lockorder"], f, indent=2,
                      sort_keys=True)
            f.write("\n")
    if args.json:
        print(json.dumps(result.json_obj(), sort_keys=True))
    else:
        for f in result.active:
            print(f.text())
        if args.info:
            for f in result.info:
                print(f.text())
        for e in result.stale_baseline:
            if e.get("stale_kind") == "unknown-rule":
                print(f"graftcheck: stale baseline entry (rule id "
                      f"{e['rule']!r} no longer exists — renamed? re-key "
                      f"or delete it): {e['path']} [{e['rule']}] "
                      f"{e['line']!r}")
            else:
                print(f"graftcheck: stale baseline entry (code was fixed "
                      f"— delete it): {e['path']} [{e['rule']}] "
                      f"{e['line']!r}")
        n = len(result.active)
        mode = " (changed-only)" if result.changed_only else ""
        print(f"graftcheck: {n} finding(s) "
              f"({len(result.info)} info, {len(result.baselined)} "
              f"baselined) in {result.files} files{mode}, "
              f"{len(result.rules)} rules, {result.seconds:.1f}s")
    return result.exit_code


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI entry: 0 clean / 1 findings / 2 internal error."""
    try:
        return _main(argv)
    except SystemExit as e:  # argparse --help / usage errors
        code = e.code if isinstance(e.code, int) else 2
        return code
    except RuleCrash as e:
        print(f"graftcheck: internal error: {e}", file=sys.stderr)
        traceback.print_exc()
        return 2
    except Exception as e:  # noqa: BLE001 — exit-code contract
        print(f"graftcheck: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        traceback.print_exc()
        return 2
