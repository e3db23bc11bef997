"""lock-order: whole-repo lock-acquisition graph + deadlock cycles.

The per-file ``lock-discipline`` rule (rules/locks.py) proves every
guarded attribute is touched under its lock; what it cannot see is the
*order* locks nest in across objects — and a cycle in that order is a
deadlock waiting for the right interleaving.  The PR 12 engine→recorder
ordering ("the engine calls into the recorder while holding its own
lock; the recorder never calls back out") was asserted only by a module
docstring and a test comment.  This rule *derives* it, repo-wide:

Pass 1 (``collect``) models every class that touches a lock:

* **lock attributes** — ``# guarded by`` lock names, Condition alias
  members, ``threading.Lock/RLock/Condition`` assignments in
  ``__init__``, and any ``with self.<attr>:`` subject;
* **aliases** — ``threading.Condition(self._lock)`` makes the two names
  one lock (same grammar as rules/locks.py); the new cross-class
  annotation ``# shared lock: Class._attr`` on an ``__init__``
  assignment merges a lock *handed in* from another object (the
  FlightRecorder hands its lock to every RequestRecord it issues);
* **attribute types** — ``self.x = ClassName(...)`` in ``__init__``, or
  the new ``# instance of ClassName`` annotation when the constructor
  call is not visible (``MegatronServer.engine``), so
  ``self.x.method()`` and ``with self.x._lock:`` resolve;
* **per-method events** — in source order, each lock acquisition and
  each method call, with the set of locks lexically held there
  (enclosing ``with`` items + the method's ``# holds`` annotation).

Pass 2 (``finalize``) resolves calls into a bounded call graph
(``self.m()`` exactly; ``self.x.m()`` / ``v = self.x; v.m()`` via
attribute types; otherwise by method name when exactly ONE lock-relevant
class defines it — ambiguous names and a stoplist of generic verbs
resolve to nothing), computes each method's transitive acquisition set
to a fixed point, and emits the edge ``A -> B`` wherever ``B`` is
acquired (directly or via a call) while ``A`` is held.  Any strongly
connected component with more than one node is a potential deadlock and
is reported as an ``error`` finding.  The full graph — nodes, edges
with example sites (``file:Class.method``), and the topological order when
acyclic — is exposed
as the ``lockorder`` artifact (``--lockorder-out``, committed as
``tools/graftcheck/lockorder.json`` evidence).

Known under-approximations (documented, deliberate): acquisitions
through module-level indirection (``with trace.span(...)`` —  a call,
not an attribute), untyped receivers, and ambiguous method names
generate no edges.  Missing edges can hide a deadlock; they never
invent one — the rule errs loud on cycles, quiet on coverage, and the
anti-vacuity tests pin the edges that must exist.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.graftcheck.core import (
    FileContext,
    Finding,
    ProjectContext,
    ProjectRule,
    qualname,
)
from tools.graftcheck.rules.locks import (
    _GUARDED_RE,
    _HOLDS_RE,
    _lock_names,
    _self_attr,
)

_SHARED_RE = re.compile(r"shared lock:\s*([A-Za-z_]\w*)\.([A-Za-z_]\w*)")
_INSTANCE_RE = re.compile(r"instance of\s+([A-Za-z_]\w*)")

#: Generic verbs never resolved by bare name — ``self._stop.set()``
#: must not resolve to ``GaugeMetric.set``.  Typed receivers
#: (``self.x.set()`` with a known attribute type) still resolve.
_FALLBACK_STOPLIST = {
    "acquire", "add", "append", "clear", "close", "extend", "flush",
    "get", "is_set", "items", "join", "keys", "pop", "put", "read",
    "release", "run", "send", "set", "start", "stop", "update",
    "values", "wait", "write",
}

_LOCK_CTORS = ("Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore")


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """['self', 'pool', '_lock'] for ``self.pool._lock``, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


class _Collector:
    """Builds the JSON facts for one file."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx

    # ---- class-level model ----

    def _def_comment(self, fn: ast.AST, pattern: re.Pattern) -> Set[str]:
        end = fn.body[0].lineno if fn.body else fn.lineno + 1
        for line in range(fn.lineno, end + 1):
            m = pattern.search(self.ctx.comment_on(line))
            if m:
                return _lock_names(m.group(1))
        return set()

    def collect_class(self, cls: ast.ClassDef) -> Optional[dict]:
        ctx = self.ctx
        locks: Set[str] = set()
        aliases: List[List[str]] = []
        shared: Dict[str, str] = {}
        attr_types: Dict[str, str] = {}
        init = None
        methods = [s for s in cls.body
                   if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in methods:
            if fn.name == "__init__":
                init = fn
        if init is not None:
            for node in ast.walk(init):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                value = node.value
                attrs = [a for a in (_self_attr(t) for t in targets) if a]
                if not attrs:
                    continue
                comment = ctx.comment_on(node.lineno)
                m = _GUARDED_RE.search(comment)
                if m:
                    locks |= _lock_names(m.group(1))
                m = _SHARED_RE.search(comment)
                if m:
                    for attr in attrs:
                        shared[attr] = f"{m.group(1)}.{m.group(2)}"
                        locks.add(attr)
                m = _INSTANCE_RE.search(comment)
                if m:
                    for attr in attrs:
                        attr_types[attr] = m.group(1)
                if isinstance(value, ast.Call):
                    q = qualname(value.func) or ""
                    tail = q.rsplit(".", 1)[-1]
                    if tail in _LOCK_CTORS:
                        for attr in attrs:
                            locks.add(attr)
                        if tail == "Condition" and value.args:
                            inner = _self_attr(value.args[0])
                            if inner is not None:
                                locks.add(inner)
                                for attr in attrs:
                                    aliases.append(sorted({attr, inner}))
                    elif tail and tail[0].isupper():
                        # self.x = ClassName(...): remember the type so
                        # self.x.method() resolves in pass 2
                        for attr in attrs:
                            attr_types.setdefault(attr, tail)
        # any `with self.X:` subject anywhere in the class is a lock
        for node in ast.walk(cls):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    attr = _self_attr(item.context_expr)
                    if attr is not None:
                        locks.add(attr)
        out_methods: Dict[str, dict] = {}
        for fn in methods:
            md = self._collect_method(cls, fn, locks, attr_types)
            if md is not None:
                out_methods[fn.name] = md
        if not locks and not out_methods:
            return None
        return {
            "locks": sorted(locks),
            "aliases": sorted(aliases),
            "shared": shared,
            "attr_types": attr_types,
            "methods": out_methods,
        }

    # ---- method events ----

    def _resolve_lock_ref(self, expr: ast.AST, locks: Set[str],
                          attr_types: Dict[str, str],
                          local_types: Dict[str, str]) -> Optional[dict]:
        """A with-subject as a lock reference: {'owner': None|'Class',
        'lock': name}.  owner None = a lock of the current class."""
        chain = _attr_chain(expr)
        if not chain or len(chain) < 2:
            return None
        if chain[0] == "self" and len(chain) == 2:
            return {"owner": None, "lock": chain[1]}
        if chain[0] == "self" and len(chain) == 3 \
                and chain[1] in attr_types:
            return {"owner": attr_types[chain[1]], "lock": chain[2]}
        if len(chain) == 2 and chain[0] in local_types:
            return {"owner": local_types[chain[0]], "lock": chain[1]}
        return None

    def _collect_method(self, cls: ast.ClassDef, fn: ast.AST,
                        locks: Set[str], attr_types: Dict[str, str],
                        ) -> Optional[dict]:
        ctx = self.ctx
        holds = sorted(self._def_comment(fn, _HOLDS_RE))
        # one linear pre-pass for local aliases: v = self.x (typed) or
        # v = ClassName(...)
        local_types: Dict[str, str] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                src = _self_attr(node.value)
                if src is not None and src in attr_types:
                    local_types[name] = attr_types[src]
                elif isinstance(node.value, ast.Call):
                    q = qualname(node.value.func) or ""
                    tail = q.rsplit(".", 1)[-1]
                    if tail and tail[0].isupper() \
                            and tail not in _LOCK_CTORS:
                        local_types[name] = tail

        def held_at(node: ast.AST,
                    stop_item: Optional[ast.withitem] = None) -> List[dict]:
            out = [{"owner": None, "lock": h} for h in holds]
            for anc in ctx.ancestors(node):
                if anc is fn:
                    break
                if isinstance(anc, (ast.With, ast.AsyncWith)):
                    for item in anc.items:
                        if item is stop_item:
                            break
                        ref = self._resolve_lock_ref(
                            item.context_expr, locks, attr_types,
                            local_types)
                        if ref is not None:
                            out.append(ref)
            return out

        events: List[dict] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for i, item in enumerate(node.items):
                    ref = self._resolve_lock_ref(
                        item.context_expr, locks, attr_types, local_types)
                    if ref is None:
                        continue
                    held = held_at(node)
                    for prev in node.items[:i]:
                        pref = self._resolve_lock_ref(
                            prev.context_expr, locks, attr_types,
                            local_types)
                        if pref is not None:
                            held.append(pref)
                    events.append({"kind": "acquire", "lock": ref,
                                   "line": item.context_expr.lineno,
                                   "held": held})
            elif isinstance(node, ast.Call):
                tgt = self._call_target(node, attr_types, local_types)
                if tgt is not None:
                    events.append({"kind": "call", "target": tgt,
                                   "line": node.lineno,
                                   "held": held_at(node)})
        if not events and not holds:
            return None
        return {"holds": holds, "events": events}

    def _call_target(self, node: ast.Call, attr_types: Dict[str, str],
                     local_types: Dict[str, str]) -> Optional[dict]:
        func = node.func
        if not isinstance(func, ast.Attribute):
            return None
        meth = func.attr
        recv = func.value
        if isinstance(recv, ast.Name) and recv.id == "self":
            return {"form": "self", "method": meth}
        chain = _attr_chain(recv)
        if chain and chain[0] == "self" and len(chain) == 2 \
                and chain[1] in attr_types:
            return {"form": "typed", "cls": attr_types[chain[1]],
                    "method": meth}
        if chain and len(chain) == 1 and chain[0] in local_types:
            return {"form": "typed", "cls": local_types[chain[0]],
                    "method": meth}
        if meth in _FALLBACK_STOPLIST or meth.startswith("__"):
            return None
        return {"form": "name", "method": meth}


# ---------------------------------------------------------------------------
# Pass 2: the graph
# ---------------------------------------------------------------------------


class _Graph:
    """Canonical lock graph: union-find over (Class, lock) nodes, edges
    with example sites, SCC cycle detection."""

    def __init__(self):
        self._parent: Dict[str, str] = {}
        self._prefer: Set[str] = set()   # annotation-named canonical roots
        # (a, b) -> example sites, ``file:Class.method`` -> first line there
        # (the artifact names the method only, so that an edit above it
        # does not change the committed evidence; a finding is anchored
        # at the line)
        self.edges: Dict[Tuple[str, str], Dict[str, int]] = {}
        self.alias_members: Dict[str, Set[str]] = {}

    # ---- union-find ----

    def _find(self, n: str) -> str:
        while self._parent.get(n, n) != n:
            self._parent[n] = self._parent.get(self._parent[n],
                                               self._parent[n])
            n = self._parent[n]
        return n

    def add_node(self, n: str) -> None:
        self._parent.setdefault(n, n)
        self.alias_members.setdefault(self._find(n), set()).add(n)

    def union(self, a: str, b: str, prefer_b: bool = False) -> None:
        self.add_node(a)
        self.add_node(b)
        ra, rb = self._find(a), self._find(b)
        if ra == rb:
            return
        # annotation targets (shared lock: X._l) win; otherwise the
        # lexicographically smaller name is the stable canonical choice
        if prefer_b:
            self._prefer.add(rb)
        root, child = (rb, ra) if (rb in self._prefer or
                                   (ra not in self._prefer and rb < ra)) \
            else (ra, rb)
        self._parent[child] = root
        members = self.alias_members.pop(child, {child})
        self.alias_members.setdefault(root, {root}).update(members)

    def canon(self, n: str) -> str:
        return self._find(n) if n in self._parent else n

    def add_edge(self, a: str, b: str, example: str, line: int) -> None:
        a, b = self.canon(a), self.canon(b)
        if a == b:
            return
        sites = self.edges.setdefault((a, b), {})
        sites[example] = min(line, sites.get(example, line))

    # ---- analysis ----

    def nodes(self) -> List[str]:
        return sorted({self._find(n) for n in self._parent})

    def cycles(self) -> List[List[str]]:
        """SCCs with >1 node (iterative Tarjan), each sorted + rotated
        for stable output."""
        adj: Dict[str, List[str]] = {}
        for (a, b) in self.edges:
            adj.setdefault(a, []).append(b)
            adj.setdefault(b, [])
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        sccs: List[List[str]] = []
        counter = [0]

        for start in sorted(adj):
            if start in index:
                continue
            work = [(start, iter(sorted(adj[start])))]
            index[start] = low[start] = counter[0]
            counter[0] += 1
            stack.append(start)
            on_stack.add(start)
            while work:
                v, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on_stack.add(w)
                        work.append((w, iter(sorted(adj[w]))))
                        advanced = True
                        break
                    if w in on_stack:
                        low[v] = min(low[v], index[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    pv = work[-1][0]
                    low[pv] = min(low[pv], low[v])
                if low[v] == index[v]:
                    scc = []
                    while True:
                        w = stack.pop()
                        on_stack.discard(w)
                        scc.append(w)
                        if w == v:
                            break
                    if len(scc) > 1:
                        sccs.append(sorted(scc))
        return sorted(sccs)

    def topo_order(self) -> List[str]:
        """Kahn topological order (deterministic: sorted zero-degree
        set); empty when the graph has a cycle."""
        nodes = self.nodes()
        indeg = {n: 0 for n in nodes}
        adj: Dict[str, List[str]] = {n: [] for n in nodes}
        for (a, b) in self.edges:
            adj[a].append(b)
            indeg[b] += 1
        ready = sorted(n for n in nodes if indeg[n] == 0)
        out: List[str] = []
        while ready:
            n = ready.pop(0)
            out.append(n)
            for m in sorted(adj[n]):
                indeg[m] -= 1
                if indeg[m] == 0:
                    ready.append(m)
            ready.sort()
        return out if len(out) == len(nodes) else []


class LockOrderRule(ProjectRule):
    id = "lock-order"
    summary = ("repo-wide lock-acquisition graph from with-nesting, "
               "'# holds' annotations and a bounded call graph; any "
               "cycle = potential deadlock")

    # ---- pass 1 ----

    def collect(self, ctx: FileContext):
        if ctx.tree is None:
            return None
        classes: Dict[str, dict] = {}
        collector = _Collector(ctx)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                model = collector.collect_class(node)
                if model is not None:
                    classes[node.name] = model
        if not classes:
            return None
        return {"classes": classes}

    # ---- pass 2 ----

    def build_graph(self, project: ProjectContext) -> Tuple[dict, dict]:
        """The lockorder artifact, and the first line of each example site
        it names (``file:Class.method``, not a line: the evidence changes
        when the lock structure does, not when a line moves)."""
        facts = project.facts_for(self.id)
        # class name -> (relpath, model); later duplicate class names are
        # ignored deterministically (first file in walk order wins)
        classes: Dict[str, Tuple[str, dict]] = {}
        for relpath in sorted(facts):
            for cname, model in facts[relpath]["classes"].items():
                classes.setdefault(cname, (relpath, model))

        graph = _Graph()
        for cname, (_rel, model) in classes.items():
            for lock in model["locks"]:
                graph.add_node(f"{cname}.{lock}")
            for group in model["aliases"]:
                for a, b in zip(group, group[1:]):
                    graph.union(f"{cname}.{a}", f"{cname}.{b}")
        for cname, (_rel, model) in classes.items():
            for lock, target in model["shared"].items():
                tcls = target.split(".", 1)[0]
                if tcls in classes:
                    graph.union(f"{cname}.{lock}", target, prefer_b=True)

        # bare-name fallback table: method name -> defining classes with
        # lock-relevant bodies
        by_name: Dict[str, List[str]] = {}
        for cname, (_rel, model) in classes.items():
            for mname, md in model["methods"].items():
                if md["events"] or md["holds"]:
                    by_name.setdefault(mname, []).append(cname)

        def resolve(caller_cls: str, target: dict) -> Optional[str]:
            form = target["form"]
            meth = target["method"]
            if form == "self":
                cls = caller_cls
            elif form == "typed":
                cls = target["cls"]
            else:
                cands = by_name.get(meth, [])
                if len(cands) != 1:
                    return None
                cls = cands[0]
            if cls in classes and meth in classes[cls][1]["methods"]:
                return f"{cls}.{meth}"
            return None

        def node_of(caller_cls: str, ref: dict) -> str:
            owner = ref["owner"] or caller_cls
            return graph.canon(f"{owner}.{ref['lock']}")

        # transitive acquisition sets, to a fixed point
        acquires: Dict[str, Set[str]] = {}
        calls: Dict[str, List[str]] = {}
        for cname, (_rel, model) in classes.items():
            for mname, md in model["methods"].items():
                key = f"{cname}.{mname}"
                acq: Set[str] = set()
                outs: List[str] = []
                for ev in md["events"]:
                    if ev["kind"] == "acquire":
                        acq.add(node_of(cname, ev["lock"]))
                    else:
                        tgt = resolve(cname, ev["target"])
                        if tgt is not None:
                            outs.append(tgt)
                acquires[key] = acq
                calls[key] = outs
        for _ in range(len(acquires) + 1):
            changed = False
            for key, outs in calls.items():
                for tgt in outs:
                    extra = acquires.get(tgt, set()) - acquires[key]
                    if extra:
                        acquires[key] |= extra
                        changed = True
            if not changed:
                break

        # edges: B acquired (directly or via a resolved call) under A
        for cname, (rel, model) in classes.items():
            for mname, md in model["methods"].items():
                for ev in md["events"]:
                    held = [node_of(cname, h) for h in ev["held"]]
                    if not held:
                        continue
                    site = f"{rel}:{cname}.{mname}"
                    if ev["kind"] == "acquire":
                        acquired = {node_of(cname, ev["lock"])}
                    else:
                        tgt = resolve(cname, ev["target"])
                        acquired = acquires.get(tgt, set()) if tgt else set()
                    for b in acquired:
                        for a in held:
                            graph.add_edge(a, b, site, ev["line"])

        cycles = graph.cycles()
        return {
            "graftcheck_lockorder": 1,
            # classes that OWN a lock: a class that takes none (however
            # many methods it has) does not move the committed evidence
            "classes": sum(1 for _rel, model in classes.values()
                           if model["locks"]),
            "nodes": [
                {"id": n,
                 "aliases": sorted(graph.alias_members.get(n, {n}))}
                for n in graph.nodes()],
            "edges": [
                {"from": a, "to": b, "examples": sorted(ex)[:3]}
                for (a, b), ex in sorted(graph.edges.items())],
            "order": graph.topo_order(),
            "cycles": cycles,
        }, {site: line for ex in graph.edges.values()
            for site, line in ex.items()}

    def finalize(self, project: ProjectContext) -> Iterable[Finding]:
        artifact, line_of = self.build_graph(project)
        project.artifacts["lockorder"] = artifact
        edge_by_from: Dict[str, List[dict]] = {}
        for e in artifact["edges"]:
            edge_by_from.setdefault(e["from"], []).append(e)
        for cycle in artifact["cycles"]:
            # anchor the finding at one edge inside the cycle
            members = set(cycle)
            site = None
            chain = []
            for e in artifact["edges"]:
                if e["from"] in members and e["to"] in members:
                    chain.append(f"{e['from']} -> {e['to']} "
                                 f"(e.g. {e['examples'][0]})")
                    if site is None:
                        site = e["examples"][0]
            path = (site or "unknown:").rpartition(":")[0]
            yield self.project_finding(
                path or "unknown", line_of.get(site, 1),
                "potential deadlock: lock-acquisition cycle "
                + " ; ".join(chain)
                + " — break the cycle or document a single global order")
