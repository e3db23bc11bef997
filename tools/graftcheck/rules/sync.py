"""Device-synchronization rules.

``obs-no-sync`` (ported): code under an ``observability/`` package
directory must never call ``jax.device_get`` or ``block_until_ready``.
Observability instruments the async training loop's overlap; an
instrument that syncs the device destroys the thing it measures, and the
PR-2 bitwise-loss guarantee with it.  The AST port narrows the old regex
to *code*: docstrings and comments in observability/ may now explain WHY
the package never syncs without tripping the rule (regression-pinned).

``sync-in-jit`` (new): no ``float()/int()/bool()/.item()/np.asarray/
device_get/block_until_ready`` on values inside traced code — functions
decorated with ``jax.jit``, passed to ``jax.jit``/``cached_jit``, or used
as shard_map bodies.  Under a tracer these either leak (ConcretizationTypeError
at best) or insert a hidden host-device sync that serializes the exact
dispatch pipeline PR 2 and PR 4 built; the Megatron-LM scaling result
(PAPERS.md) assumes the hot loop never blocks on the host.

``span-device-attr`` (ISSUE 12): no device-array-valued attributes on
``span()``/``instant()`` calls or flight-recorder ``event()`` calls.
The tracer and the flight recorder hold attrs by reference and
serialize them at DUMP time — a jax array smuggled in as an attr defers
a host-device sync to exactly the moment an operator asks for the
timeline, and keeps device buffers alive for the life of the ring.
Attrs must be host scalars: hoist the value out with ``int()``/
``float()``/``np.asarray`` *outside* any traced code first.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set

from tools.graftcheck.core import FileContext, Finding, Rule, qualname

_SYNC_NAMES = {"device_get", "block_until_ready"}


def _in_observability(path: str) -> bool:
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    return "observability" in parts


class ObsNoSyncRule(Rule):
    id = "obs-no-sync"
    summary = "device syncs in observability/ code (prose is fine now)"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.tree is None or not _in_observability(ctx.path):
            return
        msg = ("device sync in observability/ — instruments must never "
               "sync the device (megatron_llm_tpu/observability/"
               "__init__.py)")
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute) and node.attr in _SYNC_NAMES:
                yield self.finding(ctx, node, msg)
            elif isinstance(node, ast.Name) and node.id in _SYNC_NAMES:
                yield self.finding(ctx, node, msg)
            elif isinstance(node, ast.ImportFrom):
                if any(a.name in _SYNC_NAMES for a in node.names):
                    yield self.finding(ctx, node, msg)


# ---------------------------------------------------------------------------
# sync-in-jit
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}
# numpy-materializing spellings (any of the conventional numpy aliases)
_NP_SYNCS = {"np.asarray", "numpy.asarray", "onp.asarray",
             "np.array", "numpy.array", "onp.array"}


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` (as used in
    decorators)."""
    qn = qualname(node)
    if qn in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        fqn = qualname(node.func)
        if fqn in _JIT_NAMES:
            return True
        if fqn in _PARTIAL_NAMES and node.args \
                and qualname(node.args[0]) in _JIT_NAMES:
            return True
    return False


def _defs_by_name(tree: ast.AST) -> Dict[str, List[ast.AST]]:
    out: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


class SyncInJitRule(Rule):
    id = "sync-in-jit"
    summary = "host-device syncs / tracer leaks inside traced functions"

    def _resolve(self, arg: ast.AST, defs: Dict[str, List[ast.AST]],
                 nested_only: bool = False) -> List[ast.AST]:
        """Function nodes a jit/shard_map/cached_jit argument refers to.

        ``nested_only`` is the cached_jit builder case: ``build()`` itself
        runs at trace-BUILD time (host side, syncs are legal there) — only
        the functions it defines/returns are traced."""
        if isinstance(arg, ast.Lambda):
            # the engine idiom ``lambda: tick`` — a thunk whose RETURN
            # VALUE is the traced function; mark that function whole
            # (nested_only does not apply: the thunk body never runs
            # under the tracer, only what it returns does)
            if isinstance(arg.body, ast.Name):
                return list(defs.get(arg.body.id, []))
            return [arg]
        if not isinstance(arg, ast.Name):
            return []
        targets: List[ast.AST] = list(defs.get(arg.id, []))
        if not nested_only:
            return targets
        nested: List[ast.AST] = []
        for t in targets:
            for sub in ast.walk(t):
                if sub is not t and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                    nested.append(sub)
        return nested

    def _traced_nodes(self, ctx: FileContext) -> Set[ast.AST]:
        defs = _defs_by_name(ctx.tree)
        traced: Set[ast.AST] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jit_expr(d) for d in node.decorator_list):
                    traced.add(node)
            elif isinstance(node, ast.Call):
                fqn = qualname(node.func) or ""
                if fqn in _JIT_NAMES and node.args:
                    traced.update(self._resolve(node.args[0], defs))
                elif fqn.endswith("shard_map") and node.args:
                    traced.update(self._resolve(node.args[0], defs))
                elif fqn.endswith("cached_jit"):
                    # cached_jit(cfg, name, statics, build): the builder's
                    # nested defs are the traced program
                    build = node.args[3] if len(node.args) > 3 else None
                    for kw in node.keywords:
                        if kw.arg == "build":
                            build = kw.value
                    if build is not None:
                        traced.update(self._resolve(build, defs,
                                                    nested_only=True))
        # builder-factory convention (ISSUE 17): the engine reaches the
        # ragged tick builder through a cross-module thunk
        # (``build=lambda: make_ragged_tick_fn(...)``) that the per-file
        # resolver above cannot follow — the thunk body is a Call, not a
        # Name.  Module-level ``make_*_fn`` factories that touch jax are
        # therefore cached_jit builders by convention: the factory body
        # runs at build time (host side), every function it defines is
        # the traced program.  Factories with no jax reference (REST
        # client builders and the like) are host-side and exempt.
        for node in ctx.tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not (node.name.startswith("make_")
                    and node.name.endswith("_fn")):
                continue
            if not any(isinstance(sub, ast.Name)
                       and sub.id in {"jnp", "jax", "lax"}
                       for sub in ast.walk(node)):
                continue
            for sub in ast.walk(node):
                if sub is not node and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                    traced.add(sub)
        return traced

    def _check_body(self, ctx: FileContext, fn: ast.AST
                    ) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            fqn = qualname(node.func)
            if isinstance(node.func, ast.Name) \
                    and node.func.id in {"float", "int", "bool"}:
                # int(3) / float("1e-3") are host constants, not syncs
                if node.args and not all(
                        isinstance(a, ast.Constant) for a in node.args):
                    yield self.finding(
                        ctx, node,
                        f"{node.func.id}() on a traced value — leaks the "
                        f"tracer or forces a host sync inside jit; keep "
                        f"it in jnp or hoist it out of the traced "
                        f"function")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item":
                yield self.finding(
                    ctx, node,
                    ".item() inside traced code — device sync; return "
                    "the array and read it outside the program")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                yield self.finding(
                    ctx, node,
                    ".block_until_ready() inside traced code — the "
                    "program cannot wait on itself; sync outside")
            elif fqn in _NP_SYNCS:
                yield self.finding(
                    ctx, node,
                    f"{fqn}() inside traced code — materializes the "
                    f"tracer on host (use jnp, or move the conversion "
                    f"outside the traced function)")
            elif fqn is not None and (fqn == "device_get"
                                      or fqn.endswith(".device_get")):
                yield self.finding(
                    ctx, node,
                    "device_get inside traced code — hidden host-device "
                    "sync; drain metrics outside the program (the PR-2 "
                    "deferred-metrics pattern)")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.tree is None:
            return
        seen: Set[tuple] = set()
        for fn in self._traced_nodes(ctx):
            for f in self._check_body(ctx, fn):
                key = (f.line, f.col, f.message)
                if key not in seen:
                    seen.add(key)
                    yield f


# ---------------------------------------------------------------------------
# span-device-attr
# ---------------------------------------------------------------------------

# recording entry points whose KEYWORD attrs are serialized at dump time:
# trace spans/instants (observability/trace.py) and flight-recorder
# events (observability/flight.py — event / set_phase / finish)
_ATTR_SINKS = {"span", "instant", "event", "set_phase", "finish"}
# call-qualname prefixes that produce device arrays
_DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.", "jax.")
# ...except the jax spellings that are host-side by construction
_HOST_CALLS = {"jax.named_scope", "jax.debug.print"}


def _is_device_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    qn = qualname(node.func)
    if qn is None or qn in _HOST_CALLS:
        return False
    return any(qn == p[:-1] or qn.startswith(p) for p in _DEVICE_PREFIXES)


class SpanDeviceAttrRule(Rule):
    id = "span-device-attr"
    summary = ("device-array attrs on span()/instant()/flight-recorder "
               "events (forces a host sync at dump time)")

    def _tainted(self, fn: ast.AST) -> Set[str]:
        """Names bound (anywhere in ``fn``) to a device-producing call.
        Deliberately flow-insensitive: a name that EVER holds a device
        array in the function should not be an event attr under any
        branch."""
        tainted: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _is_device_call(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
                    elif isinstance(tgt, (ast.Tuple, ast.List)):
                        for el in tgt.elts:
                            if isinstance(el, ast.Name):
                                tainted.add(el.id)
        return tainted

    def _scope_of(self, ctx: FileContext, node: ast.AST) -> ast.AST:
        """Nearest enclosing function (the taint scope), else the
        module."""
        cur = ctx.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = ctx.parent(cur)
        return ctx.tree

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.tree is None:
            return
        taint_cache: Dict[ast.AST, Set[str]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = (node.func.id if isinstance(node.func, ast.Name)
                    else node.func.attr
                    if isinstance(node.func, ast.Attribute) else None)
            if name not in _ATTR_SINKS or not node.keywords:
                continue
            scope = self._scope_of(ctx, node)
            if scope not in taint_cache:
                taint_cache[scope] = self._tainted(scope)
            tainted = taint_cache[scope]
            for kw in node.keywords:
                if kw.arg is None:
                    continue
                bad = (_is_device_call(kw.value)
                       or (isinstance(kw.value, ast.Name)
                           and kw.value.id in tainted))
                if bad:
                    yield self.finding(
                        ctx, kw.value,
                        f"attr {kw.arg!r} on {name}() is a device "
                        f"array — the tracer/flight recorder "
                        f"serializes attrs at dump time, forcing a "
                        f"host sync then; record a host scalar "
                        f"instead (int()/float() outside traced "
                        f"code)")
