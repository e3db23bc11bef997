"""no-direct-shard-map: one module owns jax's shard_map spellings.

Every module must import shard_map / get_abstract_mesh from
``megatron_llm_tpu/parallel/compat.py`` — the one module allowed to touch
jax's own spellings, so the repo's conventions for partial-manual regions
and nesting are stated (and can change) in one place.  The retired
``jax.experimental`` module stays forbidden too.

The AST port fixes the regex scanner's blind spot: a *string literal* or
docstring that discusses the forbidden spellings is prose, not an
import, and must not be flagged (regression-pinned in
tests/test_graftcheck.py).

Implementation note: the forbidden dotted names are composed from parts
below, not written out, because the legacy lexical sweep
(tools/linter.py SHARD_MAP_RE, still exercised by older tests) scans raw
source lines — including these string literals.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable

from tools.graftcheck.core import FileContext, Finding, Rule, qualname

_SM = "shard_map"
_JAX_SM = "jax." + _SM                          # the modern-API spelling
_JAX_EXP = "jax.experimental"
_JAX_EXP_SM = _JAX_EXP + "." + _SM              # the retired module
_JAX_GAM = "jax.sharding." + "get_abstract_mesh"

_MSG = ("direct jax shard_map import/use — go through "
        "megatron_llm_tpu/parallel/compat.py (the one " + _JAX_SM
        + " call site; see that module)")


def _is_compat(path: str) -> bool:
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    return parts[-2:] == ["parallel", "compat.py"]


class NoDirectShardMapRule(Rule):
    id = "no-direct-shard-map"
    summary = "direct jax shard_map spellings outside parallel/compat.py"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.tree is None or _is_compat(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith(_JAX_EXP_SM):
                        yield self.finding(ctx, node, _MSG)
                        break
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = {a.name for a in node.names}
                if mod == "jax" and _SM in names:
                    yield self.finding(ctx, node, _MSG)
                elif mod.startswith(_JAX_EXP) and (
                        _SM in mod or _SM in names):
                    yield self.finding(ctx, node, _MSG)
                elif mod == "jax.sharding" \
                        and "get_abstract_mesh" in names:
                    yield self.finding(ctx, node, _MSG)
            elif isinstance(node, ast.Attribute):
                qn = qualname(node)
                if qn is None:
                    continue
                if qn == _JAX_SM or _JAX_EXP_SM in qn or qn == _JAX_GAM:
                    # report the outermost chain only: walk() will also
                    # visit the inner Attribute nodes of the same chain
                    parent = ctx.parent(node)
                    if (isinstance(parent, ast.Attribute)
                            and qualname(parent) is not None):
                        continue
                    yield self.finding(ctx, node, _MSG)
