"""shard_map entry point — the ONLY module that spells jax's shard_map and
its context-mesh accessor (tools/graftcheck/rules/shardmap.py and
tools/linter.py enforce this), so the conventions the parallel/ and ops/
code relies on are stated once:

* ``shard_map(f, mesh=..., in_specs=..., out_specs=..., axis_names={...},
  check_vma=False)`` — partial-manual regions are declared by
  ``axis_names`` (the axes THIS region manualizes; default every axis of
  ``mesh``), the rest stay auto (GSPMD-partitioned);
* ``get_abstract_mesh()`` — the tracing-context mesh, whose ``manual_axes``
  tell a nested region which axes an enclosing shard_map has already
  manualized (ops/attention.kernel_region, parallel/ring.cp_is_manual).  A
  nested region passes that abstract mesh as its ``mesh=`` — the concrete
  global mesh raises a mesh-mismatch there.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["shard_map", "get_abstract_mesh"]


def get_abstract_mesh():
    """The mesh of the innermost shard_map region being traced; ``.empty``
    is True outside any region."""
    return jax.sharding.get_abstract_mesh()


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: Optional[bool] = None):
    """``jax.shard_map`` with the keyword-only spelling every caller uses."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = bool(check_vma)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)
