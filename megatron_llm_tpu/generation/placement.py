"""The device layout the serving engine keeps its word-embedding table in.

The table is read two ways: the embedding GATHERS rows of it, and a tied
head contracts over its hidden axis.  A device lays a 2-D array out as it
sees fit: a TPU puts the axis that is a whole number of 128-lane rows
minor, so ``bf16[65024, 4544]`` (Falcon: 4,544 = 35.5 x 128, 65,024 =
508 x 128) lies VOCABULARY-minor by default, a gather of rows from it is a
gather of 4,544 strided elements a row, and the compiler writes the whole
table out again in rows, 564 MiB, inside every program that looks a token
up (PERF.md section 6, PR 64).  Held in rows, the gather reads it as it
lies and the head's dot reads the same bytes through a bitcast.

One rule, read off the ARRAY and never off the model: a leaf whose device
layout is rows-major already (the layout it holds, or the default of its
shape and dtype on its device) is returned as the object it was; any other
is put into the same tiling with its axes in order, once, keeping its
sharding.  ``jax.jit`` compiles for a committed argument's own layout
(and keys its executables on it), so every program the engine runs over
its parameters follows, and a program lowered on abstract parameters
follows a ``jax.ShapeDtypeStruct`` that carries the ``Format``:
``tools/tick_hlo_copies.py`` and ``tools/tick_digest.py`` hand their
abstract tables to the engine's own call (:func:`tables_in_rows`), where
the default layout is asked of a described, compile-only device.

What follows from it: a re-laid table is COMMITTED to its device, and so is
every output of a program that reads it.  :func:`committed_to` tells the
engine where its parameters are committed, and the engine gives its pools
and its uploads the same birth.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.experimental.layout import Format, Layout

from megatron_llm_tpu.observability import compiles

__all__ = ["committed_to", "device_layout", "in_rows", "rows_format",
           "tables_in_rows"]


def device_layout(leaf) -> Optional[Layout]:
    """The device-local layout of ``leaf``, an array on its device or a
    ``jax.ShapeDtypeStruct`` with a sharding: the one it holds or was
    given, else the default of its shard's shape and dtype on its device.
    None where there is no device to ask (a host array, no sharding)."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return None
    held = leaf.format.layout
    if held is not None:
        return held
    device = min(sharding.device_set, key=lambda d: d.id)
    return Layout.from_pjrt_layout(device.client.get_default_layout(
        np.dtype(leaf.dtype), sharding.shard_shape(leaf.shape), device))


def rows_format(leaf) -> Optional[Format]:
    """The ``Format`` that holds ``leaf`` in rows (its first axis major,
    its last minor, the tiling its device gave it), or None where it lies
    so already."""
    layout = device_layout(leaf)
    rows = tuple(range(len(leaf.shape)))
    if layout is None or tuple(layout.major_to_minor) == rows:
        return None
    return Format(layout.update(major_to_minor=rows), leaf.sharding)


def _pinned(leaf, fmt: Format):
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=fmt,
                                    weak_type=leaf.weak_type)
    return jax.device_put(leaf, fmt)


def in_rows(leaf):
    """``leaf`` in rows: the very object where it lies so, else a copy of
    an array on its device (the old one is the caller's to drop) or, of a
    ``jax.ShapeDtypeStruct``, the same shape pinned to the ``Format``."""
    fmt = rows_format(leaf)
    return leaf if fmt is None else _pinned(leaf, fmt)


def tables_in_rows(*trees):
    """The parameter trees an engine is built on (its model's, its draft
    model's; None passes through), each with its word-embedding table
    (``tree["embedding"]["word_embeddings"]``, tied or not) in rows: the
    SAME tree where the table lies so already.  One ``table-rows``
    start-up phase around the copies, which waits for them and says the
    bytes it re-laid (0: nothing was touched).  Trees of
    ``jax.ShapeDtypeStruct`` (the tools' abstract parameters) take the same
    path, and nothing is copied."""
    tables = [None if tree is None else tree["embedding"]["word_embeddings"]
              for tree in trees]
    # by object: a draft that shares its target's table shares the copy
    moving = {id(t): (t, fmt) for t in tables if t is not None
              for fmt in (rows_format(t),) if fmt is not None}
    nbytes = sum(math.prod(t.shape) * np.dtype(t.dtype).itemsize
                 for t, _ in moving.values())
    with compiles.startup_phase("table-rows", bytes=nbytes):
        placed = {key: jax.block_until_ready(_pinned(t, fmt))
                  for key, (t, fmt) in moving.items()}
    return tuple(
        tree if id(t) not in placed else {**tree, "embedding": {
            **tree["embedding"], "word_embeddings": placed[id(t)]}}
        for tree, t in zip(trees, tables))


def committed_to(params):
    """The sharding a leaf of ``params`` is COMMITTED to (``jax.device_put``
    with a sharding or a ``Format`` commits; a jitted initialiser's outputs
    are not), or None where none is.  A program's outputs are committed
    where one input is, and ``jax.jit`` keys its executables on which
    arguments are committed: an engine whose re-laid table is the one
    committed operand of its first tick would lower and compile (or load)
    every tick program again as the pool, then the carried tokens, come
    back committed (three times a program in the Falcon cell: PERF.md
    section 6, PR 64)."""
    for leaf in jax.tree.leaves(params):
        if getattr(leaf, "committed", False):
            return leaf.sharding
    return None
