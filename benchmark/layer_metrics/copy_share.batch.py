"""Device self time, inside the executions of the engine's tick program, of
the operations that MOVE data and compute nothing, over the device's busy
time in the traced span: what a tick spends slicing a layer's weights out
of their stack, writing them again in another layout, or turning a table
round, instead of multiplying by them where they lie.  Counted:

* an XLA ``copy`` (``copy.108``; not the asynchronous ``copy-start`` /
  ``copy-done`` of a prefetch into fast memory);
* a fusion that XLA named after its parts, when every part is one of
  ``copy``, ``slice``, ``dynamic-slice``, ``bitcast``, ``transpose``,
  ``constant`` (``constant_dynamic-slice_fusion.8``,
  ``slice_bitcast_fusion.2``; not ``bitcast_dynamic-update-slice_fusion``,
  which writes the cache, nor ``bitcast_add_fusion``);
* a plain ``fusion.N`` of kind ``kLoop`` whose ``op_name`` ends in
  ``slice``, ``dynamic_slice``, ``reshape``, ``squeeze``,
  ``reshape_squeeze`` or ``transpose`` (``fusion.699.remat
  [closed_call/slice]``).

Never a Pallas call, a scatter, a collective or a fusion around a dot.
0.0 where a tick ran and nothing of the kind did; nothing where the trace
holds no tick."""

import re

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'

PARTS = {"copy", "slice", "dynamic-slice", "bitcast", "transpose", "constant"}
TAILS = {"slice", "dynamic_slice", "reshape", "squeeze", "reshape_squeeze",
         "transpose"}
SUFFIX = re.compile(r"(\.(\d+|clone|remat\d*|sunk))*$")


def moves(op) -> bool:
    if op.is_pallas or op.is_collective:
        return False
    stem = SUFFIX.sub("", op.name)
    if stem == "copy":
        return True
    if stem.endswith("_fusion"):
        return set(stem[:-len("_fusion")].split("_")) <= PARTS
    if stem == "fusion" and "kind=kLoop" in op.text:
        tail = op.op_name.rstrip("/").rsplit("/", 1)[-1].replace(";", "_")
        return tail in TAILS
    return False


def reduce(run):
    if run.trace is None or not run.trace.busy_s or not run.trace.devices:
        return None
    tick = re.compile(readers.TICK_PROGRAM)
    ticks = [(s, e) for name, s, e in run.trace.devices[0].modules
             if tick.search(name)]
    if not ticks:
        return None
    moved = run.trace.self_seconds_within(moves, ticks)
    return 100.0 * moved / run.trace.busy_s
