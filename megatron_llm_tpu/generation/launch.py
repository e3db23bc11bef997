"""Where the serving engine calls its tick program, and nothing else.

A Mosaic kernel's payload in a lowered tick names the files and lines of the
kernel's callers, ten frames out, and a compile cache finds a program again
only where that text is the same.  The state sweeps (ops/pallas/retention.py,
ops/pallas/gated_delta.py) sit nine frames under ``tick``, so their tenth
frame is whoever calls the tick: this line, which stays put when
``generation/engine.py`` or ``generation/blocks.py`` are edited
(``tools/tick_digest.py`` lowers through it and prints its position).  KEEP
THIS FILE AS IT IS: a line above the call makes those ticks new programs.
"""


def call_tick(tick_fn, *operands):
    return tick_fn(*operands)
