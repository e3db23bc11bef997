"""Host work per tick over the whole window: the sums of
mlt_engine_tick_phase_seconds for the phases admit, plan, launch and apply
(not fetch: that is the wait for the device) over mlt_engine_ticks_total.
Apply includes the scheduler thread's wait to get the interpreter lock back
from the handler threads it woke (4-10 ms a tick with 128 streams): that is
time the host keeps the device waiting, but not CPU work of the engine."""

from benchmark.lib import readers

LAYER = 'engine host between launches generation/engine.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    ticks = readers.counter(run, "mlt_engine_ticks_total")
    sums = [readers.counter(
        run, 'mlt_engine_tick_phase_seconds_sum{phase="%s"}' % ph)
        for ph in ("admit", "plan", "launch", "apply")]
    if not ticks or any(v is None for v in sums):
        return None
    return 1e3 * sum(sums) / ticks
