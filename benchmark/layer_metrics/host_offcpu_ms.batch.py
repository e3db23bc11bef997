"""Time the scheduler thread stood still inside its own work, in ms a tick
over the whole window: the wall seconds of the phases admit, plan, launch and
apply (mlt_engine_tick_phase_seconds) less the thread's own CPU seconds over
the same work (mlt_engine_tick_host_cpu_seconds: side dispatch = admit + plan
+ launch, one stretch on the thread, and side apply; time.thread_time at four
boundaries a tick), over mlt_engine_ticks_total.  Waiting for the interpreter
behind the stream writer's pass and the handler threads, for the engine's
lock, or inside a blocking upload; host_work_ms.batch less this is what the
thread computed.  Not fetch: that is a wait by design."""

from benchmark.lib import per_tick

LAYER = 'engine host between launches generation/engine.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    return per_tick.ms(
        run,
        ['mlt_engine_tick_phase_seconds_sum{phase="%s"}' % ph
         for ph in ("admit", "plan", "launch", "apply")],
        ['mlt_engine_tick_host_cpu_seconds_sum{side="%s"}' % side
         for side in ("dispatch", "apply")])
