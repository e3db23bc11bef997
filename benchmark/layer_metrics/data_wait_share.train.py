"""Share of the window the train loop spent waiting for a batch: the program's
data-wait-ms gauge, read at every draw of the window, summed, over the
window."""

LAYER = 'train driver training.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    waits = run.gauges.get("data-wait-ms")
    if not waits or run.t_open is None:
        return None
    # in a traced run only the part of the window before the profiler
    # started (its start stalls the loop, which would read as a data wait)
    end = run.disturbed_from or run.t_close
    span = end - run.t_open
    if span <= 0:
        return None
    return 100.0 * sum(v for t, v in waits if t < end) / 1e3 / span
