"""Share of device 0's idle time in the traced span during which at least one
`serve-write` span (a body or SSE-chunk write) was open on a handler thread:
lib/spans.py idle_under."""

from benchmark.lib import spans

LAYER = 'HTTP generation/server.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_span'


def reduce(run):
    total = spans.idle_seconds(run.trace)
    under = spans.idle_under(run.trace, spans.of(run), "serve-write")
    if under is None or total <= 0:
        return None
    return 100.0 * under / total
