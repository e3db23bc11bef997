"""Share of device 0's idle time in the traced span that lies under the
scheduler thread's spans before a dispatch: `engine-admit`, `engine-plan` and
`engine-launch` (lib/spans.py idle_by_span)."""

from benchmark.lib import spans

LAYER = 'engine scheduler generation/engine.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_span'


def reduce(run):
    return spans.idle_share(
        run, ("engine-admit", "engine-plan", "engine-launch"))
