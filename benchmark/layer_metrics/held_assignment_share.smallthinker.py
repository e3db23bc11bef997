"""Router assignments whose expert this chip holds and ran, over all the
router made: `held` / `assignments` of the program's `train-moe` spans in the
capture, summed.  16 of 64 experts are held, so an even router gives 25%;
the rest of a layer's sum is other chips' work.  A part of a whole: at most
100%."""

from benchmark.lib import train_spans

LAYER = 'expert layer models/moe.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'program_span'


def reduce(run):
    noted = train_spans.moe_spans(run)
    made = sum(float(s.args["assignments"]) for s in noted)
    if not made:
        return None
    return 100.0 * sum(float(s.args["held"]) for s in noted) / made
