"""Model FLOP/s utilisation of the patterned, expert-sharded stack: the
benchmark's own FLOPs per token on THIS chip (lib/flops_hybrid.py: attention
under each layer's own mask, the router, the even share of the top-6
assignments whose expert is held, the held slice of the head; forward +
backward, no recomputation, no gather) x tokens/s of this run over chips x
the bf16 peak.  Bound: a step cannot finish its needed FLOPs faster than the
peak, so the share cannot pass 100%."""

from benchmark.lib import flops_hybrid, readers

LAYER = 'train driver training.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'host_clock'


def reduce(run):
    rate = readers.train_rate(run)
    if rate is None or run.peaks is None:
        return None
    per_token = flops_hybrid.train_flops_per_token(
        run.cell.model, int(run.cell.traffic["seq_length"]))
    return 100.0 * per_token * rate / (run.chips * run.peaks["bf16_flops_per_s"])
