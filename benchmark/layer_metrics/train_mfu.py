"""Model FLOP/s utilisation: the benchmark's own FLOPs per token (forward +
backward matmuls with the head, causal attention capped at the window; no
recomputation, no embedding gather) x tokens/s of this run over chips x the
bf16 peak."""

from benchmark.lib import flops, readers

LAYER = 'train driver training.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'host_clock'


def reduce(run):
    rate = readers.train_rate(run)
    if rate is None or run.peaks is None:
        return None
    per_token = flops.train_flops_per_token(
        run.cell.model, int(run.cell.traffic["seq_length"]))
    return 100.0 * per_token * rate / (run.chips * run.peaks["bf16_flops_per_s"])
