"""Device time of the `flash_fwd` kernel executions inside the backward's
recomputation (scope path through `rematted_computation`) over the device
time of all three flash kernels (`flash_fwd`, `flash_bwd_dq`,
`flash_bwd_dkv`), over the whole train steps in the trace."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/flash_attention.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _kernel(o):
    return o.is_pallas and o.name.split(".")[0] in KERNELS


def reduce(run):
    if run.trace is None:
        return None
    steps = run.trace.full_runs(readers.TRAIN_PROGRAM)
    every = run.trace.self_seconds_within(_kernel, steps)
    if not steps or every <= 0:
        return None
    remat = run.trace.self_seconds_within(
        lambda o: _kernel(o) and o.name.split(".")[0] == "flash_fwd"
        and "rematted_computation" in o.op_name, steps)
    return 100.0 * remat / every
