"""The looped stack's layer projections' share of their (bandwidth)
roofline over the whole ticks of the traced span: the bytes of projections
a tick streams (lib/flops_ouro.py: every layer's four matrices once a PASS,
4 x 48 x 51.4 M bf16 weights = 19.7 GB a tick, whatever its rows) over the
HBM peak, over the device self time, in those ticks, of the matrix products
under the `loop_pass` named scope: the Pallas kernels other than the paged
attention one (the GLU `fc1` kernel `glu_stack_matmul`) and every
instruction that IS a product, a `dot` or `convolution` or a fusion whose
body holds one whatever its root is named, looked up in the table of the
PROGRAM the event ran in (lib/hlo_modules.py: the tick at 0 and at 64
prompt rows number their fusions alike and fill them differently, and a
label by name alone calls the prefill tick's QKV product a `squeeze`).
Norms, rotary, the K/V scatter and the layout copies around the products
are NOT in the time: they are the glue, printed beside it.  A whole tick
is an execution of the tick program that the capture did not cut: every one
but the first and the last.  A program without the `loop_pass` scope, or a
trace without its programs' tables, reports nothing."""

import re

from benchmark.lib import flops_ouro, hlo_modules, readers

LAYER = 'engine tick generation/ragged.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace.devices:
        return None
    if "total_ut_steps" not in run.cell.model or not run.trace.path:
        return None
    rx = re.compile(readers.TICK_PROGRAM)
    ops = hlo_modules.Attributed(run.trace, hlo_modules.read(run.trace.path))
    ticks = {r for r in ops.runs if rx.search(r[2])}
    ticks -= {min(ticks, default=None), max(ticks, default=None)}
    products = glue = 0.0
    for o in ops.ops:
        ins = ops.instruction(o)
        if ins is None or ops.run_of(o) not in ticks or (
                "/loop_pass/" not in ins.op_name + "/"):
            continue
        if o.is_pallas and "paged_attention" in o.name:
            continue
        if ins.product or o.is_pallas:
            products += o.self_ns / 1e9
        else:
            glue += o.self_ns / 1e9
    if not ticks or products <= 0:
        return None
    need = len(ticks) * flops_ouro.tick_gemm_bytes(run.cell.model)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: looped projections: {len(ticks)} whole ticks, "
          f"{need / 1e9:.3f} GB of layer weights streamed "
          f"({flops_ouro.passes(run.cell.model)} passes a tick), least "
          f"{least * 1e3:.2f} ms (bandwidth-bound), {products * 1e3:.2f} ms "
          f"in the passes' matrix products, {glue * 1e3:.2f} ms in the "
          f"glue around them (norms, rotary, K/V write, copies)", flush=True)
    return 100.0 * least / products
