"""The gather of the picked latent rows and the attention over them, as a
share of their roofline over the traced span: the picked rows read once
(1,152 bytes each; a prompt chunk's rows no more of them than their context
holds, once a chunk) and the absorbed attention's operations over them
(lib/flops_axk2.py `gather_cost` + `attention_cost`), over the device self
time under the scopes `sparse_gather` and `sparse_attention`.  A program
without the scopes reports nothing."""

from benchmark.lib import flops, flops_axk2

LAYER = 'learned sparse attention ops/sparse_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "index_topk" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: "/sparse_gather/" in o.op_name + "/"
        or "/sparse_attention/" in o.op_name + "/")
    if t <= 0:
        return None
    rows = flops_axk2.span_rows(run)
    g = flops_axk2.total(flops_axk2.gather_cost, run.cell.model, rows)
    a = flops_axk2.total(flops_axk2.attention_cost, run.cell.model, rows)
    least, bound = flops.roofline_seconds(
        g["flops"] + a["flops"], g["bytes"] + a["bytes"], run.peaks)
    print(f"benchmark: picked rows: {t * 1e3:.2f} ms under sparse_gather + "
          f"sparse_attention in the traced span, {sum(r[1] for r in rows):.0f} rows, "
          f"{(g['bytes'] + a['bytes']) / 1e9:.3f} GB and "
          f"{a['flops'] / 1e12:.3f} TFLOP needed, least {least * 1e3:.2f} ms "
          f"({bound}-bound)", flush=True)
    return 100.0 * least / t
