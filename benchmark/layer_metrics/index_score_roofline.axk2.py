"""The indexer's sweep's share of its roofline over the traced span: what
scoring every row of the span against every index key of its context needs
at the chip's peaks (lib/flops_axk2.py `sweep_cost`: a decode row reads its
own context's keys, 256 bytes each, so its sweep is bandwidth-bound at any
batch; a prompt chunk's rows need their keys ONCE a chunk and are bound by
their 16.5 kFLOP a key and row), over the device self time under the scope
`index_score`.  Rows and
contexts from the client's samples (`flops_axk2.span_rows`).  A program
without the scope reports nothing."""

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
    t = run.trace.self_seconds(lambda o: "/index_score/" in o.op_name + "/")
    if t <= 0:
        return None
    rows = flops_axk2.span_rows(run)
    cost = flops_axk2.total(flops_axk2.sweep_cost, run.cell.model, rows)
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                          run.peaks)
    print(f"benchmark: index sweep: {t * 1e3:.2f} ms under index_score in the "
          f"traced span, {sum(r[1] for r in rows):.0f} rows, {cost['bytes'] / 1e9:.3f} GB and "
          f"{cost['flops'] / 1e12:.3f} TFLOP needed, least {least * 1e3:.2f} "
          f"ms ({bound}-bound)", flush=True)
    return 100.0 * least / t
