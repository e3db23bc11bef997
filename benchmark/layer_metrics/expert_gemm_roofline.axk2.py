"""The grouped expert GEMMs' share of their roofline over the whole ticks of
the traced span, for a chip that HOLDS a share of the experts: what the
touched HELD experts' weights and the rows routed to them cost to move and
to multiply at the chip's peaks (lib/flops_delta.py: an expert of 3 x 7168
x 2048 here, where `expert_gemm_roofline.commanda` reads another key for
another width), over the device self time under the scope
`moe/expert_gemm` in those ticks.

A tick counts when the capture holds its whole execution, the
`engine-launch` span that dispatched it and the `engine-moe` span in which
the engine noted what that tick's router did (`tick=`, `held=`,
`held_touched=`).  `expert_gemm_roofline.joyai` prices every assignment and
every expert touched, thirty-one thirty-seconds of which this chip never
sees (A.X-K2: 8 held of 256, group-limited).  The arithmetic is
`expert_gemm_roofline.gigachat`'s, at the same expert width."""

import warnings

from benchmark.lib import flops, flops_delta, spans

LAYER = 'expert layer models/moe.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace.path:
        return None
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        found = spans.from_profile(ProfileData.from_file(run.trace.path),
                                   spans.SPAN_NAMES | {"engine-moe"})
    noted = {int(s.args["tick"]): s.args for s in found
             if s.name == "engine-moe" and "tick" in s.args
             and "held" in s.args}
    kinds = spans.launch_kinds(run.trace, found)
    if not noted or not kinds:
        return None
    ticks, rows, touched = [], 0.0, 0.0
    for k in kinds:
        no = k["launch"].args.get("tick")
        if k["ambiguous"] or no is None or int(no) not in noted:
            continue
        ticks.append((k["start"], k["end"]))
        rows += float(noted[int(no)]["held"])
        touched += float(noted[int(no)]["held_touched"])
    t = run.trace.self_seconds_within(
        lambda o: "/moe/expert_gemm/" in o.op_name + "/", ticks)
    if not ticks or t <= 0:
        return None
    cost = flops_delta.held_gemm_cost(run.cell.model, rows, touched)
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                          run.peaks)
    print(f"benchmark: held expert GEMMs: {len(ticks)} whole ticks, {rows:.0f} "
          f"held rows on {touched:.0f} held experts, {cost['bytes'] / 1e9:.3f} "
          f"GB and {cost['flops'] / 1e12:.3f} TFLOP needed, least "
          f"{least * 1e3:.2f} ms ({bound}-bound), {t * 1e3:.2f} ms under "
          f"moe/expert_gemm", flush=True)
    return 100.0 * least / t
