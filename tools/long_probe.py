#!/usr/bin/env python3
"""One long probe of a serving cell against its reference, outside the cell:
a primed shared prefix, a body behind it, 32 greedy tokens, and the
reference's log-probability of each of them.

    chiprun --timeout 3000 -- python tools/long_probe.py \
        --workload axk2_docqa_32k_closed --seed N [--prefix 32768] [--body 640]

The cell's own comparison (``benchmark/lib/check.py``) reads probes of some
4.6k tokens; this reads ONE at the context the cell's window is timed at.
The engine (on the chip, the cell's weights, flags and pool) is first asked
for the prefix alone for one token, as the cell's priming does, and then for
prefix + body: the prefix cache serves the prefix's whole pages, the body is
prefilled behind them and 32 tokens are decoded, every row selecting among
all ~33k cached index keys.  The reference's float32 forward of 33k tokens
does not fit beside the weights on a 16 GB chip (the dense layer's
activations alone are 4.9 GB), so it runs on the HOST's CPU device, a layer
at a time, at ``highest`` precision, while the chip waits: minutes.  One
JSON line: the three statistics of ``check.compare`` under the cell's own
limits, the tokens the cache served, and the seconds each side took.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prefix", type=int, default=32768)
    ap.add_argument("--body", type=int, default=640)
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()
    args.rate, args.trace = None, 0
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:       # the chip for the program, the host for the reference
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmark.lib import cells, check, serving, traffic
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    cell = cells.Cell(args.workload)
    if jax.devices()[0].platform != ("cpu" if args.rehearsal else "tpu"):
        print("long_probe: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    served = serving.Served(cell, args)
    served.server.stop()
    eng = served.engine
    ids = traffic.probe_tokens(args.seed, 1, args.prefix + args.body,
                               served.vocab)[0]
    never = 10 ** 9
    t = time.monotonic()
    eng.submit(ids[:args.prefix], 1, top_k=1, termination_id=never)
    eng.run_until_idle()
    primed_s = time.monotonic() - t
    hits = eng.prefix_hit_tokens
    t = time.monotonic()
    req = eng.submit(ids, check.PROBE_TOKENS, top_k=1, termination_id=never)
    eng.run_until_idle()
    tokens, lps = req.result(timeout=600)
    probe = {"name": "long", "prompt": list(ids),
             "tokens": [int(x) for x in tokens[len(ids):]]}
    line = {"workload": cell.name, "seed": args.seed,
            "prompt_tokens": len(ids), "emitted": len(probe["tokens"]),
            "primed_s": primed_s, "program_s": time.monotonic() - t,
            "prefix_hit_tokens": eng.prefix_hit_tokens - hits}
    cpu = jax.devices("cpu")[0]
    params = jax.device_put(served.params, cpu)
    if not args.rehearsal:      # their room on the chip is not needed now
        for leaf in jax.tree.leaves((eng.pool.kv, served.params)):
            leaf.delete()
    t = time.monotonic()
    with jax.default_device(cpu):
        want = check.emitted_reference(cell, params, [probe])
    line["reference_s"] = time.monotonic() - t
    line.update(check.compare(cell, [float(x) for x in lps], want))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
