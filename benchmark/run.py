#!/usr/bin/env python3
"""One run of one benchmark cell in a fresh process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix and per-layer metric readers by
the names in BENCHMARK.json (this file names none of them), builds weights
on the device from the seed, warms up the cell's shapes, measures for
``--seconds``, checks correctness against the plain reference outside the
window, and prints one JSON object as the last line of stdout.

Exits non-zero, printing no result, when jax finds no TPU or fewer chips
than the cell asks for.  ``--rehearsal N`` debugs the control flow on N
virtual CPU devices at tiny widths: its last line always says
``"correct": false`` and stamps the device ``cpu``; it is never a
measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()   # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, default=0, metavar="DEVICES",
                    help="CPU rehearsal on this many virtual devices, tiny "
                         "widths; never a measurement")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open-loop mix's rate (the knee sweep; "
                         "the result is then marked as a sweep point)")
    args = ap.parse_args()

    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.rehearsal}")

    from benchmark.lib import cells, harness

    cell = cells.Cell(args.workload)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.rehearsal else "tpu"
    if platform != want or len(devices) < cell.chips:
        print(f"benchmark: found {len(devices)} x {platform}; workload "
              f"{cell.name} needs {cell.chips} x {want}", file=sys.stderr)
        return 2

    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    cache = enable_compilation_cache()
    print(f"benchmark: {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} cache {cache}", flush=True)

    clock = harness.Clock(T_PROCESS_START)
    runner = cells.load_kind(cell.traffic["kind"])
    result = runner.run(cell, args, clock)
    line = harness.result_line(cell, args, result)
    for name, pair in line["compared"].items():     # the last lines of stderr
        print(f"benchmark: compared {name} {pair['value']} limit {pair['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
