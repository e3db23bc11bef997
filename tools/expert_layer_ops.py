"""One held-share expert layer on the chip, by operation.

Runs ``models/moe.py`` ``moe_sublayer`` of the LFM2 cell's configuration
(8 of 64 SwiGLU experts of 2048 x 1536 held, top-4, ``moe_capacity_factor``
8.0, bfloat16) at the cell's two tick shapes (512 and 256 rows), the way the
serving tick runs it: ``--layers`` layers in ONE scan over a stack of
``--stack`` layers' expert weights that the scan closes over
(``StackedExperts``: the grouped kernel is handed the whole stack as its
groups, as in the tick), the hidden rows carried from layer to layer.  One
traced call a shape; its device events are summed by the jax scope they lie
under and, with ``--ops N``, the N longest operations under each scope are
named.

    chiprun -- sh -c 'python tools/expert_layer_ops.py --root .tree/parent \
        --out chiprun_out/expert_layer/parent.txt;
        python tools/expert_layer_ops.py --out chiprun_out/expert_layer/change.txt'

``--root`` names the tree whose ``megatron_llm_tpu`` and
``benchmark/lib/trace.py`` are imported (default: this file's own), so a
parent unpacked beside the change runs under the same script, one process
a side (a chip belongs to one process at a time).  ``--skew`` sends every
row's first choice to a held expert: every row of the buffer is live.
Fails anywhere but on a TPU;
``--rehearsal`` walks the control flow on the CPU at tiny widths (exit 3).
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import sys
import tempfile

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--rows", type=int, nargs="+", default=[512, 256])
ap.add_argument("--stack", type=int, default=38,
                help="layers whose experts the stack holds (the cell's 38: "
                     "the grouped kernel is handed all of them as groups)")
ap.add_argument("--layers", type=int, default=8,
                help="layers of the stack that one call runs")
ap.add_argument("--ops", type=int, default=6)
ap.add_argument("--skew", action="store_true")
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--out", default=None,
                help="write the table here (chiprun_out/...), not to stdout")
ap.add_argument("--rehearsal", action="store_true",
                help="tiny widths on the CPU: the control flow, never a time")
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.root))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import trace as trace_lib  # noqa: E402
from megatron_llm_tpu.models import make_config, moe  # noqa: E402

SCOPES = ("moe/router", "moe/dispatch", "moe/expert_gemm", "moe/combine")


def build(stack: int, seed: int):
    """The configuration and a stack of ``stack`` layers' parameters: one
    layer's draw, scaled a little differently for every layer (a stack of
    separate draws does not fit beside itself while it is put together)."""
    tiny = dict(hidden_size=128, moe_ffn_hidden_size=128, kv_channels=16,
                num_attention_heads=8, vocab_size=512) if args.rehearsal else {}
    cfg = make_config("lfm2-24b-a2b", params_dtype="bfloat16",
                      moe_experts_held=8, moe_first_held_expert=0,
                      moe_capacity_factor=8.0, **tiny)
    scale = 1.0 + 0.002 * jnp.arange(stack, dtype=jnp.float32)

    @jax.jit
    def draw(key):
        return jax.tree.map(
            lambda a: (a[None] * scale.reshape((-1,) + (1,) * a.ndim)).astype(
                jnp.bfloat16), moe.init_moe_params(cfg, key))

    return cfg, draw(jax.random.PRNGKey(seed))


def layer_fn(cfg, layers, skew: bool):
    def run(stack, x):
        def body(h, layer):
            p = jax.tree.map(lambda a: a[layer], {
                k: v for k, v in stack.items() if k != "experts"})
            if skew:   # every row's first choice a held expert
                p["router"]["bias"] = p["router"]["bias"].at[:8].add(10.0)
            p["experts"] = moe.StackedExperts(stack["experts"], layer)
            out, aux = moe.moe_sublayer(cfg, p, h[None])
            return (h + out[0]).astype(h.dtype), aux
        return jax.lax.scan(body, x, layers)
    return jax.jit(run)


def traced(call):
    jax.block_until_ready(call())
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(call())
        path = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        red = trace_lib.reduce_file(path)
        return [(o.name, o.op_name, o.self_ns, o.text) for o in red.ops()]


def main():
    if args.out:      # the end of a call's output is all that comes back
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        sys.stdout = open(args.out, "w")
    dev = jax.devices()[0]
    print(f"tree: {os.path.abspath(args.root)}; backend: {dev.platform} "
          f"({dev.device_kind})", flush=True)
    if dev.platform != "tpu" and not args.rehearsal:
        print("FAIL not on a TPU: device times come from the chip")
        sys.exit(2)
    cfg, stack = build(args.stack, args.seed)
    # the layers run, spread over the stack
    layers = jnp.arange(args.layers) * (args.stack // args.layers)
    for rows in args.rows:
        x = jax.random.normal(jax.random.PRNGKey(args.seed + rows),
                              (rows, cfg.model.hidden_size), jnp.bfloat16)
        f = layer_fn(cfg, layers, args.skew)
        out, aux = f(stack, x)
        aux = [float(v) for v in aux.sum(0)]
        ops = traced(lambda: f(stack, x))
        total = sum(ns for _, _, ns, _ in ops)
        print(f"\n== {rows} rows x {args.layers} layers"
              f"{' (skewed)' if args.skew else ''}: {total / 1e3 / args.layers:.1f} "
              f"us a layer on the device; aux summed over layers "
              f"(assignments, touched, held, dropped, held touched"
              f"{', buffer rows run, whole' if len(aux) > 7 else ''}): "
              f"{aux[2:]}; out checksum "
              f"{float(jnp.abs(out.astype(jnp.float32)).sum()):.6e}",
              flush=True)
        by_scope = collections.defaultdict(list)
        for name, op_name, ns, text in ops:
            path = op_name + "/"
            scope = next((s for s in SCOPES if f"/{s}/" in path), None)
            if scope is None:
                scope = "moe (other)" if "/moe/" in path else "outside moe"
            by_scope[scope].append((ns, name, op_name, text))
        for scope in SCOPES + ("moe (other)", "outside moe"):
            evs = by_scope.get(scope, [])
            t = sum(e[0] for e in evs)
            print(f"  {scope:16s} {t / 1e3 / args.layers:8.1f} us a layer "
                  f"({len(evs) // max(args.layers, 1)} events a layer)")
            by_name = collections.defaultdict(lambda: [0, 0, "", ""])
            for ns, name, op_name, text in evs:
                e = by_name[name]
                e[0] += ns
                e[1] += 1
                e[2], e[3] = op_name, text
            longest = sorted(by_name.items(), key=lambda kv: -kv[1][0])
            for name, (ns, n, op_name, text) in longest[:args.ops]:
                shape = text.split(" = ")[1][:60] if " = " in text else ""
                print(f"      {ns / 1e3 / args.layers:8.1f} us  {name:34s} "
                      f"x{n / args.layers:g}  {trace_lib._short(op_name, 4)}"
                      f"  {shape}")
        conds = [ns for _, _, ns, text in ops if " conditional(" in text]
        if conds:
            print(f"  the conditionals' own time: "
                  f"{sum(conds) / 1e3 / args.layers:.1f} us a layer "
                  f"({len(conds) // args.layers} a layer)")
    if args.rehearsal:
        print("rehearsal: control flow only, no device time")
        sys.exit(3)


if __name__ == "__main__":
    main()
