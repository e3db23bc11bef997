"""Is a weight copied inside a serving tick?  Compile only, no chip.

Compiles the tick of one benchmark configuration (``--workload``, a serving
cell of BENCHMARK.json: the ragged tick, or the block tick of a model that
generates by diffusion over blocks) at the engine's own geometry, with
abstract parameters and an abstract pool, for one TPU v5e (the chip if this
process has one, else a virtual topology: libtpu compiles, nothing runs),
and prints every operation of the compiled program that MOVES at least
``--min_mb`` and computes nothing: an XLA ``copy``, or a fusion whose body
holds nothing but ``copy`` / ``slice`` / ``dynamic-slice`` / ``bitcast`` /
``transpose`` / ``reshape`` / ``constant`` (what
``benchmark/layer_metrics/copy_share.batch.py`` prices in a device trace). A
row an operation: its name, the parameter leaf it reads (by the operand's
shape), operand and result shapes with their layouts (``{minor_to_major}``;
the ``T(..)`` tiling as the compiler prints it), its consumers, and the
``op_name`` the trace would show.

    JAX_PLATFORMS=cpu python tools/tick_hlo_copies.py \
        --workload brumby14b_longgen_closed [--prefill_rows 64] \
        [--min_mb 1] [--dump /root/scratch/brumby.hlo]

``parse_hlo`` / ``moved`` work on any HLO text (``--hlo FILE`` reads one
instead of compiling): ``tests/test_tick_hlo_copies.py`` holds a canned
module.
"""

from __future__ import annotations

import argparse
import functools
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# opcodes that move or rename data and compute nothing
MOVERS = frozenset({
    "copy", "slice", "dynamic-slice", "bitcast", "transpose", "reshape",
    "constant", "parameter", "get-tuple-element", "tuple", "broadcast",
    "iota",
})
# ... of which these alone make a fusion worth a row (a fusion of
# constants and broadcasts fills, it does not move a weight)
MOVING = frozenset({"copy", "slice", "dynamic-slice", "transpose"})

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}


class Instr(NamedTuple):
    name: str
    shape: str            # "bf16[5120,7168]{1,0:T(8,128)(2,1)}" or a tuple
    opcode: str
    operands: Tuple[str, ...]
    calls: Optional[str]  # the fused computation / loop body
    op_name: str
    text: str

    @property
    def nbytes(self) -> int:
        return shape_bytes(self.shape)


def shape_bytes(shape: str) -> int:
    """Bytes of an array shape as HLO prints it; 0 for a tuple or token."""
    m = re.match(r"^(\w+)\[([0-9,]*)\]", shape)
    if not m or m.group(1) not in ITEMSIZE:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * ITEMSIZE[m.group(1)]


_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"^(\w+\[[^\]]*\](?:\{[^}]*\})?)\s*(.*)$")
_OPCODE = re.compile(r"^\s*([\w\-]+)\((.*)$")


def _split_operands(rest: str) -> Tuple[str, str]:
    """The text between the opcode's parentheses, and what follows."""
    depth = 1
    for i, c in enumerate(rest):
        depth += c in "([{"
        depth -= c in ")]}"
        if depth == 0:
            return rest[:i], rest[i + 1:]
    return rest, ""


def parse_hlo(text: str) -> Dict[str, List[Instr]]:
    """``{computation: [instructions]}`` of an HLO module's text (the
    compiled form: ``lowered.compile().as_text()``)."""
    comps: Dict[str, List[Instr]] = {}
    cur: Optional[List[Instr]] = None
    for line in text.splitlines():
        # a computation's head stands unindented: "%name (args) -> .. {"
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _NAME.match(line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):       # a tuple's shape, comments and all
            shape, rest = _split_operands(rest[1:])
            shape = "(" + shape + ")"
        else:
            m = _ARRAY.match(rest)
            if not m:
                continue
            shape, rest = m.groups()
        m = _OPCODE.match(rest)
        if not m:
            continue
        opcode, rest = m.groups()
        inside, after = _split_operands(rest)
        operands = tuple(re.findall(r"%([\w.\-]+)", inside)) or tuple(
            re.findall(r"(?:^|,\s*)([A-Za-z_][\w.\-]*)(?=\s*(?:,|$))", inside))
        calls = re.search(r"(?:calls|body)=%?([\w.\-]+)", after)
        op_name = re.search(r'op_name="([^"]*)"', after)
        cur.append(Instr(name, shape, opcode, operands,
                         calls.group(1) if calls else None,
                         op_name.group(1) if op_name else "", line.strip()))
    return comps


def _pure_move(comps, comp: str) -> bool:
    body = comps.get(comp, [])
    ops = {i.opcode for i in body}
    return bool(body) and ops <= MOVERS and bool(ops & MOVING)


class Moved(NamedTuple):
    comp: str
    instr: Instr
    kind: str                       # "copy" | "fusion(<opcodes>)"
    sources: Tuple[Tuple[str, str], ...]   # (operand name, its shape)
    consumers: Tuple[str, ...]      # "name opcode"


def moved(comps: Dict[str, List[Instr]], min_bytes: int = 1 << 20
          ) -> List[Moved]:
    """Every ``copy`` and every fusion of movers alone whose result holds
    at least ``min_bytes``, wherever it stands (entry, loop bodies)."""
    fused = {i.calls for body in comps.values() for i in body
             if i.opcode == "fusion" and i.calls}
    out = []
    for comp, body in comps.items():
        if comp in fused:
            continue
        by_name = {i.name: i for i in body}
        for i in body:
            if i.nbytes < min_bytes:
                continue
            if i.opcode == "copy":
                kind = "copy"
            elif i.opcode == "fusion" and i.calls and _pure_move(
                    comps, i.calls):
                kind = "fusion(" + ",".join(sorted(
                    {j.opcode for j in comps[i.calls]} & MOVING)) + ")"
            else:
                continue
            sources = tuple(
                (o, by_name[o].shape) for o in i.operands
                if o in by_name and by_name[o].nbytes >= min_bytes)
            consumers = tuple(
                f"{j.name} {j.opcode}" + (
                    f"[{_target(j)}]" if _target(j) else "")
                for j in body if i.name in j.operands)
            out.append(Moved(comp, i, kind, sources, consumers))
    return out


def _target(i: Instr) -> str:
    m = re.search(r'custom_call_target="([^"]*)"', i.text)
    if m:
        return m.group(1)
    m = re.search(r"kind=(k\w+)", i.text)
    return m.group(1) if m else ""


def _dims(shape: str) -> Tuple[int, ...]:
    m = re.match(r"^\w+\[([0-9,]*)\]", shape)
    return tuple(int(d) for d in filter(None, m.group(1).split(","))
                 ) if m else ()


def _dtype(shape: str) -> str:
    return shape.split("[", 1)[0]


def leaf_of(leaves: Dict[str, Tuple[str, Tuple[int, ...]]], shape: str
            ) -> str:
    """The parameter leaves a moved array (``shape`` as HLO prints it) may
    be: those of its dtype whose dims are its own, or its own behind a
    layer axis, or, for a stacked leaf, that hold as many elements a layer
    (a reshape or transpose of the layer's slice)."""
    def n(d):
        return functools.reduce(lambda a, b: a * b, d, 1)

    dims = _dims(shape)
    mine = {p: d for p, (dt, d) in leaves.items() if dt == _dtype(shape)}
    one = tuple(dims[next((i for i, d in enumerate(dims) if d != 1),
                          len(dims)):])       # a slice keeps its axis: [1, ..]
    exact = [p for p, d in mine.items() if d == dims or d[1:] in (dims, one)]
    if exact:
        return ", ".join(exact)
    loose = [p for p, d in mine.items() if len(d) > 2 and n(dims) == n(d[1:])]
    return ", ".join(p + " (by size)" for p in loose) or "-"


def report(text: str, leaves: Dict[str, Tuple[str, Tuple[int, ...]]],
           min_bytes: int) -> List[str]:
    comps = parse_hlo(text)
    rows = moved(comps, min_bytes)
    lines = [f"{len(rows)} moving operations of at least "
             f"{min_bytes / 2 ** 20:g} MiB, "
             f"{sum(r.instr.nbytes for r in rows) / 2 ** 20:.1f} MiB written "
             "in all (a loop body's count once an iteration)"]
    for r in sorted(rows, key=lambda r: -r.instr.nbytes):
        src = list(r.sources) or [(o, "?") for o in r.instr.operands[:1]]
        leaf = leaf_of(leaves, r.instr.shape)
        if leaf == "-" and src and src[0][1] != "?":
            leaf = leaf_of(leaves, src[0][1])
        lines.append(
            f"{r.instr.name} [{r.kind}] in {r.comp}\n"
            f"    {r.instr.nbytes / 2 ** 20:9.1f} MiB  leaf: {leaf}\n"
            f"    reads   " + "; ".join(f"{o} {s}" for o, s in src) + "\n"
            f"    writes  {r.instr.shape}\n"
            f"    read by " + ("; ".join(r.consumers) or "(the root)") + "\n"
            f"    op_name {r.instr.op_name or '-'}")
    return lines


# ---------------------------------------------------------------------------
# compiling a cell's tick
# ---------------------------------------------------------------------------


def _device():
    """One v5e: the chip where there is one, else a compile-only one."""
    import jax

    if jax.default_backend() == "tpu":
        return jax.devices()[0]
    import numpy as np
    from jax.experimental import topologies

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    return list(np.array(topo.devices).ravel())[0]


def compile_tick(workload: str, prefill_rows: Optional[int] = 0):
    """(compiled text, {leaf path: (dtype, shape)}, memory analysis) of the
    ragged tick of ``workload``'s configuration with ``prefill_rows`` prompt
    rows (0: the pure decode tick; None: the engine's cap), at the
    geometry ``ContinuousBatchingEngine`` derives from the same flags."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib.cells import Cell
    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation import engine as eng
    from megatron_llm_tpu.generation.placement import tables_in_rows
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.models.transformer import pool_classes

    cell = Cell(workload)
    cfg = parse_args(cell.flags())
    inf, m = cfg.inference, cfg.model
    mesh = build_mesh(devices=[_device()])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=repl)

    def abstract(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    # the engine's geometry (generation/engine.py __init__)
    slots, page = inf.max_batch_slots, inf.page_size
    max_seq = inf.engine_max_seq or min(cfg.data.seq_length,
                                        m.max_position_embeddings)
    chunk = inf.prefill_chunk
    cap = (max(chunk, int(inf.prefill_budget)) if inf.prefill_budget
           else eng._bucket_up(slots, chunk))
    pre = cap if prefill_rows is None else prefill_rows
    classes = pool_classes(cfg)
    state = all(cls.state for cls in classes)
    hybrid = not state and any(cls.state for cls in classes)
    width = 1 if state else -(-max_seq // page)
    num_pages = inf.kv_pool_pages or slots * width + 1
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cell.config.get("weights_dtype", "bfloat16")]

    def pool_of(make):
        return abstract(jax.eval_shape(lambda: make().kv))

    with global_mesh(mesh):
        if hybrid:
            # a page class and a state class (generation/engine.py)
            paged, st = classes
            pool = (
                pool_of(lambda: eng.PagedKVPool(
                    cfg, num_pages, page, layers=paged.layers(cfg),
                    page_class=paged.name)),
                pool_of(lambda: eng.StatePool(
                    cfg, slots, page, layers=st.layers(cfg),
                    page_class=st.name)))
            tables = lambda n: (S((n, width), jnp.int32),  # noqa: E731
                                S((n, 1), jnp.int32))
        elif len(classes) == 2:
            periods = m.num_layers // m.layer_period
            full, win = classes
            wcap = -(-int(win.window) // page) + 2 + cap // page
            pool = (
                pool_of(lambda: eng.PagedKVPool(
                    cfg, num_pages, page, layers=periods * len(full.places),
                    page_class=full.name)),
                pool_of(lambda: eng.PagedKVPool(
                    cfg, inf.kv_window_pool_pages
                    or slots * min(width, wcap) + 1, page,
                    layers=periods * len(win.places), page_class=win.name)))
            tables = lambda n: (S((n, width), jnp.int32),) * 2  # noqa: E731
        else:
            pool = pool_of(
                (lambda: eng.StatePool(cfg, slots, page)) if state
                else (lambda: eng.PagedKVPool(cfg, num_pages, page)))
            tables = lambda n: S((n, width), jnp.int32)  # noqa: E731
        params = jax.eval_shape(
            functools.partial(init_model_params, cfg), jax.random.PRNGKey(0))
        # the table in the layout the engine keeps it in
        params, = tables_in_rows(
            jax.tree.map(lambda a: S(a.shape, dtype), params))
        row = lambda dt, *tail: S((slots, *tail), dt)  # noqa: E731
        args = [params, pool, tables(slots), row(jnp.int32), row(jnp.int32),
                row(jnp.uint32, 2), row(jnp.int32), row(jnp.float32),
                row(jnp.int32), row(jnp.float32), row(jnp.int32),
                row(jnp.bool_)]
        if pre:
            args += [S((pre,), jnp.int32), S((pre,), jnp.int32),
                     tables(cap // chunk + 1), S((pre,), jnp.int32),
                     S((pre,), jnp.int32)]
        if m.diffusion_block_length:
            # the tick such a cell runs (generation/blocks.py)
            from tick_digest import block_tick_program

            tick, args = block_tick_program(
                cfg, pre, params, pool, tables, slots, chunk, S)
        else:
            tick = make_ragged_tick_fn(cfg, None, 0, pre, mesh=mesh)
        compiled = jax.jit(tick, donate_argnums=(1,)).lower(*args).compile()
    hlo_name = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
    leaves = {
        "/".join(str(getattr(k, "key", k)) for k in path):
        (hlo_name.get(str(a.dtype), str(a.dtype)), tuple(a.shape))
        for path, a in jax.tree_util.tree_flatten_with_path(params)[0]}
    return compiled.as_text(), leaves, compiled.memory_analysis()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a serving cell of BENCHMARK.json")
    ap.add_argument("--prefill_rows", type=int, default=0,
                    help="prompt rows of the tick (0: the decode tick; "
                         "-1: the engine's cap)")
    ap.add_argument("--min_mb", type=float, default=1.0)
    ap.add_argument("--dump", help="write the compiled HLO text here")
    ap.add_argument("--hlo", help="read this HLO text instead of compiling")
    args = ap.parse_args()
    leaves: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
    if args.hlo:
        with open(args.hlo) as f:
            text = f.read()
    else:
        text, leaves, mem = compile_tick(
            args.workload, None if args.prefill_rows < 0
            else args.prefill_rows)
        print(f"{args.workload}: prefill_rows {args.prefill_rows}, "
              f"temporaries {mem.temp_size_in_bytes / 2 ** 20:.1f} MiB, "
              f"arguments {mem.argument_size_in_bytes / 2 ** 20:.1f} MiB")
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            f.write(text)
    print("\n".join(report(text, leaves, int(args.min_mb * 2 ** 20))))


if __name__ == "__main__":
    main()
