#!/usr/bin/env python3
"""The sha256 of every serving cell's ragged tick, and of every train cell's
step, as it is LOWERED for a TPU (no chip: a virtual v5e topology, abstract
parameters, pools and optimizer state at the cell's own flags), Mosaic
payloads and all.

    JAX_PLATFORMS=cpu python tools/tick_digest.py [--root DIR] [--bodies]
                                                  [cell ...]

A compile cache finds a program again only if its lowered text is the same,
and a Pallas kernel's payload in that text carries the source lines of the
kernel AND of its callers (ten frames: models/transformer.py, models/moe.py,
models/language_model.py, ops/paged_attention.py).  A diagnostic: run the
tool on the parent (``--root`` a ``git archive`` of it) and on the change AT
ONE PATH (the payload carries file names too: a symbolic link turned from
one tree to the other, and ``--root`` the link), and compare the lines.  One
line a serving cell and prompt-row shape, ``<cell> rows=<n> <sha256>``, and
one a train cell, ``<cell> train_step <sha256>`` (``make_jitted_train_step``
on the batch the cell's mix makes).
A line that differs costs that cell one cold compile on the change's side
(PERF.md section 6, PRs 52 and 53).

The tool lowers ``make_ragged_tick_fn`` and ``make_jitted_train_step``
itself, so no frame of ``generation/engine.py`` or ``training.py`` is on
its stack.  The frames are kept from the kernel outwards, ten of them, so a
kernel nine frames under ``tick`` (the retention and the gated-delta sweeps)
carries the tick's CALLER as its tenth.  In a serving process that caller is
``generation/launch.py`` ``call_tick`` (PR 61: the engine and the block
driver launch through it, so that no line of ``generation/engine.py`` is in a
payload), and the tool lowers a serving tick through the same call: such a
payload names ``generation/launch.py`` here as it does there, and would name
THIS file only if a frame beyond the helper's reached it.  The last two lines
say so: ``payload files: ...`` (every source file a payload names) and
``callers: <cells whose payloads name the tick's caller>; launch.py calls
tick_fn at <line:col-line:col>``.  They too have to be equal on parent and
change: a line added above that call in ``generation/launch.py`` makes those
cells' ticks new programs.  No train cell's payload reaches its caller.

``--bodies`` answers another question, whether an edit changed a KERNEL: the
programs are lowered with no frame in a location
(``jax_traceback_in_locations_limit`` 0), so a payload is the kernel's module
and nothing of where it stands or who called it, and every line ends in
``kernels <sha256, 16 digits, of each distinct payload>``.  Those are equal on
parent and change where the kernels' bodies are, whatever moved around them
(PERF.md section 6, PR 60); the lines' own sha256 is then not the cache's."""

from __future__ import annotations

import argparse
import ast
import base64
import functools
import hashlib
import json
import os
import re
import sys


def lowered_text(tick, *operands) -> str:
    """The tick as lowered with its pools donated, through the call the
    serving engine makes of it (``generation/launch.py``): the text a
    compile cache's key is made of."""
    import jax

    from megatron_llm_tpu.generation.launch import call_tick

    return call_tick(jax.jit(tick, donate_argnums=(1,)).lower,
                     *operands).as_text()


def payloads(text: str) -> list:
    """The Mosaic payloads of a lowered program (``backend_config``'s
    ``body``: base64 of the kernel's module, locations and all)."""
    return [base64.b64decode(body) for body in re.findall(
        r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)]


def payload_files(text: str) -> set:
    """The source files named inside a lowered program's payloads."""
    return {m.decode() for body in payloads(text)
            for m in re.findall(rb"[\w/.\-]+\.py", body)}


def payload_digests(text: str) -> str:
    """`` kernels <digest> ...``: the distinct payloads of a lowered
    program, sixteen digits of each one's sha256."""
    return " kernels " + " ".join(sorted({
        hashlib.sha256(body).hexdigest()[:16] for body in payloads(text)}))


LAUNCH = os.path.join("megatron_llm_tpu", "generation", "launch.py")


def launch_call_sites(root: str) -> list:
    """Where ``generation/launch.py`` calls a tick program
    (``tick_fn(...)``), as the location a payload would carry:
    ``line:col-line:col``."""
    path = os.path.join(root, LAUNCH)
    with open(path) as f:
        tree = ast.parse(f.read())
    return [f"{n.lineno}:{n.col_offset}-{n.end_lineno}:{n.end_col_offset}"
            for n in ast.walk(tree) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name) and n.func.id == "tick_fn"]


def train_step_text(cell, mesh) -> str:
    """A train cell's step as ``training.pretrain`` builds it for the
    benchmark (``benchmark/lib/kind_train.py``: the mix's sequence length
    and micro-batch, one chip), lowered on abstract state."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.core.parallel_state import global_mesh
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.optimizer.optimizer import get_optimizer
    from megatron_llm_tpu.training_step import make_jitted_train_step

    mix = cell.traffic
    seq, mbs = int(mix["seq_length"]), int(mix["micro_batch_size"])
    gbs = mbs * int(mix.get("micro_batches_per_step", 1))
    cfg = parse_args(cell.flags(dict(
        seq_length=seq, micro_batch_size=mbs, global_batch_size=gbs,
        train_iters=10 ** 9, eval_iters=0, eval_interval=10 ** 9,
        log_interval=10 ** 9)), n_devices=1)
    with global_mesh(mesh):
        params = jax.eval_shape(functools.partial(
            init_model_params, cfg), jax.random.PRNGKey(0))
        opt = get_optimizer(cfg, params)
        opt_state = jax.eval_shape(opt.init, params)
        step, _, _ = make_jitted_train_step(
            cfg, mesh, params, optimizer=opt, opt_state=opt_state)
        batch = {k: jax.ShapeDtypeStruct((gbs, seq), t) for k, t in (
            ("tokens", jnp.int32), ("labels", jnp.int32),
            ("loss_mask", jnp.float32), ("position_ids", jnp.int32))}
        return step.lower(params, opt_state, batch,
                          jax.ShapeDtypeStruct((), jnp.int32)).as_text()


def block_tick_program(cfg, rows: int, params, pool, tables, slots: int,
                       chunk: int, S):
    """``(tick, operands)``: the tick of a model that generates by
    diffusion over blocks (``generation/blocks.py``, in the ragged tick's
    place) and its own abstract operands (``tools/tick_hlo_copies.py``
    compiles the same)."""
    import jax.numpy as jnp

    from megatron_llm_tpu.generation.blocks import (
        BlockState,
        Unmasking,
        make_block_tick_fn,
    )

    B = cfg.model.diffusion_block_length
    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    state = BlockState(S((slots,), i32), S((slots, B), i32),
                       S((slots, B), flag), S((slots, B), i32),
                       S((slots,), flag), S((slots,), i32),
                       S((slots,), flag), S((slots,), i32))
    un = Unmasking(S((slots,), f32), S((slots,), i32), S((slots,), f32),
                   S((slots,), i32), S((slots,), i32), S((slots,), f32))
    pre = (S((rows,), i32), S((rows,), i32), tables(rows // chunk + 1),
           S((rows,), i32)) if rows else ()
    return make_block_tick_fn(cfg, rows), (
        params, pool, tables(slots), state, S((slots,), flag), state,
        S((slots, 2), jnp.uint32), un, *pre)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--bodies", action="store_true",
                    help="lower with no frame in a location and print "
                         "each line's distinct kernel payloads' digests")
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib import cells as cells_mod
    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.generation.pools import PagedKVPool, StatePool
    from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.models.transformer import pool_classes

    try:
        from megatron_llm_tpu.generation.placement import tables_in_rows
    except ImportError:
        # ``--root`` a tree from before PR 64: its engine takes the
        # tables as they lie
        def tables_in_rows(*trees):
            return trees

    if args.bodies:
        jax.config.update("jax_traceback_in_locations_limit", 0)
    bodies = payload_digests if args.bodies else lambda text: ""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = build_mesh(devices=list(np.array(topo.devices).ravel())[:1])
    repl = NamedSharding(mesh, P())

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    named, reach_caller = set(), []
    # the tick's caller in a serving process, and a train step's here
    launch, me = os.path.join(root, LAUNCH), os.path.abspath(__file__)
    for entry in bench["workloads"]:
        name = entry["name"]
        if args.cells and name not in args.cells:
            continue
        cell = cells_mod.Cell(name, root)
        if cell.traffic["kind"] == "train":
            text = train_step_text(cell, mesh)
            named |= payload_files(text)
            if me in payload_files(text):
                reach_caller.append(name)
            print(f"{name} train_step "
                  f"{hashlib.sha256(text.encode()).hexdigest()}"
                  f"{bodies(text)}", flush=True)
            continue
        cfg = parse_args(cell.flags())
        inf = cfg.inference
        slots, page = inf.max_batch_slots, inf.page_size
        chunk = inf.prefill_chunk
        width = (inf.engine_max_seq or cfg.data.seq_length) // page
        classes = pool_classes(cfg)
        with global_mesh(mesh):
            params = jax.eval_shape(functools.partial(
                init_model_params, cfg), jax.random.PRNGKey(0))
            # the table in the layout the engine keeps it in
            params, = tables_in_rows(jax.tree.map(
                lambda a: S(a.shape, jnp.bfloat16), params))
            pools, widths = [], []
            many = len(classes) > 1
            for cls in classes:
                make = (lambda cls=cls: StatePool(
                    cfg, slots, page, layers=cls.layers(cfg)).kv) \
                    if cls.state else (lambda cls=cls: PagedKVPool(
                        cfg, slots * 8 + 1, page,
                        layers=cls.layers(cfg) if many else None).kv)
                pools.append(jax.tree.map(
                    lambda a: S(a.shape, a.dtype), jax.eval_shape(make)))
                widths.append(1 if cls.state else width)
            pool = tuple(pools) if many else pools[0]

            def tables(n):
                made = tuple(S((n, w), jnp.int32) for w in widths)
                return made if many else made[0]

            cap = -(-slots // chunk) * chunk
            for rows in (0, cap):
                if cfg.model.diffusion_block_length:
                    tick, operands = block_tick_program(
                        cfg, rows, params, pool, tables, slots, chunk, S)
                    text = lowered_text(tick, *operands)
                else:
                    tick = make_ragged_tick_fn(cfg, None, 0, rows, mesh=mesh)
                    pre = (S((rows,), jnp.int32), S((rows,), jnp.int32),
                           tables(rows // chunk + 1), S((rows,), jnp.int32),
                           S((rows,), jnp.int32)) if rows else ()
                    text = lowered_text(
                        tick, params, pool, tables(slots),
                        S((slots,), jnp.int32), S((slots,), jnp.int32),
                        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
                        S((slots,), jnp.float32), S((slots,), jnp.int32),
                        S((slots,), jnp.float32), S((slots,), jnp.int32),
                        S((slots,), jnp.bool_), *pre)
                named |= payload_files(text)
                if (launch in payload_files(text)
                        and name not in reach_caller):
                    reach_caller.append(name)
                print(f"{name} rows={rows} "
                      f"{hashlib.sha256(text.encode()).hexdigest()}"
                      f"{bodies(text)}", flush=True)
    print("payload files: " + " ".join(sorted(
        os.path.relpath(f, root) if os.path.isabs(f) else f for f in named)),
        flush=True)
    print(f"callers: {' '.join(reach_caller) or 'none'}; launch.py calls "
          f"tick_fn at {' '.join(launch_call_sites(root))}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
