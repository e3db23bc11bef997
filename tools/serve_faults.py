#!/usr/bin/env python3
"""What a serving cell's reference comparison reads and what it is known to
fail, at one seed and with no measured window.

    chiprun -- python tools/serve_faults.py --workload commanda_plus_agent_16k --seed N
    chiprun -- python tools/serve_faults.py --workload brumby14b_longgen_closed --seed N
    chiprun -- python tools/serve_faults.py --workload gigachat35_reasoning_closed --seed N
    chiprun -- python tools/serve_faults.py --workload nemotron3_nano_chat_closed --seed N
    chiprun -- python tools/serve_faults.py --workload lfm2_24b_chat_closed --seed N
    chiprun -- python tools/serve_faults.py --workload sdar30b_chat_blocks_closed --seed N
    chiprun -- python tools/serve_faults.py --workload ouro26b_shortqa_closed --seed N
    chiprun -- python tools/serve_faults.py --workload axk2_docqa_32k_closed --seed N

As ``benchmark/control.py`` (which it follows line by line and cannot be a
part of: a ``model_config`` PR adds to the benchmark and edits none of its
files): builds the cell's weights, engine and server as a run does, streams
the cell's probes through the HTTP API, and holds to the cell's own limits
(``benchmark/lib/check.py`` ``compare``), one JSON line in all:

* ``program``: the log-probabilities the engine streamed against the float32
  reference: what a run's ``correct`` compares;
* ``control``: the reference computed in bfloat16 throughout against itself
  in float32 at the same prompts and tokens: the precision below the
  configuration's, which the limits must not pass;
* ``control_fp8`` (``--fp8``): the float32 reference on the weights ROUNDED
  to float8_e4m3 (every matrix under one scale of its own, its largest
  magnitude at the format's 240; vectors as given) against itself on the
  weights as given, at the same prompts and tokens: a precision truly below
  the configuration's, for a cell whose bfloat16 control reads as its
  program does (the K/V pool is freed first: the rounded copy of the
  weights takes its room);
* one entry a planted fault: the program against a reference that has the
  fault (the difference is the one an honest reference reads of a program
  with it).  The faults are the three choices the reference module exposes
  (``benchmark/reference/commanda_block.py``): window layers that see every
  key, rotation on the full layer, the shared experts summed and not
  averaged; and the three of ``benchmark/reference/brumby_block.py``: the
  gate ignored (no decay), degree 1, the normaliser dropped; and those of
  ``benchmark/reference/gigachat35_block.py``: a linear layer's decay
  ignored, ``beta`` = 1, its conv's tail dropped at a tick's boundary (128
  prompt rows), its output gate's scale 1 for 2 (which the after-norm
  cancels: it reads as ``program`` does, and says so), and the readings the
  config leaves to the modelling file flipped one by one (the gain ``1 +
  w``, no after-norms, no MLA scaling factor, the attention ungated, no
  clamp); and the nine of ``benchmark/reference/nemotron_h_block.py``: a
  Mamba-2 layer's decay ignored, ``dt`` without its softplus, every head
  reading group 0's B and C, the gated norm over all 4,096 values for the
  groups' 512, the conv's bias dropped, ``relu`` for ``relu^2``, the routed
  scaling factor 1 for 2.5, the shared expert left out, rotary embedding
  applied; and the eleven of ``benchmark/reference/lfm2_block.py``: a short
  conv's taps reversed, the conv reading one input ahead, the gate ``B``
  dropped, the gate ``C`` dropped, its tail dropped at a tick's boundary
  (256 prompt rows) and at every row (a decode row is a run of its
  own), the head norms on q and k left out, the rotary
  embedding left out, ``expert_bias`` used in the weight, the chosen
  scores' normalisation dropped, the two dense layers treated as expert
  layers; and those of ``benchmark/reference/sdar_block.py``: a causal
  mask in the place of the block-causal one, the commit pass left out (later
  blocks read the K/V of mask inputs), the logits shifted by one, a block's
  keys read from its final tokens before they are known, the rotary
  embedding at a row's mask position, and (shared with LFM2's) the head
  norms and the gates' normalisation left out; and those of
  ``benchmark/reference/ouro_block.py``: three passes of the stack for four,
  the norm between passes left out (the final norm after the last pass
  alone), a query of every pass reading the FIRST pass's keys (keys and
  values shared between passes: the cache-slot fault), and (shared with
  GigaChat's) the two norms after the sublayers left out; and the six of
  ``benchmark/reference/axk2_block.py``: half the keys picked (``index_topk``
  1,024 for 2,048), the indexer's scores without the ReLU, the gate a head
  left out, the norms' low-rank gates left out, the router's groups ignored,
  the indexer's rope dims rotated in pairs for halves.  A fault is tried
  where the cell's reference has its choice.

A limit of the configuration's ``tolerance`` lies between the ``program``
readings and the others over a dozen seeds; an entry other than ``program``
that reads ``reference_ok`` true is a limit too wide (the bfloat16
``control`` of a cell that says so in its ``tolerance.why`` apart).  Exits 2 off the TPU
(``--rehearsal 1``: the CPU at tiny widths, never a reading)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# fault -> (the reference's choice, what takes its place)
FAULTS = {
    "window_layers_see_every_key": ("window_of", lambda model, kind: None),
    "rotation_on_the_full_layer": ("rotates", lambda model, kind: True),
    "shared_experts_summed": ("shared_scale", lambda model: 1.0),
    "gate_ignored": ("log_decay", lambda gate, u: 0.0 * (
        u @ gate["kernel"].astype(u.dtype))),
    "degree_one": ("degree", lambda model: 1),
    "normaliser_dropped": ("normalised", lambda model: False),
    "delta_decay_ignored": ("delta_decay", lambda p, u, hv: 0.0 * (
        u @ p["ba"]["kernel"])[..., hv:]),
    "delta_beta_one": ("write_strength", lambda p, u, hv: 1.0 + 0.0 * (
        u @ p["ba"]["kernel"])[..., :hv]),
    "conv_tail_dropped_at_a_tick": ("conv_reset_every", lambda model: 128),
    "delta_gate_scale_one": ("output_gate_scale", lambda model: 1.0),
    "gain_one_plus_w": ("norm_gain", lambda w, model: 1.0 + w),
    "no_after_norms": ("post_norms", lambda model: False),
    "no_mla_scaling_factor": ("softmax_scale", lambda model: (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5),
    "attention_ungated": ("attention_gate", lambda p, u: 1.0),
    "no_swiglu_clamp": ("swiglu_limit", lambda model: None),
    "mamba_decay_ignored": ("decay", lambda a_log, dt: 1.0 + 0.0 * dt),
    "dt_without_softplus": ("step_size", lambda raw, bias: raw + bias),
    "every_head_reads_group_0": ("group_of", lambda heads, groups: [0] * heads),
    "norm_over_the_whole_width": ("norm_groups", lambda model: 1),
    "conv_bias_dropped": ("conv_bias", lambda bias: 0.0 * bias),
    "relu_for_relu_squared": ("expert_activation", lambda x: x * (x > 0)),
    "scaling_factor_one": ("routed_scale", lambda model: 1.0),
    "shared_expert_left_out": ("shared_weight", lambda model: 0.0),
    "rotary_applied": ("rotates_qk", lambda model: True),
    "conv_taps_reversed": ("conv_taps", lambda w: w[::-1]),
    "conv_acausal": ("conv_lookahead", lambda model: 1),
    "conv_gate_b_dropped": ("in_gate", lambda b, u: u),
    "conv_gate_c_dropped": ("out_gate", lambda cg, y: y),
    "conv_tail_dropped_at_a_run": ("conv_restarts_every", lambda model: 256),
    "conv_tail_never_carried": ("conv_restarts_every", lambda model: 1),
    "qk_norm_left_out": ("qk_normed", lambda model: False),
    "rope_left_out": ("rope_applied", lambda model: False),
    "expert_bias_in_the_weight": ("weighed_scores", lambda s, biased: biased),
    "gate_normalisation_dropped": ("gates_normalised", lambda model: False),
    "dense_layers_as_expert_layers": ("dense_layers", lambda model: 0),
    "causal_mask_for_block_causal": ("mask_end", lambda pos, model: pos + 1),
    "commit_pass_left_out": ("earlier_blocks_clean", lambda model: False),
    "logits_shifted_by_one": ("logits_shift", lambda model: 1),
    "block_keys_of_final_tokens": ("own_block_stream", lambda model: False),
    "rope_at_the_mask_position": ("rope_position", lambda pos, model: (
        pos // model["diffusion_block_length"] + 1)
        * model["diffusion_block_length"] - 1),
    "three_passes_for_four": ("passes",
                              lambda model: model["total_ut_steps"] - 1),
    "norm_between_passes_left_out": ("normed_between", lambda model: False),
    "queries_read_the_first_pass_keys": ("keys_pass", lambda t, model: 0),
    "half_the_keys_picked": ("index_topk_of",
                             lambda model: int(model["index_topk"]) // 2),
    "index_scores_without_relu": ("index_activation", lambda x: x),
    "head_gate_left_out": ("head_gate", lambda p, u: 1.0 + 0.0 * (
        u @ p["g_proj"]["kernel"])),
    "norm_gates_left_out": ("gated_norm", lambda x, p, model: x * (
        (x * x).mean(-1, keepdims=True) + model["rms_norm_eps"]) ** -0.5
        * p["scale"]),
    "router_groups_ignored": ("router_groups", lambda model: (1, 1)),
    "index_rope_in_pairs": ("rope_halves", lambda x, model: sys.modules[
        "benchmark.reference.axk2_block"].rope_pairs(x, model)),
}


def fp8_weights(params):
    """``params`` with every matrix rounded to float8_e4m3 (4 bits of
    exponent, 3 of mantissa to bfloat16's 8 and 7) under one scale a leaf,
    its largest magnitude at the format's largest (240): what a weight-only
    fp8 deployment holds.  Vectors (norm scales, biases) are left as given.
    ``lax.reduce_precision`` and not a pair of ``astype``: the compiler
    drops a conversion there and back (the first readings on the chip were
    0.0 to the last digit)."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        if a.ndim < 2:
            return a
        wide = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide)) / 240.0
        # a stack of zero biases (a LayerNorm's, a layer a row) has no scale
        low = jax.lax.reduce_precision(
            wide / jnp.where(scale > 0, scale, 1.0), 4, 3)
        return (low * scale).astype(a.dtype)

    return jax.tree.map(jax.jit(rounded), params)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--no-faults", action="store_true",
                    help="program and control only: a reading a seed")
    ap.add_argument("--fp8", action="store_true",
                    help="add control_fp8 (a cell with one pool of K/V pages)")
    args = ap.parse_args()
    args.rate, args.trace = None, 0
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark.lib import cells, check, serving
    from benchmark.reference import common
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    cell = cells.Cell(args.workload)
    device = jax.devices()[0]
    if device.platform != ("cpu" if args.rehearsal else "tpu"):
        print("serve_faults: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    served = serving.Served(cell, args)
    try:
        probes, hits = served.stream_probes(args.seed)
    finally:
        served.server.stop()
    ref = check.reference_module(cell)
    got = [lp for p in probes for lp in p["logprobs"]]
    in_use = (device.memory_stats() or {}).get("bytes_in_use")
    t = time.monotonic()
    want = check.emitted_reference(cell, served.params, probes)
    line = {"workload": cell.name, "seed": args.seed,
            "bytes_in_use_before_reference": in_use,
            "probe_lengths": [len(p["prompt"]) for p in probes],
            "probe_prefix_hit_tokens": hits,
            "program": check.compare(cell, got, want),
            "reference_s": time.monotonic() - t,
            "peak_bytes_in_use_after_reference":
                (device.memory_stats() or {}).get("peak_bytes_in_use"),
            "bytes_limit": (device.memory_stats() or {}).get("bytes_limit")}
    with mock.patch.object(common, "F32", jnp.bfloat16):
        low = check.emitted_reference(cell, served.params, probes)
    line["control"] = check.compare(cell, low, want)
    for name, (choice, fault) in FAULTS.items():
        if args.no_faults or not hasattr(ref, choice):
            continue
        # the reference traces its layer programs with the choice as it
        # stands (anew every call, or keyed by it: lfm2_block._traced_with)
        with mock.patch.object(ref, choice, fault):
            faulty = check.emitted_reference(cell, served.params, probes)
        line[name] = check.compare(cell, got, faulty)
    if args.fp8:    # last: the pool's room goes to the rounded weights
        for leaf in jax.tree.leaves(served.engine.pool.kv):
            leaf.delete()
        line["control_fp8"] = check.compare(cell, check.emitted_reference(
            cell, fp8_weights(served.params), probes), want)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
