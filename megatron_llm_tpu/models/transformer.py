"""The transformer stack — TPU-native redesign of megatron/model/transformer.py.

Differences from the reference (transformer.py:77-1347), by design:

* **Functional, not stateful**: parameters are a nested-dict pytree; the
  forward is a pure function — required for jit/pjit/shard_map/checkpoint.
* **Layers are stacked and scanned** (``lax.scan``) instead of a Python
  module list (transformer.py:1331-1337): one compiled block regardless of
  depth, which keeps XLA compile time flat at 80 layers.
* **GQA without K/V expansion**: the reference broadcast-expands K/V heads
  (transformer.py:459-466); we keep K/V at n_kv_heads and group queries.
* **Fused QKV projection** sized ``kv_channels * (n_heads + 2*n_kv_heads)``
  with *group-major* layout — for each KV head: its G query heads, then K,
  then V.  This matches the reference's interleaved qkv convention
  (transformer.py:325-343, weights_conversion/utils/permute_qkv.py) and makes
  TP sharding a clean split over KV groups.
* **Activation recompute** is ``jax.checkpoint`` with a policy, not an RNG
  state-juggling reimplementation (random.py:175-245): functional PRNG makes
  recompute-identical dropout automatic.

Layer params schema (one layer; stacked on axis 0 when scanned):

    {'input_norm':  {'scale': [h], 'bias'?: [h]},
     'attention':   {'qkv':   {'kernel': [h, (n+2*nkv)*d], 'bias'?},
                     'dense': {'kernel': [n*d, h],          'bias'?}},
     'post_norm':   {...},    # absent when parallel_attn
     'mlp_norm':    {...},    # Falcon-40B parallel_layernorm only
     'mlp':         {'fc1': {'kernel': [h, ffn*(2 if glu else 1)], 'bias'?},
                     'fc2': {'kernel': [ffn, h],                   'bias'?}}}

Latent attention (``attention_type == 'mla'``, :func:`mla_sublayer`) has
its own ``'attention'`` subtree, n heads of nope + rope query dims:

    {'q_down':  {'kernel': [h, q_lora_rank]},   'q_norm':  {'scale'},
     'q_up':    {'kernel': [q_lora_rank, n*(nope+rope)]},
     'kv_down': {'kernel': [h, kv_lora_rank + rope]}, 'kv_norm': {'scale'},
     'kv_up':   {'kernel': [kv_lora_rank, n, nope + v]},
     'dense':   {'kernel': [n*v, h]}}

Power retention (``attention_type == 'retention'``,
:func:`retention_sublayer`) keeps the fused ``qkv`` and ``dense`` and adds
``q_norm`` / ``k_norm`` (``{'scale': [d]}``, one RMSNorm a head, before
RoPE) and ``gate`` (``{'kernel': [h, nkv], 'bias': [nkv]}``).

The gated delta rule (a LINEAR layer, :func:`delta_sublayer`; the mixer
of ``linear_layout``'s layers) has its own ``'attention'`` subtree, ``hk``
key heads of ``dk`` and ``hv`` value heads of ``dv``:

    {'qkvz':  {'kernel': [h, 2*hk*dk + 2*hv*dv]},      # q | k | v | z
     'ba':    {'kernel': [h, 2*hv]},                   # beta | a
     'conv':  {'kernel': [width, 2*hk*dk + hv*dv]},    # depthwise, causal
     'a_log': [hv], 'dt_bias': [hv],
     'o_norm': {'weight': [dv]},
     'dense': {'kernel': [hv*dv, h]}}

A HYBRID model (``linear_layout``) keeps the two kinds of mixer apart from
the scanned stack, whose other leaves stay uniform: ``params["mixers"] =
{'attention': [layers of that kind, ...], 'delta': [...]}``, each stacked
over its own layers in order, and :func:`layer_stacks` hands them to the
stack as its ``'attention'`` subtree.

A model with ``dense_prefix_layers`` holds two stacks: ``dense_layers``
(the prefix, dense MLP) and ``layers`` (the scanned expert layers).  A stack
of ONE-SUBLAYER layers (``sublayer_pattern``: a layer is ``x + Mixer(Norm(
x))``, the mixer a Mamba-2 block, an expert block or an attention block) is
models/sublayers.py's: :func:`layer_kinds`, :func:`pool_classes` and
:func:`transformer_forward` hand it over.

The LAYER PATTERN is owned here (:class:`LayerKind`, :func:`layer_kinds`):
every layer has the same leaves, so the tree stays the uniform ``[L, ...]``
stack, and what differs between kinds of layer (the keys a query may see,
whether q and k rotate) is static data of the layer's place in its period.
:func:`transformer_forward` scans over PERIODS with the period's layers
unrolled in the body, so each kernel gets its static window and nothing
chooses between kernels at run time; a uniform model is a period of one.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.core import rng as rng_mod
from megatron_llm_tpu.ops import attention as attn_ops
from megatron_llm_tpu.ops.activations import get_mlp_activation, glu_product
from megatron_llm_tpu.ops.norms import init_norm_params, norm
from megatron_llm_tpu.ops.rope import apply_rotary_emb

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype=jnp.float32):
    return std * jax.random.normal(key, shape, dtype=dtype)


def init_layer_params(cfg, key: jax.Array, cross_attention: bool = False,
                      dense_ffn: bool = False) -> Params:
    """One layer of the scanned stack; ``dense_ffn`` makes a layer of the
    dense prefix instead (a dense MLP whatever ``num_experts`` says)."""
    m = cfg.model
    h = m.hidden_size
    d = m.kv_channels
    n, nkv = m.num_attention_heads, m.num_attention_heads_kv
    ffn = m.ffn_hidden_size
    glu = m.glu_activation is not None
    std = m.init_method_std
    # scaled init for output projections: std / sqrt(2 * num_layers)
    # (reference model/utils.py scaled_init_method_normal)
    out_std = std / (2.0 * m.num_layers) ** 0.5 if m.use_scaled_init_method else std

    k = jax.random.split(key, 7)
    # a plain norm's leaves, or a gated norm's (A.X-K2): the file's end
    new_norm = _norm_maker(cfg, key)
    p: Params = {"input_norm": new_norm()}
    if m.sublayer_pattern:   # its ONE sublayer is ``params["mixers"]``'s
        return p
    if dense_ffn and m.dense_prefix_linear:
        p["attention"] = init_mixer_params(cfg, k[0], k[1], "delta")
    elif not m.linear_layout or dense_ffn:
        p["attention"] = init_mixer_params(cfg, k[0], k[1], "attention")
    # else: a hybrid's scanned stack, whose mixers are stacks of their own
    # (``params["mixers"]``, :func:`init_mixers`)
    if m.post_sublayer_norms:
        p["attn_out_norm"], p["mlp_out_norm"] = new_norm(), new_norm()
    if m.retention:
        p["attention"].update(_init_retention_params(cfg, k[4]))
    if m.num_experts is not None and not dense_ffn:
        # MoE layer: router + expert FFN stack replaces the dense MLP
        # (beyond-reference — see models/moe.py)
        from megatron_llm_tpu.models.moe import init_moe_params

        p["moe"] = init_moe_params(cfg, jax.random.fold_in(k[2], 0))
    else:
        # GLU fc1 is [h, 2, ffn] (value half at [:,0,:], gated half at
        # [:,1,:]) so a tp sharding on the ffn axis never splits across
        # the gate/value boundary — the flat reference layout would force
        # a resharding at the chunk-2 split under GSPMD.  Drawn by
        # :func:`init_mlp_params` (at the file's end: a one-sublayer
        # stack's dense MLP is the same subtree, models/sublayers.py);
        # the call keeps this function's lines where they were
        p["mlp"] = init_mlp_params(cfg, k[2], k[3])
    if not m.parallel_attn:
        p["post_norm"] = new_norm()
    if m.parallel_layernorm:
        p["mlp_norm"] = new_norm()
    if cross_attention:
        # T5 decoder inter-attention (reference t5_model.py via
        # ParallelAttention attn_type=cross, transformer.py:280): separate Q
        # and fused-KV projections over the encoder output.
        p["cross_attention"] = {
            "q": {"kernel": _normal(k[4], (h, n * d), std)},
            "kv": {"kernel": _normal(k[5], (h, 2 * nkv * d), std)},
            "dense": {"kernel": _normal(k[6], (n * d, h), out_std)},
        }
        p["cross_norm"] = init_norm_params(h, m.use_rms_norm)
        if m.use_bias:
            p["cross_attention"]["q"]["bias"] = jnp.zeros((n * d,), jnp.float32)
            p["cross_attention"]["kv"]["bias"] = jnp.zeros((2 * nkv * d,), jnp.float32)
            p["cross_attention"]["dense"]["bias"] = jnp.zeros((h,), jnp.float32)
    assert not (m.mla and (m.use_bias or m.add_qkv_bias)), (
        "latent attention has no biases")
    if m.use_bias or m.add_qkv_bias:
        # add_qkv_bias: Qwen2-style QKV-only bias (dense/mlp stay bias-free)
        p["attention"]["qkv"]["bias"] = jnp.zeros(((n + 2 * nkv) * d,), jnp.float32)
    if m.use_bias:
        p["attention"]["dense"]["bias"] = jnp.zeros((h,), jnp.float32)
        if "mlp" in p:
            p["mlp"]["fc1"]["bias"] = jnp.zeros((2, ffn) if glu else (ffn,), jnp.float32)
            p["mlp"]["fc2"]["bias"] = jnp.zeros((h,), jnp.float32)
    return p


def init_mixer_params(cfg, k_in: jax.Array, k_out: jax.Array,
                      mixer: str) -> Params:
    """One layer's ``'attention'`` subtree: the model's ``attention_type``
    (``mixer`` 'attention') or the gated delta rule's ('delta')."""
    m = cfg.model
    h, std = m.hidden_size, m.init_method_std
    out_std = std / (2.0 * m.num_layers) ** 0.5 if m.use_scaled_init_method else std
    if mixer == "delta":
        return _init_delta_params(cfg, k_in, k_out, out_std)
    if m.mla:
        return _init_mla_params(cfg, k_in, k_out, out_std)
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    return {
        "qkv": {"kernel": _normal(k_in, (h, (n + 2 * nkv) * d), std)},
        "dense": {"kernel": _normal(k_out, (n * d, h), out_std)},
        **_init_head_norms(cfg)}


def init_mixers(cfg, key: jax.Array) -> Params:
    """A hybrid's mixers of the scanned stack, a stack a kind over that
    kind's layers in order: ``{'attention': [...], 'delta': [...]}``.  How
    many follows ``scanned_periods`` (Config.finalize), not ``num_layers``:
    the count is the model's, whatever a caller that builds the uniform
    stack a layer at a time makes of a copy's ``num_layers``."""
    m = cfg.model
    out = {}
    for j, mixer in enumerate(("attention", "delta")):
        count = m.scanned_periods * sum(
            1 for kind in layer_kinds(cfg) if kind.mixer == mixer)
        out[mixer] = jax.vmap(
            lambda kk, mixer=mixer: init_mixer_params(
                cfg, *jax.random.split(kk), mixer))(
            jax.random.split(jax.random.fold_in(key, j), count))
    return out


# under the bias alone a linear layer's state loses per token what leaves
# a key GATE_HORIZON tokens back between GATE_KEEPS of its weight
DELTA_A_RANGE = (1.0, 16.0)
# the standard deviation at which q, k and v leave the conv and enter SiLU
DELTA_CONV_OUT_STD = 0.1


def _init_delta_params(cfg, k_in: jax.Array, k_out: jax.Array,
                       out_std: float) -> Params:
    """``a_log`` and ``dt_bias`` are DRAWN (as the retention gate's bias
    is): with plain draws a state forgets within ~20 tokens and no fault
    in carrying it across ticks would show.

    The conv's filter is DRAWN too, so that q, k and v enter SiLU at a
    standard deviation of ``DELTA_CONV_OUT_STD``, where SiLU is nearly
    ``x / 2``.  At a filter of ``1 / sqrt(width)`` they enter at ~1.7 and
    leave with SiLU's positive mean on every channel: any key then sides
    with any query, a long state reads back the plain average of its
    values, and the layer hands EVERY token of every sequence nearly the
    same vector (a third of the energy of what a router four layers on
    reads, at the 432B widths).  A router so fed sends a tick's rows to
    the same experts, which no model whose load was balanced does, and
    which experts they are follows the seed."""
    m = cfg.model
    h, std = m.hidden_size, m.init_method_std
    hk, hv = m.linear_num_key_heads, m.linear_num_value_heads
    dk, dv = m.linear_key_head_dim, m.linear_value_head_dim
    qk, vz = hk * dk, hv * dv
    k1, k2, k3, k4, k5 = jax.random.split(k_in, 5)
    lo, hi = (-jnp.log(keep) / GATE_HORIZON for keep in GATE_KEEPS[::-1])
    rate = jnp.exp(jax.random.uniform(k4, (hv,), jnp.float32,
                                      jnp.log(lo), jnp.log(hi)))
    a = jax.random.uniform(k5, (hv,), jnp.float32, *DELTA_A_RANGE)
    return {
        "qkvz": {"kernel": _normal(k1, (h, 2 * qk + 2 * vz), std)},
        "ba": {"kernel": _normal(k2, (h, 2 * hv), std)},
        # a depthwise filter of ``width`` taps over a normed row through
        # ``qkvz``, which has a deviation of std * sqrt(h) a channel
        "conv": {"kernel": _normal(
            k3, (m.linear_conv_kernel_dim, 2 * qk + vz),
            DELTA_CONV_OUT_STD
            / (std * (h * m.linear_conv_kernel_dim) ** 0.5))},
        "a_log": jnp.log(a),
        "dt_bias": jnp.log(jnp.expm1(rate / a)),
        "o_norm": {"weight": jnp.zeros((dv,), jnp.float32)},
        "dense": {"kernel": _normal(k_out, (vz, h), out_std)},
    }


def _init_mla_params(cfg, k_down: jax.Array, k_out: jax.Array,
                     out_std: float) -> Params:
    m = cfg.model
    h, n, std = m.hidden_size, m.num_attention_heads, m.init_method_std
    nope, rope, v = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    kq, kkv, kqu, kkvu = jax.random.split(k_down, 4)
    # the output gate's leaf (a value a head and channel, or a head) and
    # the indexer's, where the model has them: the file's end
    gate = _mla_extras(cfg, k_down)
    return {
        **gate,
        "q_down": {"kernel": _normal(kq, (h, m.q_lora_rank), std)},
        "q_norm": init_norm_params(m.q_lora_rank, True, gain=m.norm_gain),
        "q_up": {"kernel": _normal(kqu, (m.q_lora_rank, n * (nope + rope)),
                                   std)},
        "kv_down": {"kernel": _normal(kkv, (h, m.kv_lora_rank + rope), std)},
        "kv_norm": init_norm_params(m.kv_lora_rank, True, gain=m.norm_gain),
        # per head [k_nope | v]: the up-projection the absorbed form folds
        # into the query (its first nope columns) and the output (the rest)
        "kv_up": {"kernel": _normal(kkvu, (m.kv_lora_rank, n, nope + v),
                                    std)},
        "dense": {"kernel": _normal(k_out, (n * v, h), out_std)},
    }


# a key this many tokens back keeps between these shares of its weight
# under the gate's bias alone (log sigmoid(b) ~ -exp(-b)): what a freshly
# initialised retention layer remembers
GATE_HORIZON = 2048
GATE_KEEPS = (0.1, 0.9)


def _init_retention_params(cfg, key: jax.Array) -> Params:
    """The head norms and the gate.  The gate's bias is DRAWN so that the
    gates sit near 1: with a zero bias a random gate halves the state at
    every token, nothing older than ~20 tokens weighs anything, and a
    fault in carrying the state across ticks would change no output."""
    m = cfg.model
    d, nkv = m.kv_channels, m.num_attention_heads_kv
    k_w, k_b = jax.random.split(key)
    lo, hi = (-jnp.log(-jnp.log(keep) / GATE_HORIZON) for keep in GATE_KEEPS)
    return {
        "q_norm": init_norm_params(d, True),
        "k_norm": init_norm_params(d, True),
        "gate": {"kernel": _normal(k_w, (m.hidden_size, nkv),
                                   m.init_method_std),
                 "bias": jax.random.uniform(k_b, (nkv,), jnp.float32,
                                            lo, hi)},
    }


def init_stacked_layers(cfg, key: jax.Array, num_layers: Optional[int] = None,
                        cross_attention: bool = False,
                        dense_ffn: bool = False) -> Params:
    """Stack per-layer params on axis 0 (for lax.scan / per-stage pipelines)."""
    L = num_layers if num_layers is not None else cfg.model.num_layers
    keys = jax.random.split(key, L)
    return jax.vmap(
        lambda kk: init_layer_params(cfg, kk, cross_attention=cross_attention,
                                     dense_ffn=dense_ffn)
    )(keys)


# ---------------------------------------------------------------------------
# Sublayers
# ---------------------------------------------------------------------------


class StackedLinear(NamedTuple):
    """A dense projection of every layer of a stack (its ``kernel`` ``[L,
    in, out]`` or GLU ``[L, in, 2, out]``, or the int8 pair, and its
    ``bias``) and the layer whose projection is meant: what the serving
    tick hands :func:`_linear` instead of a scanned slice
    (``transformer_forward``)."""

    stack: Params
    layer: jax.Array


# the projections every sublayer runs through ``_linear``: the leaves the
# serving tick reads from the stack (the others' kernels -- a router, a
# retention gate, MLA's ``kv_up`` -- are read by name and ride the scan)
TICK_LINEARS = ("qkv", "dense", "fc1", "fc2", "q_down", "q_up", "kv_down",
                "g_proj", "qkvz", "in_proj")


def _is_linear(node) -> bool:
    return isinstance(node, dict) and ("kernel" in node or "kernel_q" in node)


def _take_linears(tree: Params) -> Tuple[Params, Params]:
    """(the dense projections of a stacked-layer tree, at their places;
    the tree without them).  The routed experts' GEMMs are grouped ones
    with a stack of their own (models/moe.StackedExperts)."""
    taken, rest = {}, {}
    for name, node in tree.items():
        if name in TICK_LINEARS and _is_linear(node):
            taken[name] = node
        elif isinstance(node, dict) and name != "experts":
            sub, left = _take_linears(node)
            if sub:
                taken[name] = sub
            rest[name] = left
        else:
            rest[name] = node
    return taken, rest


def _put_linears(layer_params: Params, linears: Params,
                 layer: jax.Array) -> Params:
    """A layer's params with every projection of ``linears`` at its place
    as ``StackedLinear(stack, layer)``."""
    out = dict(layer_params)
    for name, node in linears.items():
        if _is_linear(node):
            out[name] = StackedLinear(node, layer)
        else:
            out[name] = _put_linears(layer_params.get(name, {}), node, layer)
    return out


def _reads_pairs(x: jax.Array, leaf: jax.Array) -> bool:
    """Whether a GLU ``fc1`` stack goes to the kernel that reads it in
    place (ops/pallas/stacked_linear.py); said once while tracing, as the
    attention paths are."""
    from megatron_llm_tpu.core import parallel_state
    from megatron_llm_tpu.ops.pallas import stacked_linear

    target = parallel_state.target_platform()
    why = stacked_linear.refusal(x, leaf)
    if target != "tpu":
        why = f"target platform is {target}"
    elif (parallel_state.mesh_is_initialized()
          and parallel_state.get_global_mesh().size > 1):
        # a pallas_call is one device's program: GSPMD cannot partition it
        # over a tp-sharded stack, so a mesh keeps XLA's slice and re-layout
        why = "the stack is sharded over a mesh"
    attn_ops.announce_path("glu_fc1_stack", "xla" if why else "pallas",
                           why or "")
    return why is None


def _stacked_linear(p: StackedLinear, x: jax.Array) -> jax.Array:
    """``_linear`` against layer ``p.layer`` of a stack, the weights read
    where they lie (PERF.md section 6, PR 42: what the compiled tick does
    with each form).  One rule, two readings of the operand by its shape:

    * ``[L, in, out]``: XLA's own dot fuses the ``dynamic-slice`` of the
      stack into its read; what made it copy the slice out first (and
      transposed) was the consumer's reshape of the OUTPUT (the q/k/v
      split), which it folds into the dot as extra weight dimensions that
      the stored tiling does not have.  The barrier keeps the split behind
      the GEMM: the rows' product is materialised (a few hundred rows), the
      weight is not.
    * GLU ``[L, in, 2, out]``: the stored tiling (pairs packed in a word,
      the contraction axis outside the tile) is no GEMM operand, so XLA
      writes the layer out again in one that is; the pair kernel reads the
      stack and splits the words in registers.  Off the TPU, under a mesh,
      for int8 leaves and odd widths: the slice and XLA's re-layout, as
      before."""
    stack, layer = p
    quant = "kernel_q" in stack
    leaf = stack["kernel_q" if quant else "kernel"]

    def at(a):
        return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)

    if leaf.ndim == 4 and not quant and _reads_pairs(x, leaf):
        from megatron_llm_tpu.ops.pallas.stacked_linear import glu_stack_matmul

        y = glu_stack_matmul(x.reshape(-1, x.shape[-1]), leaf, layer)
        y = y.reshape(*x.shape[:-1], *leaf.shape[2:])
    else:
        kernel = at(leaf).astype(x.dtype)
        y = jax.lax.optimization_barrier(
            x @ kernel.reshape(kernel.shape[0], -1))
        y = y.reshape(*y.shape[:-1], *kernel.shape[1:])
    if quant:
        y = y * at(stack["kernel_scale"]).astype(y.dtype)
    if "bias" in stack:
        y = y + at(stack["bias"]).astype(x.dtype)
    return y


def _linear(p: Params, x: jax.Array) -> jax.Array:
    if isinstance(p, StackedLinear):
        return _stacked_linear(p, x)
    # weight-only int8 support (the shared quantized-leaf contract,
    # ops/quant.py:resolve_kernel): HBM reads int8, the convert fuses into
    # the GEMM; the per-channel scale applies to the output (after the GLU
    # chunk-axis restore)
    from megatron_llm_tpu.ops.quant import resolve_kernel

    kernel, scale = resolve_kernel(p, x.dtype)
    if kernel.ndim == 3:
        # GLU fc1 [h, 2, ffn]: flatten for one GEMM, restore the chunk axis
        # (same contract as ops/fp8.fp8_linear)
        y = x @ kernel.reshape(kernel.shape[0], -1)
        y = y.reshape(*y.shape[:-1], *kernel.shape[1:])
    else:
        y = x @ kernel
    if scale is not None:
        y = y * scale.astype(y.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def _linear_impl(cfg):
    """The projection implementation for this config: plain bf16/fp32
    matmul, or fp8 GEMMs when cfg.model.fp8 is set (ops/fp8.py — the
    TransformerEngine-path analog; embedding/logits/softmax stay in high
    precision exactly as TE keeps them out of fp8)."""
    from megatron_llm_tpu.ops.fp8 import linear_for_config

    return linear_for_config(cfg) or _linear


def split_qkv(
    qkv: jax.Array, n_heads: int, n_kv_heads: int, head_dim: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split group-major fused QKV [..., (n+2*nkv)*d] into q/k/v head tensors."""
    g = n_heads // n_kv_heads
    *lead, _ = qkv.shape
    grouped = qkv.reshape(*lead, n_kv_heads, g + 2, head_dim)
    q = grouped[..., :g, :].reshape(*lead, n_heads, head_dim)
    k = grouped[..., g, :]
    v = grouped[..., g + 1, :]
    return q, k, v


class LayerKind(NamedTuple):
    """What a kind of layer does in its attention: ``window`` keys a causal
    query may see (None = all of them), whether q and k ``rotate``, and its
    ``mixer``: 'attention' (the model's ``attention_type``) or 'delta' (a
    linear layer: the gated delta rule on a recurrent state); in a stack of
    one-sublayer layers (models/sublayers.py) the layer's ONE sublayer:
    'attention', 'mamba', 'experts', 'conv' (a short convolution), 'mlp'."""

    window: Optional[int]
    rotate: bool
    mixer: str = "attention"

    @property
    def scope(self) -> str:
        """The named scope of this kind's attention in a patterned stack
        (``attention/global``, ``attention/window``)."""
        return "global" if self.window is None else "window"


def layer_kinds(cfg) -> Tuple[LayerKind, ...]:
    """One period of the layer pattern: layer ``l`` of the scanned stack is
    of kind ``layer_kinds(cfg)[l % period]``.  A uniform model has one
    kind: its window if it has one, rotation wherever it is handed a rope
    table.  A stack of one-sublayer layers is ONE period, the whole order."""
    m = cfg.model
    if m.sublayer_pattern:
        return _sublayers().sublayer_kinds(cfg)
    if m.linear_layout is not None:
        return tuple(LayerKind(None, True, "delta" if lin else "attention")
                     for lin in m.linear_layout)
    if m.sliding_window_layout is None:
        return (LayerKind(m.sliding_window_size, True),)
    return tuple(
        LayerKind(m.sliding_window_size if w else None, bool(r))
        for w, r in zip(m.sliding_window_layout, m.rope_layout))


def stack_kinds(cfg, first_layer: int) -> Tuple[LayerKind, ...]:
    """One period of the stack that starts at the model's layer
    ``first_layer``: the dense prefix is its own stack with its own kinds
    (``dense_prefix_linear``: linear layers with a dense MLP), the scanned
    stack has :func:`layer_kinds`."""
    if _linear_prefix(cfg, first_layer):
        return (LayerKind(None, True, "delta"),)
    return layer_kinds(cfg)


def _linear_prefix(cfg, first_layer: int) -> bool:
    """Whether the stack that starts at ``first_layer`` is a hybrid's dense
    prefix of linear layers."""
    m = cfg.model
    return m.dense_prefix_linear and first_layer < m.dense_prefix_layers


class PoolClass(NamedTuple):
    """One page class of the paged pool: the layers of a period that need
    the same keys kept (``window`` of them; None = every key) and so share
    pages, a leaf and a block table.  ``state``: the class keeps no keys
    but one recurrent state a sequence (power retention), its leaf indexed
    by state slot and its table one entry wide."""

    window: Optional[int]
    places: Tuple[int, ...]   # places in the period, in order
    state: bool = False
    prefix: int = 0           # layers of the dense prefix, before those

    @property
    def name(self) -> str:
        if self.state:
            return "state"
        return "full" if self.window is None else "window"

    def layers(self, cfg) -> int:
        """Layers of the model that keep their memory in this class."""
        m = cfg.model
        return self.prefix + m.num_layers // m.layer_period * len(self.places)


def pool_classes(cfg) -> Tuple[PoolClass, ...]:
    """The page classes of the serving pool: the distinct cache needs of
    :func:`layer_kinds`, the class that keeps every key first.  A model
    whose layers all need the same keys has ONE class, which keeps every
    page a sequence wrote (a uniform window's pool does not slide).  A
    hybrid has a page class (its attention layers' latent rows) AND a
    state class (its linear layers', the dense prefix's first); a stack of
    one-sublayer layers a K/V page class and a state class (Mamba-2)."""
    kinds = layer_kinds(cfg)
    m = cfg.model
    if m.sublayer_pattern:
        return _sublayers().sublayer_pool_classes(cfg)
    if m.delta:
        places = lambda mixer: tuple(                  # noqa: E731
            j for j, k in enumerate(kinds) if k.mixer == mixer)
        lin = m.dense_prefix_layers * m.dense_prefix_linear
        return (PoolClass(None, places("attention"),
                          prefix=m.dense_prefix_layers - lin),
                PoolClass(None, places("delta"), True, prefix=lin))
    windows = sorted({k.window for k in kinds},
                     key=lambda w: (w is not None, w))
    if len(windows) == 1:
        return (PoolClass(None, tuple(range(len(kinds))),
                          bool(cfg.model.retention)),)
    return tuple(
        PoolClass(w, tuple(j for j, k in enumerate(kinds) if k.window == w))
        for w in windows)


def _uniform_kind(cfg) -> LayerKind:
    kinds = layer_kinds(cfg)
    assert len(kinds) == 1, (
        "a layer of a patterned stack must be told its kind "
        "(transformer_forward does; block_forward(kind=...))")
    return kinds[0]


@jax.named_scope("attention")  # a region a device trace can price
def attention_sublayer(
    cfg,
    p: Params,
    x: jax.Array,  # [b, s, h] (post input-norm)
    rope: Optional[Tuple[jax.Array, jax.Array]],
    position_ids: Optional[jax.Array],
    segment_ids: Optional[jax.Array],
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    token_idx: Optional[jax.Array] = None,
    attn_bias: Optional[jax.Array] = None,
    paged=None,
    kind: Optional[LayerKind] = None,
):
    """ParallelAttention analog (transformer.py:280-657).

    ``kind`` is the layer's kind (its window, whether it rotates); None
    means the one kind of a uniform model.

    ``paged`` (ops/paged_attention.PagedState) switches the incremental-decode
    branch to the block-table page pool: ``kv_cache`` is then a
    :class:`LayerPool` — the whole layered pool and this layer's index —
    and each row writes/attends at its own position in this layer's pages,
    in place: the continuous-batching engine's fused tick.

    Returns (output [b, s, h], new_kv_cache).
    """
    m = cfg.model
    b, s, _ = x.shape
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    kind = kind or _uniform_kind(cfg)
    # a patterned stack names its kinds, so that a device trace can tell
    # the window layers' kernels from the global layers'
    with (jax.named_scope(kind.scope) if m.layer_period > 1
          else contextlib.nullcontext()):
        return _attention(cfg, p, x, rope, position_ids, segment_ids,
                          dropout_key, deterministic, kv_cache, cache_index,
                          token_idx, attn_bias, paged, kind)


def _attention(cfg, p, x, rope, position_ids, segment_ids, dropout_key,
               deterministic, kv_cache, cache_index, token_idx, attn_bias,
               paged, kind: LayerKind):
    m = cfg.model
    b, s, _ = x.shape
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels

    from megatron_llm_tpu.parallel.tp import (
        apply_column_parallel,
        apply_row_parallel,
    )

    linear = _linear_impl(cfg)
    qkv = apply_column_parallel(cfg, p["qkv"], x, linear)
    q, k, v = _head_normed(cfg, p, *split_qkv(qkv, n, nkv, d))

    if rope is not None and kind.rotate:
        cos, sin = rope
        q = apply_rotary_emb(q, cos, sin, position_ids)
        k = apply_rotary_emb(k, cos, sin, position_ids)

    # apply_query_key_layer_scaling (reference CoreAttention:158-176) divides
    # QK^T by layer_number and multiplies back inside an fp32 softmax purely to
    # avoid fp16 overflow — a mathematical identity. Our softmax is always
    # computed in fp32 (attention.py softmax_fp32), so the flag needs no code.
    scale = 1.0 / (d ** 0.5)

    new_cache = None
    if paged is not None:
        # Continuous-batching paged path. s == 1 is the decode tick: one
        # token per row, each at its own position. s > 1 is a prefill CHUNK:
        # the block of tokens occupies positions positions[b] ..
        # positions[b] + s - 1 of each row. Either way: write k/v through
        # the block table, then attend over the block table
        # (ops/paged_attention.py). Inactive slots' block tables point at
        # the reserved null page 0, so their writes land in garbage that is
        # never attended.
        from megatron_llm_tpu.ops import kv_quant
        from megatron_llm_tpu.ops.paged_attention import (
            paged_attention_decode,
            paged_attention_prefill,
            paged_attention_ragged,
        )

        pool, layer = kv_cache
        page_size = kv_quant.page_size_of(pool)
        pos = paged.positions
        # ragged compressed tables (ISSUE 11): block_tables holds the
        # tick's UNIQUE tables and table_index maps rows onto them; the
        # K/V write needs per-row tables, a [rows, pages] int gather
        row_tables = paged.block_tables
        if paged.table_index is not None:
            row_tables = row_tables[paged.table_index]
        wpos = _write_pos(paged)[:, None] + jnp.arange(s)[None, :]  # [b, s]
        # clip: idle slots' device-side positions keep advancing between
        # engine re-uploads, and a chunk's garbage padding rows may run past
        # the table; clipped lookups resolve to null-page (or
        # decode-overwritten) entries, so the stray writes are never attended
        page_slot = jnp.clip(wpos // page_size, 0,
                             row_tables.shape[1] - 1)
        page_ids = jnp.take_along_axis(row_tables, page_slot, axis=1)
        offs = wpos % page_size
        # ONE scatter of whole rows, a head's key and value side by side
        # (ops/kv_quant.py owns the row), into this layer's pages of the
        # flat pool; quantized pools (--kv_dtype int8/fp8): page-granular
        # quantizing write with per-page, per-head scales
        pool = kv_quant.paged_write(
            pool, page_ids, offs, kv_quant.pack_kv(k, v), layer)
        new_cache = pool
        if s == 1 and paged.horizons is not None:
            # ragged tick (ISSUE 11): one launch for a mixed
            # decode/verify/prefill row batch; each row carries its own
            # data-carried kv horizon (0 = dead padding row) and an index
            # into the tick's unique block tables
            ctx = paged_attention_ragged(
                q, pool, paged.block_tables, paged.table_index, pos,
                paged.horizons,
                scale=scale, sliding_window=kind.window,
                use_kernel=cfg.training.use_flash_attn, layer=layer,
                walks=paged.walks,
            )
        elif s == 1:
            ctx = paged_attention_decode(
                q, pool, paged.block_tables, pos, scale=scale,
                sliding_window=kind.window,
                use_kernel=cfg.training.use_flash_attn, layer=layer,
            )
        else:
            ctx = paged_attention_prefill(
                q, pool, paged.block_tables, pos, scale=scale,
                sliding_window=kind.window,
                use_kernel=cfg.training.use_flash_attn, layer=layer,
            )
    elif kv_cache is not None:
        # Incremental decode: write current k/v at cache_index, attend to the
        # full cache prefix (InferenceParams semantics, text_generation/
        # forward_step.py:17 + transformer.py:413-506).
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
        new_cache = (ck, cv)
        kv_len = ck.shape[1]
        q_pos = cache_index + jnp.arange(s)[:, None]
        kv_pos = jnp.arange(kv_len)[None, :]
        allowed = q_pos >= kv_pos
        if kind.window is not None:
            allowed &= q_pos - kv_pos < kind.window
        bias = jnp.where(allowed, 0.0, attn_ops.NEG_INF).astype(jnp.float32)[None, None]
        ctx = attn_ops.xla_attention(q, ck, cv, bias=bias, scale=scale)
    else:
        ctx = attn_ops.attention(
            q, k, v,
            causal=not m.bidirectional and not m.diffusion_block_length,
            sliding_window=kind.window,
            segment_ids=segment_ids,
            token_idx=token_idx,
            bias=_block_bias(m, attn_bias, s),
            scale=scale,
            use_flash=cfg.training.use_flash_attn,
            dropout_rate=0.0 if deterministic else m.attention_dropout,
            dropout_key=dropout_key,
            zigzag=cfg.parallel.cp_zigzag,
        )

    # named so remat policies can save the attention output and skip
    # recomputing the (custom-vjp) flash kernel forward in the backward pass
    from jax.ad_checkpoint import checkpoint_name

    ctx = checkpoint_name(ctx, "attn_out")
    out = apply_row_parallel(cfg, p["dense"], ctx.reshape(b, s, n * d),
                             linear)
    return out, new_cache


class LayerPool(NamedTuple):
    """The whole paged pool ``[layers, pages, page, row]`` (a K/V pool or a
    latent one; ops/kv_quant.py owns the row) and the layer of it this
    sublayer writes and reads: the pool rides the layer scan's carry and
    every layer updates its own pages in place (a scan over stacked slices
    copies the whole pool out and back each tick)."""

    pool: Any
    layer: jax.Array


@jax.named_scope("attention")
def mla_sublayer(cfg, p: Params, x: jax.Array, rope, position_ids,
                 segment_ids, kv_cache=None, paged=None):
    """Multi-head latent attention (DeepSeek-V2/V3; JoyAI-LLM-Flash).

    Per token: ``c_q = RMSNorm(x W_dq)``; per head ``[q_nope | q_rope] =
    c_q W_uq``; ``[c_kv | k_rope] = x W_dkv``, ``c_kv <- RMSNorm(c_kv)``;
    RoPE on each head's ``q_rope`` and on ``k_rope``, which all heads
    share.  Two forms of the same numbers:

    * **expanded** (no cache: the trainer, the dense forward):
      ``[k_nope_h | v_h] = c_kv W_ukv`` per head, keys ``[k_nope_h |
      k_rope]``, plain causal attention with qk width nope + rope and v
      width ``v_head_dim`` on the XLA path (the flash kernel is built for
      equal widths and is not asked);
    * **absorbed** (``paged``: every row of the engine's tick):
      ``q~_h = q_nope_h W_uk_h^T``, scores ``(q~_h . c_kv + q_rope_h .
      k_rope) / sqrt(nope + rope)`` against the cached row itself, ``u_h =
      softmax_h c_kv``, ``out_h = u_h W_uv_h``.  The cache holds ``[c_kv |
      k_rope]`` after norm and RoPE and nothing else: ONE key of
      ``latent_cache_width`` values a token that every head reads, whose
      first ``kv_lora_rank`` values are also the value (the paged kernel's
      single-KV-head path, ``ops/paged_attention.py``).

    Returns (output [b, s, h], the updated pool or None).
    """
    from megatron_llm_tpu.parallel.tp import (
        apply_column_parallel,
        apply_row_parallel,
    )

    m = cfg.model
    b, s, _ = x.shape
    n, r = m.num_attention_heads, m.kv_lora_rank
    nope, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    eps = m.layernorm_epsilon
    linear = _linear_impl(cfg)
    scale = 1.0 / ((nope + rd) ** 0.5)
    if m.rope_scaling_type == "yarn":
        from megatron_llm_tpu.ops.rope import yarn_mscale

        scale *= yarn_mscale(m.rope_scaling_factor,
                             m.rope_yarn_mscale_all_dim) ** 2

    with jax.named_scope("mla"):
        c_q = norm(linear(p["q_down"], x), p["q_norm"], eps, True)
        q = apply_column_parallel(cfg, p["q_up"], c_q, linear)
        q = q.reshape(b, s, n, nope + rd)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        ckv = linear(p["kv_down"], x)
        c_kv = norm(ckv[..., :r], p["kv_norm"], eps, True)
        k_rope = ckv[..., None, r:]                       # [b, s, 1, rd]
        cos, sin = rope
        q_rope = apply_rotary_emb(q_rope, cos, sin, position_ids)
        k_rope = apply_rotary_emb(k_rope, cos, sin, position_ids)
        w_ukv = p["kv_up"]["kernel"].astype(x.dtype)      # [r, n, nope + vd]

        new_pool = None
        if paged is not None:
            ctx, new_pool = _mla_rows(cfg, p, x, c_q, rope, position_ids)(
                cfg, q_nope, q_rope, c_kv, k_rope[:, :, 0], w_ukv, kv_cache,
                paged, scale)
        else:
            assert kv_cache is None, (
                "latent attention decodes through the paged pool only: the "
                "dense incremental cache holds K/V heads")
            kv = jnp.einsum("bsr,rnd->bsnd", c_kv, w_ukv)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (b, s, n, rd))], axis=-1)
            qf = jnp.concatenate([q_nope, q_rope], axis=-1)
            # unequal qk / v widths: attention() keeps this off the flash
            # kernel and off the ring, and says so
            ctx = attn_ops.attention(qf, k, kv[..., nope:], **_mla_mask(
                cfg, p, x, c_q, rope, position_ids, segment_ids), causal=True,
                scale=scale, use_flash=cfg.training.use_flash_attn)
    from jax.ad_checkpoint import checkpoint_name

    ctx = checkpoint_name(ctx, "attn_out").reshape(b, s, n * vd)
    if m.attention_output_gate:
        # a value a head and channel (the G1 form of arXiv:2505.06708) or
        # ONE a head (attention_gate_headwise), from the layer's normed input
        with jax.named_scope("mla"):
            ctx = ctx * _head_wide(m, vd, jax.nn.sigmoid(
                linear(p["g_proj"], x).astype(jnp.float32)).astype(ctx.dtype))
    out = apply_row_parallel(cfg, p["dense"], ctx, linear)
    return out, new_pool


def _mla_paged(cfg, q_nope, q_rope, c_kv, k_rope, w_ukv, cache: LayerPool,
               paged, scale):
    """The absorbed form against the latent pool.  Every fed token is one
    row at its own position, whatever the call's shape (a tick's ``[R, 1]``
    rows, a scoring chunk's ``[b, s]``): write the rows' ``[c_kv | k_rope]``
    through the block table into this layer's slice of the pool, then one
    ragged paged attention with the pool as key AND value."""
    from megatron_llm_tpu.ops import kv_quant
    from megatron_llm_tpu.ops.paged_attention import paged_attention_ragged

    m = cfg.model
    b, s, n, nope = q_nope.shape
    r = m.kv_lora_rank
    pool, layer = cache
    page_size, width = pool.shape[2:]
    rows = b * s
    pos = (paged.positions[:, None] + jnp.arange(s)[None, :]).reshape(rows)
    if paged.table_index is not None:
        tables, index = paged.block_tables, paged.table_index
        assert s == 1
    else:
        tables = paged.block_tables
        index = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
    row_tables = tables[index]                                  # [rows, W]
    # clip: as the K/V pair's write (attention_sublayer), stray rows land
    # in pages that are never attended
    page_slot = jnp.clip(pos // page_size, 0, row_tables.shape[1] - 1)
    page_ids = jnp.take_along_axis(row_tables, page_slot[:, None], axis=1)
    latent = jnp.concatenate([c_kv, k_rope], axis=-1).reshape(rows, 1, 1, -1)
    pad = width - latent.shape[-1]          # whole 128-lane rows (the pool)
    if pad:
        latent = jnp.pad(latent, ((0, 0),) * 3 + ((0, pad),))
    # this layer's pages of the flat pool, in place: no slice of a layer
    pool = kv_quant.paged_write(
        pool, page_ids, (pos % page_size)[:, None], latent, layer)
    q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_ukv[..., :nope])
    q_abs = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(rows, 1, n, -1)
    if pad:
        q_abs = jnp.pad(q_abs, ((0, 0),) * 3 + ((0, pad),))
    horizons = paged.horizons if paged.horizons is not None else pos + 1
    u = paged_attention_ragged(
        q_abs, pool, tables, index, pos, horizons, scale=scale,
        use_kernel=cfg.training.use_flash_attn, layer=layer, latent=True,
        walks=paged.walks if paged.table_index is not None else None)
    ctx = jnp.einsum("bsnr,rnd->bsnd", u[..., :r].reshape(b, s, n, r),
                     w_ukv[..., nope:])
    return ctx, pool



@jax.named_scope("attention")
def retention_sublayer(cfg, p: Params, x: jax.Array, rope, position_ids,
                       kv_cache=None, paged=None):
    """Gated degree-2 power retention (ops/retention.py) in place of
    softmax attention: ``q, k <- RoPE(RMSNorm_d(.))`` a head, ``l =
    log sigmoid(x W_g + b_g)`` a KV head, then

    * no cache (the trainer, the dense forward): the chunked form from a
      zero state, differentiable;
    * ``paged`` (every row of the engine's tick): ``kv_cache`` is a
      :class:`LayerPool` over the STATE pool (``ops/retention.State``
      leaves ``[layers, slots + 1, nkv, ...]``, float32), a row's table
      holds its state SLOT (0: a dead row), and the tick's rows sweep the
      pool once: a run of rows of one sequence reads its slot's state
      once and writes it once (the Pallas kernel on a TPU target, else
      ``retention_tick``).

    Returns (output [b, s, h], the updated pool or None)."""
    from megatron_llm_tpu.ops import retention as ret
    from megatron_llm_tpu.parallel.tp import (
        apply_column_parallel,
        apply_row_parallel,
    )

    m = cfg.model
    b, s, _ = x.shape
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    linear = _linear_impl(cfg)
    with jax.named_scope("retention"):
        q, k, v = split_qkv(
            apply_column_parallel(cfg, p["qkv"], x, linear), n, nkv, d)
        eps = m.layernorm_epsilon
        q = norm(q.astype(jnp.float32), p["q_norm"], eps, True)
        k = norm(k.astype(jnp.float32), p["k_norm"], eps, True)
        cos, sin = rope
        q = apply_rotary_emb(q, cos, sin, position_ids)
        k = apply_rotary_emb(k, cos, sin, position_ids)
        log_decay = jax.nn.log_sigmoid(
            x.astype(jnp.float32) @ p["gate"]["kernel"].astype(jnp.float32)
            + p["gate"]["bias"].astype(jnp.float32))           # [b, s, nkv]
        new_pool = None
        if paged is not None:
            assert s == 1 and paged.table_index is not None, (
                "power retention is served by the ragged tick alone: one "
                "row a token, its state slot in its table")
            pool, layer = kv_cache
            slots = paged.block_tables[paged.table_index, 0]
            ctx, new_pool = _retention_sweep(cfg)(
                q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0], pool, slots,
                paged.positions, layer)
            ctx = ctx[:, None]
        else:
            assert kv_cache is None, (
                "power retention decodes through the engine's state pool "
                "only: the dense incremental cache holds K/V heads")
            ctx = ret.retention_chunked(q, k, v, log_decay)
    from jax.ad_checkpoint import checkpoint_name

    ctx = checkpoint_name(ctx.astype(x.dtype), "attn_out")
    out = apply_row_parallel(cfg, p["dense"], ctx.reshape(b, s, n * d),
                             linear)
    return out, new_pool


def _retention_sweep(cfg):
    """The tick's state sweep: the Pallas kernel where the program is
    compiled for a TPU and the feature rows are whole lanes, else the
    ``jnp`` form; said once while tracing, as the attention paths are."""
    from megatron_llm_tpu.core.parallel_state import target_platform
    from megatron_llm_tpu.ops import retention as ret
    from megatron_llm_tpu.ops.pallas.retention import retention_sweep

    target = target_platform()
    refusal = None
    if not cfg.training.use_flash_attn:
        refusal = "use_flash_attn is off"
    elif target != "tpu":
        refusal = f"target platform is {target}"
    elif ret.feature_dim(cfg.model.kv_channels) % 128:
        refusal = (f"head_dim {cfg.model.kv_channels}: its feature rows "
                   "are not whole 128-lane groups")
    attn_ops.announce_path("retention_tick", "jnp" if refusal else "pallas",
                           refusal or "")
    return retention_sweep if refusal is None else ret.retention_tick


@jax.named_scope("attention")
def delta_sublayer(cfg, p: Params, x: jax.Array, kv_cache=None, paged=None):
    """The gated delta rule (ops/gated_delta.py) as a layer's mixer:
    ``[q | k | v] = SiLU(conv(x W_qkv))`` (a causal depthwise convolution),
    ``z = x W_z``, ``beta = sigmoid(x W_b)``, ``g = -exp(a_log) *
    softplus(x W_a + dt_bias)`` in float32; q and k L2-normalised a head,
    q times ``dk^-0.5``; the rule; then a head's output normed and gated
    by ``z`` (``ops/norms.gated_head_norm``) and projected out.

    * no cache (the dense forward): the convolution from zeros and the
      chunked form from a zero state, differentiable;
    * ``paged`` (every row of the engine's tick): ``kv_cache`` is a
      :class:`LayerPool` over the STATE pool (``ops/gated_delta.DeltaState``
      leaves ``[layers, slots + 1, ...]``, float32), a row's table holds its
      state SLOT (0: a dead row), and the tick's rows sweep the pool once:
      a run of rows of one sequence reads its slot's state and conv tail
      once and writes them once (the Pallas kernel ``delta_sweep`` on a TPU
      target, else ``delta_tick``).

    Returns (output [b, s, h], the updated pool or None)."""
    from megatron_llm_tpu.ops import gated_delta as gd
    from megatron_llm_tpu.ops.norms import gated_head_norm
    from megatron_llm_tpu.parallel.tp import apply_row_parallel

    m = cfg.model
    b, s, _ = x.shape
    hk, hv = m.linear_num_key_heads, m.linear_num_value_heads
    dk, dv = m.linear_key_head_dim, m.linear_value_head_dim
    qk, vz = hk * dk, hv * dv
    linear = _linear_impl(cfg)
    f32 = jnp.float32
    with jax.named_scope("delta"):
        qkvz = linear(p["qkvz"], x)
        mixed, z = qkvz[..., :2 * qk + vz], qkvz[..., 2 * qk + vz:]
        ba = x.astype(f32) @ p["ba"]["kernel"].astype(f32)     # [b, s, 2hv]
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(f32))
        w_conv = p["conv"]["kernel"]
        new_pool = None
        if paged is not None:
            assert s == 1 and paged.table_index is not None, (
                "a linear layer is served by the ragged tick alone: one "
                "row a token, its state slot in its table")
            pool, layer = kv_cache
            slots = paged.block_tables[paged.table_index, 0]
            mixed, tails = gd.conv_tick(
                mixed[:, 0], w_conv, pool.conv, slots, paged.positions,
                layer * pool.s.shape[1])
            mixed = mixed[:, None]
        else:
            assert kv_cache is None, (
                "a linear layer decodes through the engine's state pool "
                "only: the dense incremental cache holds K/V heads")
            mixed = gd.causal_conv(mixed, w_conv)
        mixed = jax.nn.silu(mixed)
        q = gd.l2_normalize(mixed[..., :qk].reshape(b, s, hk, dk)) \
            * dk ** -0.5
        k = gd.l2_normalize(mixed[..., qk:2 * qk].reshape(b, s, hk, dk))
        v = mixed[..., 2 * qk:].reshape(b, s, hv, dv)
        if paged is not None:
            o, states = _delta_sweep(cfg)(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], pool.s,
                slots, paged.positions, layer)
            o, new_pool = o[:, None], gd.DeltaState(states, tails)
        else:
            o = gd.delta_chunked(q, k, v, g, beta)
        o = gated_head_norm(o, z.reshape(b, s, hv, dv), p["o_norm"]["weight"],
                            m.layernorm_epsilon)
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o.astype(x.dtype), "attn_out")
    with jax.named_scope("delta"):
        out = apply_row_parallel(cfg, p["dense"], o.reshape(b, s, vz), linear)
    return out, new_pool


def _delta_sweep(cfg):
    """The tick's state sweep of a linear layer: the Pallas kernel where
    the program is compiled for a TPU and a head's state is whole (8, 128)
    tiles, else the ``jnp`` form; said once while tracing, as the attention
    paths are."""
    from megatron_llm_tpu.core.parallel_state import target_platform
    from megatron_llm_tpu.ops import gated_delta as gd

    m = cfg.model
    target = target_platform()
    refusal = None
    if not cfg.training.use_flash_attn:
        refusal = "use_flash_attn is off"
    elif target != "tpu":
        refusal = f"target platform is {target}"
    elif m.linear_key_head_dim % 8 or m.linear_value_head_dim % 128:
        refusal = (f"a head's state [{m.linear_key_head_dim}, "
                   f"{m.linear_value_head_dim}] is not whole (8, 128) tiles")
    attn_ops.announce_path("delta_tick", "jnp" if refusal else "pallas",
                           refusal or "")
    if refusal is not None:
        return gd.delta_tick
    from megatron_llm_tpu.ops.pallas.gated_delta import delta_sweep

    return delta_sweep


def cross_attention_sublayer(
    cfg,
    p: Params,
    x: jax.Array,            # [b, sq, h] (post cross-norm)
    encoder_hidden: jax.Array,  # [b, skv, h]
    enc_bias: Optional[jax.Array],  # [b or 1, 1, sq, skv] additive bias
    dropout_key: Optional[jax.Array],
    deterministic: bool,
):
    """T5 decoder inter-attention (reference ParallelAttention with
    attn_type=cross_attn, transformer.py:280-343): Q from the decoder stream,
    K/V from the encoder output, full (non-causal) attention."""
    m = cfg.model
    b, sq, _ = x.shape
    n, nkv, d = m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels
    linear = _linear_impl(cfg)
    q = linear(p["q"], x).reshape(b, sq, n, d)
    kv = linear(p["kv"], encoder_hidden)
    skv = encoder_hidden.shape[1]
    kv = kv.reshape(b, skv, nkv, 2, d)
    k, v = kv[..., 0, :], kv[..., 1, :]
    ctx = attn_ops.xla_attention(
        q, k, v, bias=enc_bias, scale=1.0 / (d ** 0.5),
        dropout_rate=0.0 if deterministic else m.attention_dropout,
        dropout_key=dropout_key,
    )
    return linear(p["dense"], ctx.reshape(b, sq, n * d))


def ffn_sublayer(cfg, p: Params, x: jax.Array,
                 layer_input: Optional[jax.Array] = None):
    """Dense MLP or MoE, depending on the layer params. Returns (out,
    aux[AUX_LEN]): the router's losses and counts (zeros for dense).
    ``layer_input`` is the layer's normed input, which the router reads
    instead of ``x`` where the family places it before the attention
    (``moe_router_input``)."""
    from megatron_llm_tpu.models import moe as moe_mod

    if "moe" in p:
        before = cfg.model.moe_router_input == "layer_input"
        assert layer_input is not None or not before
        return moe_mod.moe_sublayer(
            cfg, p["moe"], x, router_x=layer_input if before else None)
    return mlp_sublayer(cfg, p["mlp"], x), moe_mod.zero_aux()


@jax.named_scope("mlp")
def mlp_sublayer(cfg, p: Params, x: jax.Array) -> jax.Array:
    """ParallelMLP analog (transformer.py:77-142): fc1 -> activation -> fc2.

    GLU path: fc1 kernel is [h, 2, ffn]; one GEMM computes both halves, the
    gate is x1 * act(x2) matching the reference chunk-2 convention
    (glu_activations.py:14-16).
    """
    from megatron_llm_tpu.parallel.tp import (
        apply_column_parallel,
        apply_row_parallel,
    )

    m = cfg.model
    linear = _linear_impl(cfg)
    if m.glu_activation is not None:
        # [..., 2, ffn] (both impls restore the axis)
        y = apply_column_parallel(cfg, p["fc1"], x, linear)
        gated = glu_product(m.glu_activation, y[..., 0, :], y[..., 1, :],
                            m.swiglu_limit)
        return apply_row_parallel(cfg, p["fc2"], gated, linear)
    act = get_mlp_activation(None, m.activation)
    h = act(apply_column_parallel(cfg, p["fc1"], x, linear))
    return apply_row_parallel(cfg, p["fc2"], h, linear)


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


def block_forward(
    cfg,
    p: Params,
    hidden: jax.Array,  # [b, s, h]
    *,
    rope=None,
    position_ids=None,
    segment_ids=None,
    token_idx=None,
    attn_bias=None,
    encoder_hidden=None,
    enc_bias=None,
    dropout_key=None,
    deterministic: bool = True,
    hidden_dropout_rate: Optional[float] = None,
    kv_cache=None,
    cache_index=None,
    paged=None,
    sp_constraint=None,
    kind: Optional[LayerKind] = None,
):
    """One transformer layer (ParallelTransformerLayer, transformer.py:659-894).
    ``kind``: the layer's kind in a patterned stack (None = uniform).

    Pre-LN residual block; ``parallel_attn`` runs attention and MLP from the
    same normed input and sums both into the residual (Falcon,
    transformer.py:851-886). ``sp_constraint`` is an optional callable applying
    the sequence-parallel sharding constraint to residual-stream tensors.
    """
    m = cfg.model
    eps = m.layernorm_epsilon
    rate = m.hidden_dropout if hidden_dropout_rate is None else hidden_dropout_rate
    if dropout_key is not None:
        dk_attn, dk_h1, dk_h2, dk_x, dk_hx = jax.random.split(dropout_key, 5)
    else:
        dk_attn = dk_h1 = dk_h2 = dk_x = dk_hx = None
    _sp = sp_constraint if sp_constraint is not None else (lambda t: t)

    ln1 = norm(hidden, p["input_norm"], eps, m.use_rms_norm)
    if kind is not None and kind.mixer == "delta":
        assert token_idx is None and attn_bias is None and (
            segment_ids is None) and (
            deterministic or not m.attention_dropout), (
            "a linear layer: no cp token order, bias, packed segments or "
            "attention dropout")
        attn_out, new_cache = delta_sublayer(
            cfg, p["attention"], ln1, kv_cache=kv_cache, paged=paged)
    elif m.mla:
        assert token_idx is None and attn_bias is None and (
            deterministic or not m.attention_dropout), (
            "latent attention: no cp token order, bias or attention dropout")
        attn_out, new_cache = mla_sublayer(
            cfg, p["attention"], ln1, rope, position_ids, segment_ids,
            kv_cache=kv_cache, paged=paged)
    elif m.retention:
        assert token_idx is None and attn_bias is None and (
            segment_ids is None) and (
            deterministic or not m.attention_dropout), (
            "power retention: no cp token order, bias, packed segments or "
            "attention dropout")
        attn_out, new_cache = retention_sublayer(
            cfg, p["attention"], ln1, rope, position_ids,
            kv_cache=kv_cache, paged=paged)
    else:
        attn_out, new_cache = attention_sublayer(
            cfg, p["attention"], ln1, rope, position_ids, segment_ids,
            dk_attn, deterministic, kv_cache, cache_index,
            token_idx=token_idx, attn_bias=attn_bias, paged=paged, kind=kind,
        )

    if m.parallel_attn:
        assert "cross_attention" not in p, (
            "cross-attention layers (T5 decoder) require the sequential "
            "block; parallel_attn would silently skip the encoder attention"
        )
        mlp_in = norm(hidden, p["mlp_norm"], eps, m.use_rms_norm) if m.parallel_layernorm else ln1
        mlp_out, aux = ffn_sublayer(cfg, p, mlp_in)
        out = hidden + rng_mod.dropout(dk_h1, rate, attn_out, deterministic or dk_h1 is None) \
            + rng_mod.dropout(dk_h2, rate, mlp_out, deterministic or dk_h2 is None)
        out = _sp(out)
    else:
        if m.post_sublayer_norms:
            attn_out = norm(attn_out, p["attn_out_norm"], eps, m.use_rms_norm)
        resid = hidden + rng_mod.dropout(dk_h1, rate, attn_out, deterministic or dk_h1 is None)
        resid = _sp(resid)
        if "cross_attention" in p:
            # decoder inter-attention block (LayerType.decoder,
            # transformer.py:838-850)
            lnx = norm(resid, p["cross_norm"], eps, m.use_rms_norm)
            x_out = cross_attention_sublayer(
                cfg, p["cross_attention"], lnx, encoder_hidden, enc_bias,
                dk_x, deterministic,
            )
            resid = resid + rng_mod.dropout(
                dk_hx, rate, x_out, deterministic or dk_hx is None
            )
            resid = _sp(resid)
        ln2 = norm(resid, p["post_norm"], eps, m.use_rms_norm)
        mlp_out, aux = ffn_sublayer(cfg, p, ln2, layer_input=ln1)
        if m.post_sublayer_norms:
            mlp_out = norm(mlp_out, p["mlp_out_norm"], eps, m.use_rms_norm)
        out = resid + rng_mod.dropout(dk_h2, rate, mlp_out, deterministic or dk_h2 is None)
        out = _sp(out)
    return out, new_cache, aux


def _lima_rates(cfg, num_layers: int) -> jax.Array:
    """LIMA per-layer dropout ramp 0 -> hidden_dropout (transformer.py:1041-1048)."""
    m = cfg.model
    if not m.lima_dropout or num_layers <= 1:
        return jnp.full((num_layers,), m.hidden_dropout, jnp.float32)
    return jnp.linspace(0.0, m.hidden_dropout, num_layers)


def _remat_policy(name: str):
    policies = {
        "none": None,
        "full": jax.checkpoint_policies.nothing_saveable,
        "save_dots_except_logits": jax.checkpoint_policies.checkpoint_dots,
        # 'selective' ~ reference selective recompute: save everything except
        # the attention internals (we approximate with save-only-dot-products).
        "selective": jax.checkpoint_policies.dots_saveable,
        # dots + the named attention outputs: the backward reuses the saved
        # flash result instead of re-running the kernel forward
        "save_dots_and_attn": jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        ),
        # near-full recompute, but keep the flash-attention outputs: the one
        # tensor whose recompute is a whole Pallas kernel run. Memory close
        # to 'full' (enables the largest micro-batches), backward cost close
        # to 'selective'.
        "save_attn_only": jax.checkpoint_policies.save_only_these_names(
            "attn_out"
        ),
    }
    return policies.get(name, jax.checkpoint_policies.checkpoint_dots)


def transformer_forward(
    cfg,
    stacked_layers: Params,
    hidden: jax.Array,
    *,
    rope=None,
    position_ids=None,
    segment_ids=None,
    token_idx=None,
    attn_bias=None,
    encoder_hidden=None,
    enc_bias=None,
    dropout_key=None,
    deterministic: bool = True,
    kv_caches=None,        # dense: stacked [L, ...] pair; paged: the pool
    cache_index=None,
    paged=None,
    sp_constraint=None,
    layer_offset: int = 0,
    pool_first_layer=0,
):
    """Run the stacked layers (ParallelTransformer, transformer.py:974-1347).

    When ``cfg.training.scan_layers`` (default), layers are scanned with an
    optional remat policy; otherwise a Python loop (useful for debugging and
    per-layer inspection).
    Returns (hidden, new_kv_caches, aux) — ``aux`` is the summed MoE router
    loss pair [2] (load-balance, z), zeros for dense models.

    ``paged``: ``kv_caches`` is a paged pool, ONE leaf over all its layers
    (ops/kv_quant.py: K/V or latent), whose layer 0 is the model's layer
    ``pool_first_layer`` (a pipeline stage hands its own slice).  It rides
    the scan's CARRY and every layer writes and reads its own pages of it
    in place (:class:`LayerPool`); with the engine's donated buffers no
    copy of the pool or of a layer's slice exists in the tick.  The dense
    incremental cache (``cache_index``) is a stacked pair scanned per layer.

    Which leaves ride the scan.  Without ``paged`` (the trainer, the dense
    forward, the dense incremental cache): every leaf of ``stacked_layers``
    is a scanned operand, a patterned stack's as whole periods.  With
    ``paged`` (every tick of the engine: ragged, verify, decode, chained,
    the draft model's): the norms, a router, a retention gate and head
    norms and MLA's ``kv_up`` ride the scan; the routed experts
    (``StackedExperts``) and the dense projections (``TICK_LINEARS``, as
    ``StackedLinear``) are read from their stacks, which the scan's body
    closes over, by the layer's index: the program holds no copy of a
    layer's weights (``tools/tick_hlo_copies.py`` prints what it holds).
    """
    if cfg.model.sublayer_pattern:    # layers of ONE sublayer: their own stack
        return _sublayers().sublayer_stack_forward(
            cfg, stacked_layers, hidden, kv_caches=kv_caches, paged=paged,
            rope=rope, position_ids=position_ids, unserved=dict(
                segment_ids=segment_ids, token_idx=token_idx,
                attn_bias=attn_bias, encoder_hidden=encoder_hidden,
                enc_bias=enc_bias, cache_index=cache_index,
                sp_constraint=sp_constraint, layer_offset=layer_offset or None,
                dropout_key=None if deterministic else dropout_key))
    # the pattern: layer l of this stack is of kind kinds[l % period].  A
    # stack handed to this function is whole periods from a period's first
    # layer on (a pipeline stage's slice too: Config.finalize), so a
    # layer's place in its period is its place in the stack, whatever
    # layer_offset is
    kinds = stack_kinds(cfg, layer_offset)
    period = len(kinds)
    # a hybrid's mixers: a stack a kind (``layer_stacks``), each over its
    # own layers; place j of a period reads layer ``rank[j]`` of its kind's
    # ``per[mixer]`` layers a period
    mixers = None
    rank = [sum(k.mixer == kind.mixer for k in kinds[:j])
            for j, kind in enumerate(kinds)]
    per = {kind.mixer: sum(k.mixer == kind.mixer for k in kinds)
           for kind in kinds}
    if len(per) > 1:
        mixers = stacked_layers["attention"]
        stacked_layers = {k: v for k, v in stacked_layers.items()
                          if k != "attention"}
    num_layers = jax.tree_util.tree_leaves(stacked_layers)[0].shape[0]
    rates = _lima_rates(cfg, cfg.model.depth)
    in_carry = paged is not None and kv_caches is not None
    pool = kv_caches if in_carry else None
    if in_carry:
        kv_caches = None
    # the serving tick does not scan the expert weights: a scanned slice
    # that feeds the grouped kernel is a copy of the layer's experts (2.4 GB
    # a layer at 256 x 2048 x 768; 74% of the tick's device time when it
    # was one, PERF.md PR 31), so the whole stack stays outside the scan and
    # the layer names its own experts (models/moe.StackedExperts).
    # The trainer scans them: a closed-over stack would take a stack-sized
    # gradient from every layer
    from megatron_llm_tpu.models import moe as moe_mod

    all_experts = None
    if (paged is not None and "moe" in stacked_layers
            and moe_mod.use_dropless(cfg)):
        all_experts = stacked_layers["moe"]["experts"]
        stacked_layers = {**stacked_layers, "moe": {
            k: v for k, v in stacked_layers["moe"].items() if k != "experts"}}
    # nor the dense projections (TICK_LINEARS: qkv, dense, fc1, fc2, a
    # shared expert's, MLA's down and up): a scanned slice of a GLU fc1 is
    # written out twice before its GEMM reads it, and a patterned stack's
    # ``a[j]`` slices a period before it slices a layer (PERF.md section 6,
    # PR 42: 27% of the Brumby tick's device time, 19% of Falcon's).  The
    # stack stays outside the scan, the layer names its own projection
    # (``StackedLinear``) and ``_linear`` reads it in place.  Kept as they
    # were: the trainer and every path without ``paged`` (a stack-sized
    # gradient a layer, as above); fp8 linears (ops/fp8.py casts a layer's
    # kernel as it multiplies); the tp overlap rings (parallel/overlap.py
    # hands ``p["kernel"]`` to a shard_map of its own)
    from megatron_llm_tpu.parallel import overlap as tp_overlap_mod

    linears = mixer_linears = None
    if (paged is not None and _linear_impl(cfg) is _linear
            and tp_overlap_mod.current() is None):
        linears, stacked_layers = _take_linears(stacked_layers)
        if mixers is not None:
            taken = {mx: _take_linears(tree) for mx, tree in mixers.items()}
            mixer_linears = {mx: t[0] for mx, t in taken.items()}
            mixers = {mx: t[1] for mx, t in taken.items()}

    # a layer's page class, its rank among the class's layers of a period,
    # how many those are and how many of the class's layers come before
    # this stack, by its place in the period
    # (told by the TABLES: a quantized pool is a tuple of its own)
    classed = in_carry and paged is not None and isinstance(
        paged.block_tables, tuple)
    class_at = None
    if classed and _linear_prefix(cfg, layer_offset):
        # the first layers of the state class
        c = next(i for i, cls in enumerate(pool_classes(cfg)) if cls.state)
        class_at = {0: (c, 0, 1, 0)}
    elif classed:
        class_at = {j: (c, cls.places.index(j), len(cls.places), cls.prefix)
                    for c, cls in enumerate(pool_classes(cfg))
                    for j in cls.places}

    def one_layer(carry, xs, kind, place=0):
        carry_hidden, pool = carry
        layer_params, layer_idx, cache = xs
        layer_paged = paged
        if classed:
            c, c_rank, per_period, before = class_at[place]
            cache = LayerPool(
                pool[c], before + (layer_idx - layer_offset)
                // len(class_at) * per_period + c_rank)
            layer_paged = paged._replace(
                block_tables=paged.block_tables[c],
                walks=paged.walks and paged.walks[c])
        elif in_carry:
            cache = LayerPool(pool, layer_idx - pool_first_layer)
        if all_experts is not None:
            layer_params = {**layer_params, "moe": {
                **layer_params["moe"], "experts": moe_mod.StackedExperts(
                    all_experts, layer_idx - layer_offset)}}
        if linears:
            layer_params = _put_linears(layer_params, linears,
                                        layer_idx - layer_offset)
        if mixer_linears:
            layer_params = {**layer_params, "attention": _put_linears(
                layer_params["attention"], mixer_linears[kind.mixer],
                (layer_idx - layer_offset) // period * per[kind.mixer]
                + rank[place])}
        dk = None if dropout_key is None else rng_mod.fold_layer(dropout_key, layer_idx)
        rate = rates[layer_idx]
        out, new_cache, aux = block_forward(
            cfg, layer_params, carry_hidden,
            rope=rope, position_ids=position_ids, segment_ids=segment_ids,
            token_idx=token_idx,
            attn_bias=attn_bias,
            encoder_hidden=encoder_hidden, enc_bias=enc_bias,
            dropout_key=dk, deterministic=deterministic,
            hidden_dropout_rate=rate,
            kv_cache=cache, cache_index=cache_index, paged=layer_paged,
            sp_constraint=sp_constraint, kind=kind,
        )
        if classed:
            pool = pool[:c] + (new_cache,) + pool[c + 1:]
            new_cache = None
        elif in_carry:
            pool, new_cache = new_cache, None
        return (out, pool), (new_cache, aux)

    assert num_layers % period == 0, (
        f"a stack of {num_layers} layers is not whole periods of {period}")
    layer_ids = jnp.arange(num_layers) + layer_offset

    if cfg.training.scan_layers:
        granularity = cfg.parallel.recompute_granularity
        policy = _remat_policy(
            "full" if granularity == "full" else cfg.training.remat_policy
            if granularity else "none"
        )
        bodies = [partial(one_layer, kind=k, place=j)
                  for j, k in enumerate(kinds)]
        if granularity is not None:
            # a checkpoint a LAYER, not a period: what the backward holds
            # at once is one layer's internals, as in a uniform stack
            bodies = [jax.checkpoint(b, policy=policy, prevent_cse=False)
                      for b in bodies]
        if period == 1:
            body = bodies[0]
            xs = (stacked_layers, layer_ids, kv_caches)
        else:
            # scan over periods, the period's layers unrolled in the body:
            # a layer's kind is static there
            def body(carry, xs):
                outs = []
                *common, mixed = xs
                for j, layer_body in enumerate(bodies):
                    params_j, ids_j, cache_j = jax.tree.map(
                        lambda a: a[j], tuple(common))
                    if mixed is not None:
                        params_j = {**params_j, "attention": jax.tree.map(
                            lambda a: a[rank[j]], mixed[kinds[j].mixer])}
                    carry, out = layer_body(carry, (params_j, ids_j, cache_j))
                    outs.append(out)
                return carry, jax.tree.map(lambda *o: jnp.stack(o), *outs)

            periods = num_layers // period
            xs = jax.tree.map(
                lambda a: a.reshape(periods, a.shape[0] // periods,
                                    *a.shape[1:]),
                (stacked_layers, layer_ids, kv_caches, mixers))
        (hidden, pool), (new_caches, aux_stack) = jax.lax.scan(
            body, (hidden, pool), xs)
        if period > 1:
            new_caches, aux_stack = jax.tree.map(
                lambda a: a.reshape(num_layers, *a.shape[2:]),
                (new_caches, aux_stack))
        return hidden, pool if in_carry else new_caches, aux_stack.sum(0)
    else:
        from megatron_llm_tpu.models.moe import zero_aux

        new_caches = []
        aux_total = zero_aux()
        for i in range(num_layers):
            layer_p = jax.tree.map(lambda a: a[i], stacked_layers)
            if mixers is not None:
                mx = kinds[i % period].mixer
                at = i // period * per[mx] + rank[i % period]
                layer_p = {**layer_p, "attention": jax.tree.map(
                    lambda a: a[at], mixers[mx])}
            cache = None if kv_caches is None else jax.tree.map(lambda a: a[i], kv_caches)
            (hidden, pool), (nc, aux) = one_layer(
                (hidden, pool), (layer_p, layer_ids[i], cache),
                kinds[i % period], i % period)
            new_caches.append(nc)
            aux_total = aux_total + aux
        if in_carry:
            new_caches = pool
        elif kv_caches is not None:
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            new_caches = None
        return hidden, new_caches, aux_total


def layer_stacks(cfg, params: Params):
    """(stacked layers, first absolute layer) of each stack a token passes,
    in order: the dense prefix where the model has one, then the scanned
    stack.  A uniform model has the one stack it always had."""
    stacks = []
    if "dense_layers" in params:
        stacks.append((params["dense_layers"], 0))
    layers = params["layers"]
    if "mixers" in params:
        # a hybrid: the scanned stack's mixers, a stack a kind
        layers = {**layers, "attention": params["mixers"]}
    stacks.append((layers, cfg.model.dense_prefix_layers))
    return stacks


def _sublayers():
    """models/sublayers.py (which imports this module), when first asked."""
    from megatron_llm_tpu.models import sublayers

    return sublayers


def _init_head_norms(cfg) -> Params:
    """``qk_head_norm``: the leaves of one RMSNorm a head on q and on k."""
    if not cfg.model.qk_head_norm:
        return {}
    return {"q_norm": init_norm_params(cfg.model.kv_channels, True),
            "k_norm": init_norm_params(cfg.model.kv_channels, True)}


def _head_normed(cfg, p: Params, q, k, v):
    """``qk_head_norm``: q and k each under its RMSNorm over a head's
    ``kv_channels``, BEFORE the rotation (float32 inside, the activations'
    dtype out: what the pages then hold is the normed, rotated key)."""
    if not cfg.model.qk_head_norm:
        return q, k, v
    eps = cfg.model.layernorm_epsilon
    return norm(q, p["q_norm"], eps, True), norm(k, p["k_norm"], eps, True), v


def init_mlp_params(cfg, k_in: jax.Array, k_out: jax.Array) -> Params:
    """The ``mlp`` subtree: a dense MLP of ``ffn_hidden_size``, inside a
    layer (:func:`init_layer_params`) or as a sublayer of its own."""
    m = cfg.model
    h, ffn, std = m.hidden_size, m.ffn_hidden_size, m.init_method_std
    out_std = std / (2.0 * m.num_layers) ** 0.5 if m.use_scaled_init_method else std
    glu = m.glu_activation is not None
    return {"fc1": {"kernel": _normal(k_in, (h, 2, ffn) if glu else (h, ffn), std)},
            "fc2": {"kernel": _normal(k_out, (ffn, h), out_std)}}


def _write_pos(paged):
    """Where a paged row's K/V lands: its own position.  ``paged.positions``
    is that too for a causal model; for a block-causal one it is the row's
    MASK position (its block's last) and the write's comes beside it."""
    return (paged.positions if paged.write_positions is None
            else paged.write_positions)


def block_mask_position(positions, block: int):
    """The last position a query at ``positions`` sees under a block-causal
    mask of ``block``: its own block's last."""
    return (positions // block + 1) * block - 1


def _block_bias(m, attn_bias, s: int):
    """The dense forward's mask of a block-causal model
    (``diffusion_block_length``) as an additive bias, blocks cut from
    position 0; ``attn_bias`` as it came for every other model."""
    if not m.diffusion_block_length:
        return attn_bias
    assert attn_bias is None, "a block-causal model takes no other bias"
    q_pos = jnp.arange(s)
    allowed = q_pos[None, :] <= block_mask_position(
        q_pos, m.diffusion_block_length)[:, None]
    return jnp.where(allowed, 0.0, attn_ops.NEG_INF).astype(
        jnp.float32)[None, None]


# ---- A.X-K2's additions (PR 67), at the file's end so that every line
# above stands where it stood: a Pallas kernel's payload in a lowered tick
# names the LINES of its callers (tools/tick_digest.py), and the accepted
# cells' ticks stay the parent's programs -------------------------------------


def _norm_maker(cfg, key: jax.Array):
    """``() -> leaves`` of one of a layer's norms: plain, or with the
    low-rank gate behind it (``gated_norm``), a key a call."""
    m = cfg.model
    if not m.gated_norm:
        return partial(init_norm_params, m.hidden_size, m.use_rms_norm,
                       bias=m.norm_bias, gain=m.norm_gain)
    keys = iter(jax.random.split(jax.random.fold_in(key, 7), 2))
    return lambda: init_norm_params(
        m.hidden_size, True, gated_rank=m.gated_norm_rank, key=next(keys))


def _mla_extras(cfg, key: jax.Array) -> Params:
    """The latent attention's optional leaves: ``g_proj`` (the output gate:
    a value a head and channel, or ONE a head) and the indexer's."""
    m = cfg.model
    n = m.num_attention_heads
    out = {}
    if m.attention_output_gate:
        out["g_proj"] = {"kernel": _normal(
            jax.random.fold_in(key, 4),
            (m.hidden_size, n if m.attention_gate_headwise
             else n * m.v_head_dim), m.init_method_std)}
    if m.index_topk:
        from megatron_llm_tpu.models.sparse_mla import init_index_params

        out.update(init_index_params(cfg, jax.random.fold_in(key, 5)))
    return out


def _mla_rows(cfg, p: Params, x, c_q, rope, position_ids):
    """What attends a tick's rows: :func:`_mla_paged`, or under a learned
    indexer (``index_topk``) models/sparse_mla.py ``paged``, which also
    needs the layer's leaves and input for the index queries and key."""
    if not cfg.model.index_topk:
        return _mla_paged
    from megatron_llm_tpu.models.sparse_mla import paged

    return partial(paged, p=p, x=x, c_q=c_q, rope=rope,
                   position_ids=position_ids, linear=_linear_impl(cfg))


def _mla_mask(cfg, p: Params, x, c_q, rope, position_ids, segment_ids):
    """The no-cache forward's mask arguments: the packed segments, and under
    a learned indexer the same selection as a bias (no gradient; every key
    while the sequence is no longer than ``index_topk``)."""
    m = cfg.model
    if not m.index_topk or x.shape[1] <= m.index_topk:
        return dict(segment_ids=segment_ids)
    from megatron_llm_tpu.models import sparse_mla

    return dict(segment_ids=segment_ids, bias=jax.lax.stop_gradient(
        sparse_mla.dense_bias(cfg, *sparse_mla.index_inputs(
            cfg, p, x, c_q, rope, position_ids, _linear_impl(cfg)),
            segment_ids=segment_ids)))


def _head_wide(m, vd: int, gate: jax.Array) -> jax.Array:
    """A gate of one value a head ``[..., n]`` spread over its ``vd``
    channels; an elementwise gate as it is."""
    return jnp.repeat(gate, vd, axis=-1) if m.attention_gate_headwise \
        else gate
