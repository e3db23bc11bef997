"""Draft-model bundle for speculative decoding.

The draft is a full model in its own right — same architecture family as
the target, its own (smaller) config and params — but it serves one
purpose: proposing tokens the target then verifies.  Three contracts keep
it honest:

* **Shared token space.** Draft and target must agree on the vocab (and
  therefore the tokenizer): acceptance compares token ids, and the
  residual rejection sampler subtracts the draft distribution from the
  target's over the SAME vocab axis.  ``check_draft_compat`` enforces it.
* **Shared page geometry.** The draft's K/V lives in the same
  :class:`~megatron_llm_tpu.generation.pools.PagedKVPool` as the
  target's — same page ids, same block tables, same refcounts — so the
  draft only needs a per-layer/head shape of its own, which the pool
  allocates alongside the target arrays.
* **Same sharding rules.** Under a tensor-parallel mesh the draft params
  shard by the identical parallel/tp.py rules as the target (the engine
  applies them at construction), so one mesh serves both models.

``resolve_draft`` turns the ``--spec_draft`` flag into a bundle:

* ``"llama2:num_layers=2,hidden_size=256"`` — a make_config spec,
  random-initialized (smoke/bench shape; inherits the target's vocab
  when the spec does not name one);
* ``"llama2:num_layers=2,...@/path/to/ckpt"`` — same, with params loaded
  from a checkpoint directory instead of random init.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class DraftModel:
    """A (config, params) pair the engine speculates with."""

    cfg: Any
    params: Any

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))


def check_draft_compat(target_cfg, draft_cfg, *, max_seq: int) -> None:
    """Assert the draft can propose for the target: same token space, a
    position horizon covering the engine's sequence budget, and a KV shape
    the shared page pool can host alongside the target's."""
    t, d = target_cfg.model, draft_cfg.model
    if t.vocab_size != d.vocab_size:
        raise ValueError(
            f"draft vocab {d.vocab_size} != target vocab {t.vocab_size} — "
            "speculative acceptance compares token ids, the models must "
            "share a tokenizer")
    if d.max_position_embeddings < max_seq:
        raise ValueError(
            f"draft max_position_embeddings {d.max_position_embeddings} < "
            f"engine max_seq {max_seq}")
    if getattr(d, "sliding_window_size", None) != getattr(
            t, "sliding_window_size", None):
        raise ValueError(
            "draft and target must agree on sliding_window_size: the "
            "verify step replays draft-advanced positions through the "
            "target's attention horizon")
    from megatron_llm_tpu.models.language_model import padded_vocab_size

    if padded_vocab_size(t.vocab_size, target_cfg) != padded_vocab_size(
            d.vocab_size, draft_cfg):
        raise ValueError(
            "draft and target padded vocab widths differ — the residual "
            "rejection sampler subtracts q from p over the same axis")


def _parse_override(raw: str):
    raw = raw.strip()
    low = raw.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    try:
        return int(raw)
    except ValueError:
        pass
    try:
        return float(raw)
    except ValueError:
        return raw


def parse_draft_spec(spec: str):
    """``"family:key=val,...[@/ckpt/dir]"`` -> (family, overrides, load)."""
    load: Optional[str] = None
    if "@" in spec:
        spec, load = spec.rsplit("@", 1)
    family, _, kvs = spec.partition(":")
    overrides = {}
    for part in kvs.split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        if not _:
            raise ValueError(f"--spec_draft override {part!r} is not key=val")
        overrides[k.strip()] = _parse_override(v)
    return family.strip(), overrides, load


def resolve_draft(spec: str, target_cfg, *, seed: int = 0) -> DraftModel:
    """Build the draft bundle the ``--spec_draft`` flag names."""
    from megatron_llm_tpu.models import init_model_params, make_config

    family, overrides, load = parse_draft_spec(spec)
    t = target_cfg.model
    overrides.setdefault("vocab_size", t.vocab_size)
    overrides.setdefault("seq_length", target_cfg.data.seq_length)
    overrides.setdefault("max_position_embeddings", t.max_position_embeddings)
    overrides.setdefault("params_dtype", target_cfg.training.params_dtype)
    overrides.setdefault("use_flash_attn", target_cfg.training.use_flash_attn)
    overrides.setdefault("micro_batch_size", 1)
    overrides.setdefault("global_batch_size", 1)
    overrides.setdefault("train_iters", 1)
    cfg = make_config(family, **overrides)

    key = jax.random.PRNGKey(seed)
    if load is None:
        params = init_model_params(cfg, key)
    else:
        from megatron_llm_tpu.checkpointing import load_checkpoint

        template = jax.eval_shape(lambda k: init_model_params(cfg, k), key)
        params, _, _, _, _ = load_checkpoint(cfg, load, template)
    return DraftModel(cfg, params)


def extend_params_identity(draft_cfg, draft_params, target_cfg,
                           key: jax.Array):
    """Target params whose first ``L_draft`` layers ARE the draft and whose
    remaining layers are exact identities (zeroed attention-output and
    fc2 projections: both residual branches contribute exactly 0.0, so the
    extra layers pass hidden states through bit-for-bit).

    This is the bench/test construction for a draft the target provably
    agrees with: greedy acceptance is 100% while the target still pays for
    ``L_target`` layers of compute — the honest way to exercise the
    speculative pipeline's mechanics on random-init weights, where an
    independently initialized draft would accept ~nothing.
    Requires equal hidden/head/ffn dims; only ``num_layers`` may differ.
    """
    from megatron_llm_tpu.models import init_model_params

    d, t = draft_cfg.model, target_cfg.model
    for f in ("hidden_size", "num_attention_heads", "num_attention_heads_kv",
              "kv_channels", "ffn_hidden_size", "vocab_size"):
        assert getattr(d, f) == getattr(t, f), (
            f"identity extension needs equal {f}")
    L_d, L_t = d.num_layers, t.num_layers
    assert L_t >= L_d
    target = init_model_params(target_cfg, key)
    # non-layer leaves come straight from the draft (same shapes)
    for k in draft_params:
        if k != "layers":
            target[k] = jax.tree_util.tree_map(lambda x: x, draft_params[k])

    def splice(d_leaf, t_leaf, path):
        ext = t_leaf[L_d:]
        if path[:2] in (("attention", "dense"), ("mlp", "fc2")):
            ext = jnp.zeros_like(ext)
        return jnp.concatenate([d_leaf, ext], axis=0)

    def walk(dn, tn, path=()):
        if isinstance(dn, dict):
            return {k: walk(dn[k], tn[k], path + (k,)) for k in dn}
        return splice(dn, tn, path)

    target["layers"] = walk(draft_params["layers"], target["layers"])
    return target
