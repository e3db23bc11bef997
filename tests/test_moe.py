"""Mixture-of-Experts tests (models/moe.py) — beyond-reference feature.

The reference has no MoE (SURVEY §2.1: "EP absent"), so there is no reference
file to cite for parity; these tests follow the same discipline as the TP/CP
suites: exact semantics checks at small scale plus cross-mesh parity on the
8-device CPU mesh (conftest pins JAX_PLATFORMS=cpu with 8 virtual devices).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models.language_model import loss_from_batch
from megatron_llm_tpu.models.moe import (
    init_moe_params,
    moe_capacity,
    moe_sublayer,
    route_tokens,
)


def tiny_cfg(**kw):
    defaults = dict(
        num_layers=2,
        hidden_size=64,
        num_attention_heads=4,
        num_attention_heads_kv=2,
        vocab_size=256,
        seq_length=32,
        max_position_embeddings=64,
        params_dtype="float32",
        micro_batch_size=2,
        global_batch_size=2,
        train_iters=5,
        use_flash_attn=False,
        num_experts=4,
        moe_router_topk=2,
    )
    defaults.update(kw)
    return make_config("mixtral", **defaults)


def make_batch(cfg, key, gbs=2):
    s = cfg.data.seq_length
    tok = jax.random.randint(key, (gbs, s + 1), 0, cfg.model.vocab_size)
    return {
        "tokens": tok[:, :-1],
        "labels": tok[:, 1:],
        "loss_mask": jnp.ones((gbs, s), jnp.float32),
    }


# ---------------------------------------------------------------------------
# routing semantics
# ---------------------------------------------------------------------------


def test_route_tokens_matches_naive_loop():
    """combine/dispatch must equal a per-token greedy seating by (slot, token)
    priority — the GShard convention the einsum formulation encodes."""
    cfg = tiny_cfg(num_experts=4, moe_router_topk=2, moe_capacity_factor=0.5)
    g_, t_, e_, k_ = 2, 16, 4, 2
    logits = jax.random.normal(jax.random.PRNGKey(0), (g_, t_, e_), jnp.float32)
    cap = moe_capacity(cfg, t_)
    combine, dispatch, aux = jax.jit(
        lambda l: route_tokens(cfg, l, cap)
    )(logits)
    combine = np.asarray(combine)

    probs = np.asarray(jax.nn.softmax(logits, -1))
    expected = np.zeros((g_, t_, e_, cap), np.float32)
    for g in range(g_):
        fill = np.zeros(e_, np.int64)
        # choices in priority order: all k=0 across tokens, then k=1
        topk = np.argsort(-probs[g], axis=-1)[:, :k_]  # [T, K]
        gates = np.take_along_axis(probs[g], topk, -1)
        gates = gates / gates.sum(-1, keepdims=True)  # normalize_gates
        for k in range(k_):
            for t in range(t_):
                e = topk[t, k]
                if fill[e] < cap:
                    expected[g, t, e, fill[e]] = gates[t, k]
                    fill[e] += 1
    np.testing.assert_allclose(combine, expected, rtol=1e-5, atol=1e-6)
    assert bool(jnp.all(dispatch == (combine > 0)))


def test_aux_loss_uniform_routing_is_one():
    """Switch load-balance loss equals 1.0 under perfectly uniform routing."""
    cfg = tiny_cfg(num_experts=8, moe_router_topk=2)
    logits = jnp.zeros((2, 64, 8), jnp.float32)
    _, _, aux = route_tokens(cfg, logits, capacity=64)
    np.testing.assert_allclose(float(aux[0]), 1.0, rtol=1e-5)
    # z-loss = mean(logsumexp(0..)^2) = log(8)^2
    np.testing.assert_allclose(float(aux[1]), np.log(8.0) ** 2, rtol=1e-5)


def test_capacity_drops_lowest_priority_tokens():
    cfg = tiny_cfg(num_experts=2, moe_router_topk=1, moe_capacity_factor=0.25,
                   moe_min_capacity=1)
    t_ = 16
    # all tokens prefer expert 0
    logits = jnp.tile(jnp.array([5.0, -5.0], jnp.float32), (1, t_, 1))
    cap = moe_capacity(cfg, t_)  # = max(1, ceil(16*0.25/2)) = 2
    combine, dispatch, _ = route_tokens(cfg, logits, cap)
    seated = np.asarray(dispatch.sum((2, 3)))[0]  # per-token
    assert seated[:cap].all() and not seated[cap:].any(), (
        "earlier tokens must win capacity"
    )


def test_single_expert_equals_dense_mlp():
    """E=1, k=1, ample capacity: MoE must reduce to the dense MLP with the
    same weights (gate = softmax over one logit = 1)."""
    from megatron_llm_tpu.models.transformer import mlp_sublayer

    # llama2 base: family validation allows E=1 (mixtral's requires >1)
    cfg = make_config(
        "llama2", hidden_size=64, num_attention_heads=4, vocab_size=256,
        num_experts=1, moe_router_topk=1, moe_capacity_factor=2.0,
        moe_min_capacity=64, params_dtype="float32",
    )
    key = jax.random.PRNGKey(0)
    p = init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)
    out, aux = moe_sublayer(cfg, p, x)
    dense_p = jax.tree.map(lambda a: a[0], p["experts"])  # strip expert axis
    # an expert's fc1 is [2, h, ffn] (each half a whole matrix), the dense
    # MLP's [h, 2, ffn]
    dense_p["fc1"]["kernel"] = dense_p["fc1"]["kernel"].transpose(1, 0, 2)
    want = mlp_sublayer(cfg, dense_p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# cross-mesh parity (ep / tp / dp compositions)
# ---------------------------------------------------------------------------


def _loss_and_grads(cfg, mesh, params, batch):
    from megatron_llm_tpu.parallel.tp import batch_shardings, param_shardings

    with global_mesh(mesh):
        ps = param_shardings(mesh, params)
        params = jax.device_put(params, ps)
        batch = jax.device_put(batch, batch_shardings(cfg, mesh, batch))

        def f(p, b):
            return loss_from_batch(cfg, p, b, deterministic=True)[0]

        loss, grads = jax.jit(jax.value_and_grad(f))(params, batch)
        return float(loss), jax.device_get(grads)


@pytest.mark.parametrize("layout", [
    dict(ep=2, tp=1, dp=2),
    dict(ep=2, tp=2, dp=2),
    dict(ep=4, tp=1, dp=4),
])
def test_ep_parity_with_single_device(layout):
    """Expert-parallel loss/grads must match the unsharded computation."""
    cfg = tiny_cfg()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=4)

    ref_mesh = build_mesh(devices=jax.devices()[:1])
    ref_loss, ref_grads = _loss_and_grads(cfg, ref_mesh, params, batch)

    cfg2 = tiny_cfg()
    cfg2.parallel.expert_parallel_size = layout["ep"]
    cfg2.parallel.tensor_model_parallel_size = layout["tp"]
    cfg2.parallel.data_parallel_size = layout["dp"]
    mesh = build_mesh(
        tensor_model_parallel_size=layout["tp"],
        data_parallel_size=layout["dp"],
        expert_parallel_size=layout["ep"],
    )
    loss, grads = _loss_and_grads(cfg2, mesh, params, batch)

    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat = jax.tree_util.tree_leaves(grads)
    for a, b in zip(flat_ref, flat):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=1e-5)


def test_moe_train_step_descends_with_ep():
    from megatron_llm_tpu.training_step import make_jitted_train_step

    cfg = tiny_cfg(global_batch_size=4)
    cfg.parallel.expert_parallel_size = 2
    cfg.parallel.tensor_model_parallel_size = 2
    cfg.parallel.data_parallel_size = 2
    cfg.optimizer.use_distributed_optimizer = True
    cfg.finalize()
    mesh = build_mesh(tensor_model_parallel_size=2, data_parallel_size=2,
                      expert_parallel_size=2)
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        step, _opt, sh = make_jitted_train_step(cfg, mesh, params)
        batch = sh["place_batch"](make_batch(cfg, jax.random.PRNGKey(1), gbs=4))
        o = sh["opt_state_value"]
        p = params
        losses = []
        for i in range(4):
            p, o, m = step(p, o, batch, i)
            losses.append(float(m["lm loss"]))
            assert np.isfinite(losses[-1])
            assert "moe aux loss" in m
        assert losses[-1] < losses[0]


def test_expert_param_shardings():
    """Expert stacks shard (ep, tp); router replicated; ZeRO-1 moments of
    expert weights keep their ep axis."""
    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu.optimizer.optimizer import (
        get_optimizer,
        opt_state_partition_specs,
    )
    from megatron_llm_tpu.parallel.tp import param_partition_specs

    cfg = tiny_cfg()
    cfg.optimizer.use_distributed_optimizer = True
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    specs = param_partition_specs(params)
    layers = specs["layers"]
    assert layers["moe"]["router"]["kernel"] == P("pp", None, None)
    assert layers["moe"]["experts"]["fc1"]["kernel"] == P("pp", "ep", None, None, "tp")
    assert layers["moe"]["experts"]["fc2"]["kernel"] == P("pp", "ep", "tp", None)

    opt = get_optimizer(cfg, params)
    state = opt.init(params)
    ospecs = opt_state_partition_specs(cfg, params, state, dp_size=2, ep_size=2)
    flat = jax.tree_util.tree_flatten_with_path(
        ospecs, is_leaf=lambda x: isinstance(x, P))[0]
    expert_moment_specs = [
        spec for path, spec in flat
        if "experts" in (names := tuple(
            getattr(k, "key", getattr(k, "name", str(k))) for k in path))
        and "fc1" in names and names[-1] == "kernel" and len(spec) >= 2
    ]
    # Adam has mu and nu subtrees, each mirroring the param tree
    assert len(expert_moment_specs) >= 2, (
        f"no expert-moment specs matched: {[p for p, _ in flat][:5]}..."
    )
    for spec in expert_moment_specs:
        assert spec[1] == "ep", f"expert moment lost ep sharding: {spec}"


def test_group_size_invariance_with_ample_capacity():
    """With capacity pressure absent, routing is per-token independent, so
    the grouped computation (moe_group_size < seq) must equal ungrouped."""
    cfg = tiny_cfg(moe_capacity_factor=8.0, moe_min_capacity=64)
    key = jax.random.PRNGKey(0)
    p = init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    cfg.model.moe_group_size = 64
    out_full, _ = moe_sublayer(cfg, p, x)
    cfg.model.moe_group_size = 16
    out_grouped, _ = moe_sublayer(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out_grouped), np.asarray(out_full),
                               rtol=2e-5, atol=2e-5)


def test_moe_kv_cached_decode_matches_full_forward():
    """Greedy KV-cached decode through MoE layers must match the full-context
    forward (the routing of a token must not depend on decode chunking)."""
    from megatron_llm_tpu.generation.generation import generate_tokens
    from megatron_llm_tpu.models import model_forward

    cfg = tiny_cfg(moe_capacity_factor=8.0, moe_min_capacity=64,
                   seq_length=48)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    total = 20
    tokens = np.zeros((1, total), np.int32)
    tokens[:, :8] = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (1, 8), 0, cfg.model.vocab_size))
    out = generate_tokens(
        cfg, params, tokens, jnp.full((1,), 8, jnp.int32),
        jnp.int32(total), prefill_len=8,
        termination_id=cfg.model.vocab_size + 1,  # never fires
        sample_key=jax.random.PRNGKey(0), top_k=1,  # greedy
    )
    seq = out.tokens
    logits, _ = model_forward(cfg, params, seq[:, :-1])
    argmax = np.asarray(jnp.argmax(logits[..., :cfg.model.vocab_size], -1))
    gen = np.asarray(seq)
    for t in range(8, 20):
        assert gen[0, t] == argmax[0, t - 1], (
            f"decode diverges from teacher-forced argmax at {t}"
        )


def test_ep_with_context_parallel_parity():
    """MoE composed with ring-attention context parallelism: ep2 x cp2 x tp2
    loss matches the unsharded computation."""
    cfg = tiny_cfg(seq_length=64)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=2)

    ref_mesh = build_mesh(devices=jax.devices()[:1])
    ref_loss, _ = _loss_and_grads(cfg, ref_mesh, params, batch)

    cfg2 = tiny_cfg(seq_length=64)
    cfg2.parallel.expert_parallel_size = 2
    cfg2.parallel.tensor_model_parallel_size = 2
    cfg2.parallel.context_parallel_size = 2
    cfg2.parallel.data_parallel_size = 2
    mesh = build_mesh(
        tensor_model_parallel_size=2, context_parallel_size=2,
        data_parallel_size=2, expert_parallel_size=2,
    )
    loss, _ = _loss_and_grads(cfg2, mesh, params, batch)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)


def test_moe_gpipe_pipeline_matches_unpipelined():
    """MoE under the GPipe schedule (pp=2): loss incl. the router aux term
    and grads (incl. router/expert grads through the aux loss) match the
    unsharded computation. Note the aux normalizations differ slightly by
    construction — the pipeline averages the per-microbatch balance loss
    (matching the pp=1 grad-accumulation mean) while the reference here
    computes it over the full batch; with coeff 0.01 the gap is ~1e-5 and
    sits inside the tolerance."""
    from megatron_llm_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = tiny_cfg(seq_length=32, global_batch_size=4)
    cfg.parallel.pipeline_model_parallel_size = 2
    cfg.parallel.pipeline_schedule = "gpipe"
    cfg.parallel.num_micro_batches = 2
    cfg.finalize()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=4)

    cfg1 = tiny_cfg(seq_length=32, global_batch_size=4)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: loss_from_batch(cfg1, p, batch, deterministic=True)[0]
    ))(params)

    mesh = build_mesh(pipeline_model_parallel_size=2,
                      devices=jax.devices()[:2])
    with global_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: pipeline_loss_fn(cfg, mesh, p, batch, num_micro=2)[0]
        ))(params)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        # same tolerance as the dense GPipe parity suite (test_pipeline.py):
        # the scan-transpose backward reorders fp32 accumulations
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=5e-4,
            err_msg=f"grad mismatch at {pa}",
        )


def test_moe_interleaved_gpipe_pipeline_matches_unpipelined():
    """MoE + virtual-pipeline GPipe (pp=2, vpp=2): the per-chunk aux
    accumulation must still count every layer exactly once per microbatch."""
    from megatron_llm_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = tiny_cfg(seq_length=32, global_batch_size=4, num_layers=4)
    cfg.parallel.pipeline_model_parallel_size = 2
    cfg.parallel.pipeline_schedule = "gpipe"
    cfg.parallel.virtual_pipeline_model_parallel_size = 2
    cfg.parallel.num_micro_batches = 2
    cfg.finalize()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=4)

    cfg1 = tiny_cfg(seq_length=32, global_batch_size=4, num_layers=4)
    ref_loss = float(jax.jit(
        lambda p: loss_from_batch(cfg1, p, batch, deterministic=True)[0]
    )(params))

    mesh = build_mesh(pipeline_model_parallel_size=2,
                      devices=jax.devices()[:2])
    with global_mesh(mesh):
        loss, mets = jax.jit(
            lambda p: pipeline_loss_fn(cfg, mesh, p, batch, num_micro=2)
        )(params)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=2e-5)
    assert np.isfinite(float(mets["moe aux loss"]))


def _moe_1f1b_parity(vpp, num_layers):
    """MoE under the true-1F1B schedules (round-3 VERDICT item 3): the
    router aux term enters the loss and its gradient reaches the router
    and expert weights via the per-stage vjp aux seed — parity with the
    unpipelined computation, mirroring test_pipeline.py's dense suite."""
    from megatron_llm_tpu.parallel.pipeline import (
        pipeline_1f1b_interleaved_loss_and_grads,
        pipeline_1f1b_loss_and_grads,
    )

    cfg = tiny_cfg(seq_length=32, global_batch_size=4, num_layers=num_layers)
    cfg.parallel.pipeline_model_parallel_size = 2
    cfg.parallel.pipeline_schedule = "1f1b"
    if vpp > 1:
        cfg.parallel.virtual_pipeline_model_parallel_size = vpp
    cfg.parallel.num_micro_batches = 4
    cfg.finalize()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=4)

    cfg1 = tiny_cfg(seq_length=32, global_batch_size=4,
                    num_layers=num_layers)
    cfg1.parallel.num_micro_batches = 4
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: loss_from_batch(cfg1, p, batch, deterministic=True)[0]
    ))(params)

    engine = (pipeline_1f1b_interleaved_loss_and_grads if vpp > 1
              else pipeline_1f1b_loss_and_grads)
    mesh = build_mesh(pipeline_model_parallel_size=2,
                      devices=jax.devices()[:2])
    with global_mesh(mesh):
        loss, grads = jax.jit(
            lambda p: engine(cfg, mesh, p, batch, num_micro=4)
        )(params)

    # the aux normalization gap vs the full-batch reference is ~coeff*1e-3
    # (same situation as the GPipe parity test's docstring)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-4)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=5e-4,
            err_msg=f"grad mismatch at {pa}",
        )


def test_moe_1f1b_pipeline_matches_unpipelined():
    _moe_1f1b_parity(vpp=1, num_layers=2)


def test_moe_interleaved_1f1b_pipeline_matches_unpipelined():
    _moe_1f1b_parity(vpp=2, num_layers=4)


def test_expert_choice_routing_is_balanced():
    """EC routing: every expert fills exactly C slots with its top-C tokens
    by affinity (Zhou et al. 2022) — balanced by construction."""
    from megatron_llm_tpu.models.moe import route_expert_choice

    cfg = tiny_cfg(moe_router_type="expert_choice")
    g_, t_, e_, cap = 2, 16, 4, 4
    logits = jax.random.normal(jax.random.PRNGKey(0), (g_, t_, e_))
    combine, dispatch, aux = route_expert_choice(cfg, logits, cap)
    # each (expert, slot) seats exactly one token
    np.testing.assert_array_equal(
        np.asarray(dispatch.sum(1)), np.ones((g_, e_, cap)))
    # seated tokens are the top-C by affinity
    probs = np.asarray(jax.nn.softmax(logits, -1))
    for g in range(g_):
        for e in range(e_):
            seated = set(np.where(np.asarray(dispatch)[g, :, e].any(-1))[0])
            want = set(np.argsort(-probs[g, :, e])[:cap])
            assert seated == want
    # aux[0] reports EC's health signal: the dropped-token fraction
    # (tokens selected by NO expert) — metric-only, never enters the loss
    # (aux_loss_coeffs zeroes the balance coefficient for expert_choice)
    covered = np.asarray(dispatch).any(axis=(2, 3))  # [G, T]
    expected_dropped = 1.0 - covered.mean()
    np.testing.assert_allclose(float(aux[0]), expected_dropped, rtol=1e-6)
    assert 0.0 <= float(aux[0]) < 1.0


def test_expert_choice_capacity_clamps_to_group():
    """EC capacity never exceeds tokens-per-group (top_k would reject k > T):
    few-expert configs and s=1 decode groups must not crash."""
    from megatron_llm_tpu.models.moe import (
        init_moe_params,
        moe_capacity_expert_choice,
    )

    cfg = tiny_cfg(num_experts=2, moe_router_topk=1,
                   moe_router_type="expert_choice", moe_capacity_factor=4.0)
    assert moe_capacity_expert_choice(cfg, 16) == 16  # ceil(16*4/2)=32 -> 16
    assert moe_capacity_expert_choice(cfg, 1) == 1    # decode: one token
    p = init_moe_params(cfg, jax.random.PRNGKey(0))
    out, _ = moe_sublayer(cfg, p, jax.random.normal(
        jax.random.PRNGKey(1), (2, 1, cfg.model.hidden_size)))
    assert out.shape == (2, 1, cfg.model.hidden_size)


def test_expert_choice_balance_term_not_in_loss():
    """EC's constant balance metric must not offset the trained loss."""
    from megatron_llm_tpu.models.moe import aux_loss_coeffs

    cfg = tiny_cfg(moe_router_type="expert_choice")
    assert aux_loss_coeffs(cfg)[0] == 0.0
    cfg2 = tiny_cfg()
    assert aux_loss_coeffs(cfg2)[0] == cfg2.model.moe_aux_loss_coeff


def test_expert_choice_model_trains():
    cfg = tiny_cfg(moe_router_type="expert_choice", global_batch_size=2)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), gbs=2)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(
            lambda q: loss_from_batch(cfg, q, batch, deterministic=True)[0]
        )(p)
        return loss, jax.tree.map(lambda w, gg: w - 0.3 * gg, p, g)

    p = params
    losses = []
    for _ in range(15):
        loss, p = step(p)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_moe_checkpoint_reshard_round_trip(tmp_path):
    """Mixtral checkpoints reshard through tools/checkpoint_util (expert
    stacks are plain pytree leaves with generic sharding rules, so the
    vocab-repad + parallel-config rewrite must pass them through intact)."""
    import sys
    from pathlib import Path

    import orbax.checkpoint as ocp

    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    from checkpoint_util import reshard_checkpoint

    from megatron_llm_tpu.checkpointing import save_checkpoint

    cfg = tiny_cfg()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    save_checkpoint(cfg, str(tmp_path / "src"), 3, params)
    meta = reshard_checkpoint(str(tmp_path / "src"), str(tmp_path / "dst"),
                              target_tp=2, target_pp=1)
    assert meta["config"]["parallel"]["tensor_model_parallel_size"] == 2
    restored = ocp.StandardCheckpointer().restore(
        str(tmp_path / "dst" / "iter_0000003" / "params"))
    np.testing.assert_array_equal(
        np.asarray(restored["layers"]["moe"]["experts"]["fc1"]["kernel"]),
        np.asarray(params["layers"]["moe"]["experts"]["fc1"]["kernel"]))


def test_moe_generation_server_roundtrip():
    """The REST server generates from a Mixtral-family model (KV-cached MoE
    decode behind the full serving stack)."""
    from megatron_llm_tpu.generation import InferenceEngine
    from megatron_llm_tpu.generation.server import MegatronServer

    class ToyTok:
        eod = 0
        bos = 1

        @property
        def vocab_size(self):
            return 64

        def tokenize(self, text):
            return [2 + (ord(c) % 62) for c in text]

        def detokenize(self, ids):
            return "".join(chr(97 + (i % 26)) for i in ids if i >= 2)

    cfg = tiny_cfg(vocab_size=64, seq_length=64, moe_capacity_factor=8.0,
                   moe_min_capacity=64)
    cfg.inference.max_tokens_to_oom = 256
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    server = MegatronServer(InferenceEngine(cfg, params, ToyTok()))
    status, body = server.handle_request(
        {"prompts": ["hello moe"], "tokens_to_generate": 8}
    )
    assert status == 200, body
    assert len(body["text"]) == 1 and isinstance(body["text"][0], str)


def test_moe_rejects_encoder_families():
    with pytest.raises(AssertionError):
        make_config("bert", vocab_size=256, num_experts=4)


def test_mixtral_family_config():
    cfg = make_config("mixtral", vocab_size=256)
    assert cfg.model.num_experts == 8
    assert cfg.model.moe_router_topk == 2
    # finalize rejects ep>1 without MoE
    with pytest.raises(AssertionError):
        make_config("llama2", vocab_size=256, expert_parallel_size=2)


# ---------------------------------------------------------------------------
# the grouped kernel on a stack of layers (the serving tick's form)
# ---------------------------------------------------------------------------

from unittest import mock  # noqa: E402

from megatron_llm_tpu.models import moe  # noqa: E402


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("form", ["fc2", "glu_half_1", "transposed"])
def test_stacked_kernel_is_told_of_one_layers_groups(layer, form):
    """The TPU path in interpret mode: the grouped kernel handed the whole
    stack ``[L, E, (2,) k, n]`` with ONE layer's group sizes and the place
    in the stack where they start (megablox reads group ``g``'s weights at
    ``g - group_offset``) gives what the layer's own slice gives, for an
    fc2, a GLU half (every second group of the layer) and a transposed
    fc1; rows behind the last group belong to none."""
    import functools
    import importlib

    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    half = 1 if form == "glu_half_1" else None
    transposed = form == "transposed"
    lead = (3, 4, 2) if half is not None else (3, 4)
    stack = jax.random.normal(jax.random.PRNGKey(0), lead + (128, 256),
                              jnp.float32)
    if transposed:
        stack = stack.swapaxes(-1, -2)
    rows = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    counts = jnp.asarray([70, 0, 9, 131], jnp.int32)       # 210 of 256 rows
    want = moe.grouped_matmul(rows, stack, counts, jnp.asarray(layer), half,
                              transposed)                  # ragged_dot
    with mock.patch("megatron_llm_tpu.core.parallel_state.target_platform",
                    lambda: "tpu"), \
            mock.patch.object(megablox, "gmm", functools.partial(
                megablox.gmm, interpret=True)):
        got = jax.jit(lambda l: moe.grouped_matmul(
            rows, stack, counts, l, half, transposed))(jnp.asarray(layer))
        # one layer's own leaf: the form the trainer's scanned slice takes
        alone = moe.grouped_matmul(rows, stack[layer], counts, None, half,
                                   transposed)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    np.testing.assert_allclose(np.asarray(got[:210]), np.asarray(want[:210]),
                               rtol=0, atol=1e-4)
    assert float(jnp.abs(got[:210]).max()) > 1
