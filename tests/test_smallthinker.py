"""SmallThinker-21BA3B (ISSUE 33): a layer pattern of one full NoPE layer to
three window RoPE layers, a router that reads the attention's input, ReGLU
experts of which a chip may hold a share — at tiny widths with the real
structure (4 layers = one period, window 32 on sequences of 96, 16 experts
top-3, GQA 4 / 2 heads), seeded random float32 weights, against
``benchmark/reference/smallthinker_block.py``.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums only (a sorted grouped GEMM against a masked loop over
every expert, one fused QKV product against the same columns read apart):
logits agree to ``ATOL`` = 2e-5, gradients to ``GRAD_ATOL`` = 2e-6 (the
largest gradient leaf entry is ~1e-2).  The router's choice is discrete; at
float32 on both sides no token sits within rounding of a tie at these sizes
(the forward test would show it).  Each planted fault of ISSUE 33 lands
orders of magnitude outside the tolerance (``FAULT_FLOOR``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import smallthinker_block as ref
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.models import (
    init_model_params,
    loss_from_batch,
    make_config,
    moe,
)
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.language_model import (
    make_rope_cache,
    model_forward,
)

ATOL = 2e-5
GRAD_ATOL = 2e-6
FAULT_FLOOR = 1e-3       # every planted fault moves some logit by more
VOCAB, SEQ, WINDOW = 256, 96, 32

WIDTHS = dict(
    num_layers=4, hidden_size=64, num_attention_heads=4,
    num_attention_heads_kv=2, kv_channels=16, num_experts=16,
    moe_router_topk=3, moe_ffn_hidden_size=32, sliding_window_size=WINDOW,
    vocab_size=VOCAB, params_dtype="float32", use_flash_attn=False,
    max_position_embeddings=128, seq_length=SEQ)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             rms_norm_eps=1e-6, rope_theta=1_500_000,
             sliding_window_size=WINDOW, sliding_window_layout=[0, 1, 1, 1],
             rope_layout=[0, 1, 1, 1], moe_num_active_primary_experts=3)


def st_cfg(**kw):
    return make_config("smallthinker", **{**WIDTHS, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = st_cfg()
    return cfg, init_model_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + 1), 0, VOCAB)


def batch_of(tokens):
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
            "loss_mask": jnp.ones(tokens[:, 1:].shape, jnp.float32)}


def reference_loss(params, tokens, model=MODEL):
    logits = ref.logits(params, tokens[:, :-1], model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()


def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert (m.layer_period, m.moe_router_input, m.glu_activation) == (
        4, "layer_input", "reglu")
    kinds = tfm.layer_kinds(cfg)
    assert kinds == (tfm.LayerKind(None, False),) + 3 * (
        tfm.LayerKind(WINDOW, True),)
    assert [k.scope for k in kinds] == ["global"] + 3 * ["window"]
    layer = params["layers"]
    assert layer["moe"]["experts"]["fc1"]["kernel"].shape == (4, 16, 2, 64, 32)
    assert layer["moe"]["router"]["kernel"].shape == (4, 64, 16)
    assert layer["attention"]["qkv"]["kernel"].shape == (4, 64, 8 * 16)
    assert "mlp" not in layer and "shared" not in layer["moe"]
    big = make_config("smallthinker-21b-a3b")
    bm = big.model
    assert (bm.num_layers, bm.hidden_size, bm.num_attention_heads,
            bm.num_attention_heads_kv, bm.kv_channels, bm.num_experts,
            bm.moe_router_topk, bm.moe_ffn_hidden_size, bm.vocab_size,
            bm.sliding_window_size, bm.rope_theta, bm.layernorm_epsilon) == (
        52, 2560, 28, 4, 128, 64, 6, 768, 151936, 4096, 1.5e6, 1e-6)
    # a uniform model is a period of one kind
    assert tfm.layer_kinds(make_config("mistral", num_layers=2)) == (
        tfm.LayerKind(4096, True),)


def test_held_share_parameter_tree():
    cfg = st_cfg(moe_experts_held=4, moe_first_held_expert=8)
    layer = init_model_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert layer["moe"]["experts"]["fc1"]["kernel"].shape == (4, 4, 2, 64, 32)
    assert layer["moe"]["experts"]["fc2"]["kernel"].shape == (4, 4, 32, 64)
    assert layer["moe"]["router"]["kernel"].shape == (4, 64, 16)


def test_dense_forward_matches_reference(model, tokens):
    cfg, params = model
    out, _ = model_forward(cfg, params, tokens[:, :-1])
    want = ref.logits(params, tokens[:, :-1], MODEL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("chunks", [None, 4])
def test_loss_matches_reference(model, tokens, chunks):
    """The trained loss, through the plain head and the chunked one."""
    cfg, params = model
    cfg = st_cfg(ce_vocab_chunks=chunks)
    _, mets = loss_from_batch(cfg, params, batch_of(tokens))
    np.testing.assert_allclose(float(mets["lm loss"]),
                               float(reference_loss(params, tokens)),
                               rtol=0, atol=ATOL)
    assert float(mets["moe assignments"]) == 4 * 2 * SEQ * 3
    assert float(mets["moe held"]) == float(mets["moe assignments"])
    assert float(mets["moe dropped"]) == 0


def test_gradients_match_reference(model, tokens):
    """jax.grad of the program's loss (custom gathers of the dispatch and
    the combine, the period scan, remat) against jax.grad of the reference."""
    cfg, params = model
    cfg = st_cfg(moe_aux_loss_coeff=0.0, recompute_granularity="full")
    got = jax.grad(lambda p: loss_from_batch(cfg, p, batch_of(tokens))[0])(
        params)
    want = jax.grad(lambda p: reference_loss(p, tokens))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0, atol=GRAD_ATOL,
            err_msg=jax.tree_util.keystr(path))
    # the router learns through the weights of the chosen experts
    assert float(jnp.abs(got["layers"]["moe"]["router"]["kernel"]).max()) > 1e-5


def _one_layer(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def _share_of(layer, first, held):
    out = jax.tree.map(lambda a: a, layer)
    out["moe"] = {**layer["moe"], "experts": jax.tree.map(
        lambda a: a[first:first + held], layer["moe"]["experts"])}
    return out


@pytest.mark.parametrize("place", [0, 1])
def test_four_shares_add_up_to_the_uncut_layer(model, place):
    """Experts 0-3, 4-7, 8-11, 12-15 on four 'chips': the parts of y their
    programs compute add up to the uncut reference's layer; x1, which every
    chip computes alike, is counted once."""
    cfg, params = model
    layer = _one_layer(params, place)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)
    kind = tfm.layer_kinds(cfg)[place]
    rope = make_rope_cache(cfg)
    whole = ref.block(layer, x, MODEL, place)
    zeroed = jax.tree.map(lambda a: a, layer)
    zeroed["moe"] = {**layer["moe"], "experts": jax.tree.map(
        jnp.zeros_like, layer["moe"]["experts"])}
    x1 = ref.block(zeroed, x, MODEL, place)      # no expert adds anything
    total = x1
    for first in (0, 4, 8, 12):
        share_cfg = st_cfg(moe_experts_held=4, moe_first_held_expert=first,
                           moe_capacity_factor=4.0)
        out, _, aux = tfm.block_forward(
            share_cfg, _share_of(layer, first, 4), x, rope=rope, kind=kind)
        assert float(aux[5]) == 0 and 0 < float(aux[4]) < float(aux[2])
        total = total + (out - x1)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_a_share_matches_the_reference_given_the_same_share(model, tokens,
                                                            first):
    """The whole model on one share, program against reference: the partial
    result is what goes on to the next layer, in both."""
    cfg, params = model
    share_cfg = st_cfg(moe_experts_held=4, moe_first_held_expert=first,
                       moe_capacity_factor=4.0)
    share = {**params, "layers": jax.vmap(
        lambda l: _share_of(l, first, 4))(params["layers"])}
    out, _ = model_forward(share_cfg, share, tokens[:, :-1])
    want = ref.logits(share, tokens[:, :-1],
                      {**MODEL, "first_held_expert": first})
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=ATOL)
    whole = ref.logits(params, tokens[:, :-1], MODEL)
    assert float(jnp.abs(want - whole).max()) > FAULT_FLOOR   # a real cut


def test_full_row_buffer_drops_and_counts():
    """A share's row buffer is static; an assignment that finds it full is
    dropped and counted, the rest come out as if nothing had happened."""
    cfg = st_cfg(moe_experts_held=4, moe_first_held_expert=0,
                 moe_capacity_factor=0.25)
    t_, k_ = 1024, 3
    assert moe.held_rows(cfg, t_ * k_) == 512      # of ~768 expected
    p = moe.init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (t_, 64), jnp.float32)
    idx, w, counts, _ = moe.route(cfg, p["router"], x)
    out, ran, dropped = moe.dropless_experts(cfg, p["experts"], x, idx, w,
                                             counts)
    given = float(counts[:4].sum())
    assert float(ran) == 512 and float(dropped) == given - 512 > 0
    assert bool(jnp.isfinite(out).all())
    roomy = st_cfg(moe_experts_held=4, moe_capacity_factor=4.0)
    assert moe.held_rows(roomy, t_ * k_) == t_ * k_     # never more than all
    full, ran_all, none = moe.dropless_experts(roomy, p["experts"], x, idx, w,
                                               counts)
    assert float(ran_all) == given and float(none) == 0
    # tokens whose held assignments all got a row are untouched by the drop
    same = jnp.abs(out - full).max(-1) < ATOL
    assert 0.3 < float(same.mean()) < 1.0


def test_published_router_order_equals_the_programs():
    """Published: top-k of the logits, softmax over the k chosen.  Program
    (`route`): softmax over all, top-k, renormalise.  The same numbers."""
    cfg = st_cfg()
    p = moe.init_moe_params(cfg, jax.random.PRNGKey(0))
    p["router"]["kernel"] = 3.0 * jax.random.normal(
        jax.random.PRNGKey(5), (64, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (512, 64), jnp.float32)
    idx, w, counts, aux = moe.route(cfg, p["router"], x)
    dense = jnp.zeros((512, 16)).at[jnp.arange(512)[:, None], idx].set(w)
    want = ref.router_weights(x @ p["router"]["kernel"], 3)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want), rtol=0,
                               atol=1e-6)
    assert float(counts.sum()) == 512 * 3 == float(aux[2])


FAULTS = {
    "window_ignored": dict(sliding_window_layout=(0, 0, 0, 0)),
    "rope_in_the_full_layers": dict(rope_layout=(1, 1, 1, 1)),
    "router_fed_the_post_attention_norm": dict(
        moe_router_input="post_attention"),
    "forward_in_bf16": dict(params_dtype="bfloat16"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_fault_fails_the_comparison(model, tokens, fault):
    """The four faults ISSUE 33 plants on the chip, here at tiny widths:
    each lands outside the tolerance the honest program keeps."""
    from megatron_llm_tpu.config.arguments import _set_flag

    _, params = model
    cfg = st_cfg()
    for k, v in FAULTS[fault].items():      # past the family's own checks
        _set_flag(cfg, k, v)
    out, _ = model_forward(cfg, params, tokens[:, :-1])
    want = ref.logits(params, tokens[:, :-1], MODEL)
    worst = float(jnp.abs(out.astype(jnp.float32) - want).max())
    assert worst > FAULT_FLOOR > 10 * ATOL, worst


def _legacy_scan_loss(cfg, params, batch):
    """The uniform stack as `transformer_forward` ran it before the period
    scan: one `lax.scan` over layers, the checkpointed body a layer."""
    from megatron_llm_tpu.models import language_model as lm

    hidden = lm.embed_tokens(cfg, params, batch["tokens"])
    rope = make_rope_cache(cfg)
    layers = params["layers"]

    def one_layer(h, layer):
        out, _, aux = tfm.block_forward(cfg, layer, h, rope=rope)
        return out, aux

    body = jax.checkpoint(
        one_layer, policy=tfm._remat_policy(cfg.training.remat_policy),
        prevent_cse=False)
    hidden, _ = jax.lax.scan(body, hidden, layers)
    hidden = lm.norm(hidden, params["final_norm"], cfg.model.layernorm_epsilon,
                     cfg.model.use_rms_norm)
    loss = lm.softmax_cross_entropy(
        lm.compute_logits(cfg, params, hidden), batch["labels"])
    return (loss * batch["loss_mask"]).sum() / batch["loss_mask"].sum()


@pytest.mark.parametrize("what", ["loss", "grads"])
def test_uniform_model_is_bit_identical_under_the_period_scan(tokens, what):
    """A Mistral preset (one kind, a period of one) through the period scan
    gives the loss and the gradients of the scan over layers, bit for bit."""
    cfg = make_config(
        "mistral", num_layers=3, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=96, vocab_size=VOCAB,
        sliding_window_size=WINDOW, params_dtype="float32",
        use_flash_attn=False, max_position_embeddings=128, seq_length=SEQ,
        recompute_granularity="selective")
    params = init_model_params(cfg, jax.random.PRNGKey(2))
    batch = batch_of(tokens)
    new = lambda p: loss_from_batch(cfg, p, batch)[0]          # noqa: E731
    old = lambda p: _legacy_scan_loss(cfg, p, batch)           # noqa: E731
    if what == "loss":
        assert float(jax.jit(new)(params)) == float(jax.jit(old)(params))
        return
    got, want = jax.jit(jax.grad(new))(params), jax.jit(jax.grad(old))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_unscanned_stack_equals_the_period_scan(model, tokens):
    cfg, params = model
    out, _ = model_forward(cfg, params, tokens[:, :-1])
    loop, _ = model_forward(st_cfg(scan_layers=False), params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(loop), np.asarray(out), rtol=0,
                               atol=ATOL)


def test_two_periods_scan(tokens):
    """Eight layers: the scan makes two turns of the unrolled period."""
    cfg = st_cfg(num_layers=8)
    params = init_model_params(cfg, jax.random.PRNGKey(4))
    out, _ = model_forward(cfg, params, tokens[:, :-1])
    want = ref.logits(params, tokens[:, :-1], MODEL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=ATOL)


def test_incremental_decode_follows_the_pattern(model, tokens):
    """The dense incremental cache takes window and rotation from the
    layer's kind too: prefill + one-token steps equal the full forward."""
    cfg, params = model
    toks = tokens[:1, :48]
    full, _ = model_forward(cfg, params, toks)
    m = cfg.model
    cache = tuple(jnp.zeros((m.num_layers, 1, 64, m.num_attention_heads_kv,
                             m.kv_channels), jnp.float32) for _ in range(2))
    out, cache = model_forward(cfg, params, toks[:, :40], kv_caches=cache,
                               cache_index=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, :40]),
                               rtol=0, atol=ATOL)
    for i in range(40, 48):
        pos = jnp.full((1, 1), i, jnp.int32)
        step, cache = model_forward(cfg, params, toks[:, i:i + 1],
                                    position_ids=pos, kv_caches=cache,
                                    cache_index=i)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, i]), rtol=0, atol=ATOL)


def test_train_moe_span_rides_the_metric_drain():
    """`pretrain` emits a zero-length `train-moe` span a step, with what the
    step's routers did, from the metrics it drains anyway."""
    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.observability import trace as trace_mod
    from megatron_llm_tpu.training import pretrain

    flags = {**WIDTHS, "model_name": "smallthinker", "train_iters": 3,
             "micro_batch_size": 1, "global_batch_size": 1,
             "moe_experts_held": 4, "moe_first_held_expert": 4,
             "tokenizer_type": "NullTokenizer", "eval_iters": 0,
             "log_interval": 10 ** 9, "lr": 1e-4}
    argv = []
    for k, v in flags.items():
        argv += ["--" + k, str(v)]
    cfg = parse_args(argv, n_devices=1)

    def provider(_cfg, _tok, _consumed):
        def draws():
            key = jax.random.PRNGKey(0)
            while True:
                key, sub = jax.random.split(key)
                t = np.asarray(jax.random.randint(sub, (1, SEQ + 1), 0, VOCAB))
                yield {"tokens": t[:, :-1], "labels": t[:, 1:],
                       "loss_mask": np.ones((1, SEQ), np.float32)}
        return draws(), None

    tracer = trace_mod.configure()
    try:
        pretrain(cfg, data_iterators_provider=provider)
        spans = [e for e in tracer.snapshot() if e[1] == "train-moe"]
    finally:
        trace_mod.disable()
    assert len(spans) == 3
    for _, _, _, _, _, args in spans:
        assert args["assignments"] == 4 * SEQ * 3 and args["dropped"] == 0
        assert 0 < args["held"] < args["assignments"]
    assert sorted(a[5]["step"] for a in spans) == [1, 2, 3]


REFUSALS = {
    "pipeline_stage_not_whole_periods": (
        dict(num_layers=8, pipeline_model_parallel_size=4),
        "a pipeline stage holds whole periods"),
    "context_parallel": (
        dict(context_parallel_size=2),
        "a layer pattern with context parallelism is not written"),
    "layouts_of_two_lengths": (
        dict(rope_layout=(0, 1)), "one period each, of the same length"),
    "depth_not_whole_periods": (
        dict(num_layers=6), "not a whole number of periods"),
    "window_layers_without_a_window": (
        dict(sliding_window_size=None), "set sliding_window_size"),
    "held_experts_outside_the_router": (
        dict(moe_experts_held=8, moe_first_held_expert=12),
        "lie outside the router's 16"),
    "held_experts_under_expert_parallelism": (
        dict(moe_experts_held=8, expert_parallel_size=2,
             data_parallel_size=2),
        "moe_experts_held is one chip's share"),
    "router_input_unknown": (
        dict(moe_router_input="embedding"), "unknown moe_router_input"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_start_up_refusals(case):
    flags, sentence = REFUSALS[case]
    with pytest.raises(AssertionError, match=sentence):
        cfg = make_config("smallthinker", **{**WIDTHS, **flags})
        cfg.finalize(n_devices=8)


def test_family_requires_its_pattern():
    with pytest.raises(ValueError, match="mixes full and window layers"):
        make_config("smallthinker", **{**WIDTHS,
                                       "sliding_window_layout": (1, 1, 1, 1),
                                       "rope_layout": (1, 1, 1, 1)})
    with pytest.raises(ValueError, match="reads the layer input"):
        st_cfg(moe_router_input="post_attention")


def test_a_patterned_layer_must_be_told_its_kind(model):
    cfg, params = model
    x = jnp.zeros((1, SEQ, 64), jnp.float32)
    with pytest.raises(AssertionError, match="must be told its kind"):
        tfm.block_forward(cfg, _one_layer(params, 0), x,
                          rope=make_rope_cache(cfg))


@pytest.mark.parametrize("case", ["pattern", "share"])
def test_engine_serves_what_it_refused(model, case):
    """Since PR 39 the engine serves a pattern (two page classes: its full
    layer first here, where Command A+ has it last) and a share of the
    experts; where this test stood, it held the engine to refusing both.
    Compared with the plain reference (the pattern) and with the dense
    forward on the same share (mixtral has no reference of its own)."""
    cfg, params = model
    if case == "share":
        cfg = make_config(
            "mixtral", num_layers=2, hidden_size=64, num_attention_heads=4,
            num_attention_heads_kv=2, ffn_hidden_size=32, num_experts=8,
            moe_experts_held=2, moe_capacity_factor=8.0, vocab_size=VOCAB,
            params_dtype="float32", use_flash_attn=False)
        params = init_model_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_seq=128,
                                   page_size=8, prefill_chunk=16)
    assert (eng.wpool is not None) == (case == "pattern")
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, VOCAB, 90)]
    req = eng.submit(prompt, 16, top_k=1, termination_id=10 ** 9)
    eng.run_until_idle()
    toks, lps = req.result(timeout=120)
    seq = jnp.asarray([toks], jnp.int32)
    logits = (ref.logits(params, seq, MODEL) if case == "pattern"
              else model_forward(cfg, params, seq)[0])
    logp = jax.nn.log_softmax(logits[0, len(prompt) - 1:-1], axis=-1)
    want = np.asarray(jnp.take_along_axis(
        logp, seq[0, len(prompt):, None], axis=-1))[:, 0]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=1e-4)
    if case == "pattern":
        assert eng.window_pages_released > 0      # 90 tokens, window 32
    else:
        assert 0 < eng.moe_held_assignments < eng.moe_assignments
