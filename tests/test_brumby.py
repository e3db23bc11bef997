"""Brumby (``brumby``: manifestai Brumby-14B-Base) on the normal serving
path, at tiny widths on the CPU: every layer gated degree-2 power retention
(8 query / 2 KV heads of 16, a feature map of 192 values), served through
``ContinuousBatchingEngine`` on a STATE pool (one float32 recurrent state a
sequence, no keys, no pages).  Everything is compared with the plain
reference (``benchmark/reference/brumby_block.py``: the attention form,
which never builds a state) on the same weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import brumby_block
from benchmark.reference import common as ref_common
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.pools import (
    StatePool,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.models.transformer import pool_classes
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.ops import retention as ret
from megatron_llm_tpu.ops.pallas import retention as ret_kernel
from tests.parity import assert_memory, assert_memory_idle, held_pages

ATOL = 5e-5
VOCAB = 256
NEVER = 10 ** 9

WIDTHS = dict(
    num_layers=2, hidden_size=64, num_attention_heads=8,
    num_attention_heads_kv=2, kv_channels=16, ffn_hidden_size=96,
    vocab_size=VOCAB, params_dtype="float32", use_flash_attn=False,
    max_position_embeddings=512, seq_length=256)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
             head_dim=16, rms_norm_eps=1e-6, rope_theta=1_000_000,
             assumed={"degree": 2, "eps": 1e-6})


def brumby_cfg(**kw):
    return make_config("brumby", **{**WIDTHS, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = brumby_cfg()
    return cfg, init_model_params(cfg, jax.random.PRNGKey(0))


def reference_log_probs(params, tokens):
    tokens = jnp.asarray([tokens], jnp.int32)
    logits = brumby_block.logits(params, tokens, MODEL)
    return np.asarray(ref_common.token_log_probs(logits, tokens))[0]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, VOCAB, n)] for n in lengths]


def engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, **{**dict(max_slots=4, page_size=8, max_seq=256,
                               prefill_chunk=16), **kw})


def check(req, params, atol=ATOL):
    tokens, lps = req.result(timeout=120)
    want = reference_log_probs(params, tokens)[len(req.prompt) - 1:]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=atol)


# ---- the family and the three forms ----------------------------------------

def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert m.retention and m.use_rms_norm and not m.tie_embed_logits
    att = params["layers"]["attention"]
    assert set(att) == {"qkv", "dense", "q_norm", "k_norm", "gate"}
    assert att["gate"]["kernel"].shape == (2, 64, 2)
    # the gate's bias is drawn so that a key 2,048 tokens back keeps
    # 0.1 to 0.9 of its weight: a state fault stays visible
    keep = np.exp(2048 * np.asarray(
        jax.nn.log_sigmoid(att["gate"]["bias"])))
    assert ((keep > 0.09) & (keep < 0.91)).all()
    assert ret.feature_dim(16) == 192 and ret.feature_dim(128) == 8704
    (only,) = pool_classes(cfg)          # one class, and it keeps no keys
    assert only.state and only.name == "state" and only.places == (0,)
    with pytest.raises(ValueError, match="requires attention_type"):
        brumby_cfg(attention_type="mha")
    with pytest.raises(AssertionError, match="no window or pattern"):
        brumby_cfg(sliding_window_size=32)
    big = make_config("brumby-14b")
    assert (big.model.num_layers, big.model.num_attention_heads,
            big.model.num_attention_heads_kv, big.model.ffn_hidden_size,
            big.model.vocab_size, big.model.rope_theta) == (
        40, 40, 8, 17408, 151936, 1_000_000.0)


def _rows(seed, s, gate_at=None):
    b, n, nkv, d = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, n, d))
    k = jax.random.normal(ks[1], (b, s, nkv, d))
    v = jax.random.normal(ks[2], (b, s, nkv, d))
    ld = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, s, nkv)) + 4)
    if gate_at is not None:     # a gate near 0: the past all but forgotten
        ld = ld.at[:, gate_at].set(jnp.log(1e-3))
    return q, k, v, ld


def _weights_sum(q, k, ld):
    """sum_j a_tj of the attention form, [b, s, n]: a row's normaliser."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    big_l = jnp.cumsum(ld, axis=1).transpose(0, 2, 1)
    qk = jnp.einsum("btkgd,bjkd->bkgtj", q.reshape(b, s, nkv, n // nkv, d), k)
    gap = big_l[:, :, None, :, None] - big_l[:, :, None, None, :]
    w = jnp.tril(jnp.exp(jnp.minimum(gap, 0.0)) * qk * qk)
    return np.asarray(w.sum(-1).transpose(0, 3, 1, 2).reshape(b, s, n))


def _assert_forms_agree(q, k, v, ld, chunk):
    """Within 1e-5 in float32.  The recurrent form reads a row's normaliser
    as a 192-term dot product whose terms cancel, so where a row's weights
    sum to less than 1 (a first position whose one product is near zero)
    its rounding is held against that sum, not against 1."""
    want = ret.retention_attention(q, k, v, ld)
    np.testing.assert_allclose(ret.retention_chunked(q, k, v, ld, chunk=chunk),
                               want, rtol=0, atol=1e-5)
    off = np.abs(np.asarray(ret.retention_recurrent(q, k, v, ld) - want))
    held = np.minimum(_weights_sum(q, k, ld), 1.0)[..., None]
    assert (off * held).max() <= 1e-5


@pytest.mark.parametrize("run", [1, 7, 64])
def test_recurrent_chunked_and_attention_forms_agree(run):
    _assert_forms_agree(*_rows(run, 70), chunk=run)


def test_forms_agree_across_a_gate_near_zero():
    q, k, v, ld = _rows(3, 40, gate_at=19)     # inside the second run of 16
    _assert_forms_agree(q, k, v, ld, chunk=16)
    feats = ret.phi(q[0, 0]) @ ret.phi(k[0, 0]).T       # [n, nkv]
    np.testing.assert_allclose(feats, (q[0, 0] @ k[0, 0].T) ** 2,
                               rtol=1e-5, atol=1e-5)


# ---- the kernel against the jnp tick ----------------------------------------

def _tick(items, seed=5):
    """Rows (sequence, slot, position) -> the tick's operands."""
    q, k, v, ld = _rows(seed, 70)
    q = jnp.concatenate([q, q], axis=2)                # 8 query heads
    take = lambda t: jnp.stack([t[b, p] for b, _, p in items])  # noqa: E731
    return (take(q), take(k), take(v), take(ld),
            jnp.asarray([s for _, s, _ in items], jnp.int32),
            jnp.asarray([p for _, _, p in items], jnp.int32))


TICKS = {
    "decode_rows": [(0, 1, 9), (1, 2, 30), (0, 3, 41)],
    "a_prompt_run_among_decode_rows":
        [(0, 1, 9)] + [(1, 2, t) for t in range(20, 31)] + [(0, 3, 41)],
    "dead_rows_and_a_fresh_slot":
        [(0, 0, 3)] + [(0, 1, t) for t in range(6)] + [(0, 0, 0), (0, 0, 0)]
        + [(1, 2, t) for t in range(4, 7)] + [(1, 3, 9), (0, 0, 1)],
    "all_dead": [(0, 0, 0)] * 3,
}


@pytest.mark.parametrize("case", list(TICKS))
def test_kernel_matches_the_jnp_tick(case):
    """Interpret mode, the tiled symmetric layout: decode rows, a prompt
    run, dead rows, and a run at position 0 on a slot full of another
    sequence's state."""
    args = _tick(TICKS[case])
    pool = ret.zero_state((3, 4), 2, 16)
    noise = jax.random.normal(jax.random.PRNGKey(9), pool.s.shape)
    pool = ret.State(noise, jnp.abs(noise[..., :1, :]) + 1.0)
    want_y, want = ret.retention_tick(*args[:4], pool, *args[4:], layer=1)
    got_y, got = ret_kernel.retention_sweep(*args[:4], pool, *args[4:],
                                            jnp.int32(1), interpret=True)
    # a first position's one product can be near zero: its output is a
    # ratio of two small numbers in either form
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=2e-4)
    for a, b in zip(got, want):        # the null slot holds no one's state
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=0, atol=5e-5)
    for a, b in zip(got, pool):        # the other layers: untouched
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[2], b[2])
    live = np.asarray(args[4]) > 0
    untouched = sorted(set(range(1, 4)) - set(np.asarray(args[4])[live]))
    np.testing.assert_array_equal(got.s[1, untouched], pool.s[1, untouched])
    assert not np.asarray(got_y)[~live].any()


# ---- through the engine -------------------------------------------------------

def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jnp.asarray(prompts(100, 100, seed=1), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = brumby_block.logits(params, tokens, MODEL)
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)


def _assert_idle(eng):
    pool = eng.pool
    assert isinstance(pool, StatePool) and eng.cache is None
    assert pool.num_free == eng.max_slots and not pool.refcounts.any()
    assert [cls.width for cls in eng._classes] == [1]
    assert_memory_idle(eng)


def test_engine_matches_reference_and_reuses_slots(model):
    """Prefill in chunks, then decode, two requests of unequal length in
    the same ticks; then MORE requests than slots, so that slots change
    hands: a second sequence on a stale state would read far off."""
    cfg, params = model
    eng = engine(cfg, params, max_slots=2)
    assert eng.pool.kv.s.shape == (2, 3, 2, 16, 192)
    assert eng.pool.kv.s.dtype == jnp.float32 and eng.pages_per_seq == 1
    first = [eng.submit(p, 20, top_k=1, termination_id=NEVER)
             for p in prompts(100, 37)]
    eng.run_until_idle()
    # four more on the two slots: every slot is taken over at least once,
    # and a one-token prompt starts in a decode row at position 0
    later = [eng.submit(p, 12, top_k=1, termination_id=NEVER)
             for p in prompts(53, 1, 18, 70, seed=2)]
    eng.run_until_idle()
    for req in first + later:
        check(req, params)
    _assert_idle(eng)


def test_a_stale_state_fails_the_reference(model, monkeypatch):
    """The reset is what the comparison guards: with ``fresh`` never set,
    the second sequence on a slot starts on the first one's state."""
    cfg, params = model
    runs = ret.tick_runs
    monkeypatch.setattr(
        ret, "tick_runs", lambda s, p: (*runs(s, p)[:2],
                                        jnp.zeros_like(s, bool)))
    monkeypatch.setattr(gen, "_JIT_CACHE", {}, raising=False)
    eng = engine(brumby_cfg(seq_length=255), params, max_slots=1)
    a, b = prompts(40, 40, seed=3)
    one = eng.submit(a, 8, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    check(one, params)                 # a zero pool: nothing to forget yet
    two = eng.submit(b, 8, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    tokens, lps = two.result(timeout=120)
    want = reference_log_probs(params, tokens)[len(b) - 1:]
    assert np.abs(np.asarray(lps) - want).max() > 100 * ATOL


def test_preempted_and_resumed_matches_never_preempted(model):
    """``preempt()`` drops the state and re-queues; the resume prefills the
    tokens again from position 0, and counts them."""
    cfg, params = model
    obs_registry.set_publishing(True)
    eng = engine(cfg, params)
    p, = prompts(60, seed=4)
    req = eng.submit(p, 30, top_k=1, termination_id=NEVER)
    while len(req.generated) < 11:
        eng.step()
    assert eng.preempt(req) and req._phase == "queued"
    assert not held_pages(req)
    assert_memory(eng)
    done = len(req.generated)
    eng.run_until_idle()
    check(req, params)
    assert req._preemptions == 1 and eng.preemptions == 1
    # every token before the last was prefilled again
    assert eng.state_recomputed_tokens == len(p) + done - 1
    _assert_idle(eng)


def test_metrics_serve_the_state_series(model):
    cfg, params = model
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    eng = engine(cfg, params)
    before = {n: reg.counter(f"mlt_engine_state_{n}_total").value
              for n in ("rows", "touches", "resets")}
    a, b = prompts(40, 1, seed=6)
    for p in (a, b):
        eng.submit(p, 6, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    rows, touches, resets = (
        reg.counter(f"mlt_engine_state_{n}_total").value - before[n]
        for n in ("rows", "touches", "resets"))
    # 39 prompt rows in three runs (16 a tick) and 6 + 6 decode rows, one
    # lost to the tick that runs ahead of a stop: at least the live ones
    assert rows >= 39 + 12 and touches >= 3 + 12 and rows > touches
    assert resets == 2          # one run at position 0 a sequence
    text = reg.render() if hasattr(reg, "render") else ""
    for name in ("mlt_engine_state_rows_total",
                 "mlt_engine_state_touches_total",
                 "mlt_engine_state_resets_total",
                 "mlt_engine_state_recomputed_tokens_total",
                 "mlt_engine_state_pool_bytes"):
        assert name in text
    assert reg.gauge("mlt_engine_state_pool_bytes").value == \
        eng.pool.kv_pool_bytes() == 2 * 5 * 2 * (16 + 1) * 192 * 4


# ---- what a state does not carry yet --------------------------------------

def _mesh(**kw):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**{**dict(tensor_model_parallel_size=1,
                                pipeline_model_parallel_size=1,
                                data_parallel_size=1), **kw})


REFUSED = [
    (dict(kv_dtype="int8"), "--kv_dtype int8"),
    (dict(kv_dtype="fp8"), "--kv_dtype fp8"),
    (dict(draft=True), "--spec_k"),
    (dict(handoff=True), "cross-replica KV handoff"),
    (dict(log_probs=True), "return_log_probs"),
    (dict(tp=2), "tensor-parallel serving"),
    (dict(pp=2), "pipeline-parallel serving"),
    (dict(mixed=True), "mixes it with a page class"),
]


@pytest.mark.parametrize("kw,sentence", REFUSED,
                         ids=[s for _, s in REFUSED])
def test_refuse_unserved_says_why(model, kw, sentence):
    cfg, params = model
    kw = dict(kw)
    if kw.pop("mixed", False):
        cfg = brumby_cfg()
        cfg.model.sliding_window_layout = (1, 0)     # behind finalize's back
    if "tp" in kw:
        kw["mesh"] = _mesh(tensor_model_parallel_size=kw.pop("tp"))
    if "pp" in kw:
        kw["mesh"] = _mesh(pipeline_model_parallel_size=kw.pop("pp"))
    with pytest.raises(ValueError, match=sentence) as e:
        refuse_unserved(cfg, **kw)
    assert "constant-size recurrent state" in str(e.value)
    refuse_unserved(model[0])                   # one chip, bf16: served


def test_engine_refuses_at_start_up_and_at_the_request(model):
    cfg, params = model
    with pytest.raises(ValueError, match="--kv_dtype int8"):
        engine(cfg, params, kv_dtype="int8")
    eng = engine(cfg, params, prefix_cache=True)
    assert eng.cache is None          # off, not refused
    with pytest.raises(gen.InvalidRequest, match="return_log_probs"):
        eng.submit([1, 2, 3], 4, return_log_probs=True)
    with pytest.raises(ValueError, match="cross-replica KV handoff"):
        eng.prefill_and_export([1, 2, 3])


# ---- the trainer's path ---------------------------------------------------------

def test_gradient_is_finite_and_matches_finite_differences(model):
    """The dense forward differentiates (the chunked form by autodiff: no
    backward kernel), on one weight of the gate and one of the values."""
    cfg, params = model
    tokens = jnp.asarray(prompts(48, seed=7), jnp.int32)

    def loss(p):
        logits, _ = model_forward(cfg, p, tokens[:, :-1])
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(lp, tokens[:, 1:, None], -1).mean()

    grads = jax.grad(loss)(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    for path, at in ((("gate", "bias"), (0, 1)),
                     (("qkv", "kernel"), (1, 5, 180))):
        leaf = params["layers"]["attention"][path[0]][path[1]]
        h = 1e-2

        def moved(d):
            layers = jax.tree.map(lambda a: a, params["layers"])
            layers["attention"][path[0]][path[1]] = leaf.at[at].add(d)
            return loss({**params, "layers": layers})

        fd = float(moved(h) - moved(-h)) / (2 * h)
        got = float(grads["layers"]["attention"][path[0]][path[1]][at])
        assert abs(fd - got) <= 2e-2 * max(abs(fd), abs(got)) + 2e-5, (
            path, fd, got)
