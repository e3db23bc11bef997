"""The layout the serving engine keeps its word-embedding table in
(``generation/placement.py``): the rule reads the ARRAY, an engine built on
a table that does not lie in rows ends with it in rows (one copy, inside a
``table-rows`` start-up phase that says its bytes), an engine built on one
that does ends with the very object it was given, and the tokens and
log-probs of a run are the same bits either way.

XLA:CPU's default layout IS rows-major, and its client holds an array in
any dimension order it is asked for, so "a table that does not lie in rows"
is made here by putting one column-major.  What a v5e's default is for
Falcon's ``bf16[65024, 4544]`` is asked of a described chip in
``tests/test_aot_scale.py`` (``-k table``)."""

from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from megatron_llm_tpu.core import parallel_state as ps
from megatron_llm_tpu.generation import ContinuousBatchingEngine, placement
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.speculative import DraftModel
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.observability import compiles
from megatron_llm_tpu.parallel.tp import param_shardings

VOCAB, HIDDEN = 128, 64
ROWS, COLS = (0, 1), (1, 0)


def _cfg(family="llama2", layers=2, kv=4):
    return make_config(
        family, num_layers=layers, hidden_size=HIDDEN, num_attention_heads=4,
        num_attention_heads_kv=kv, ffn_hidden_size=128, vocab_size=VOCAB,
        seq_length=64, max_position_embeddings=128, params_dtype="float32",
        use_flash_attn=False)


def _table(params):
    return params["embedding"]["word_embeddings"]


def _in_cols(params):
    """The same tree with its table column-major: what a TPU's default
    layout makes of Falcon's."""
    t = _table(params)
    col = jax.device_put(t, Format(Layout(major_to_minor=COLS), t.sharding))
    assert col.format.layout.major_to_minor == COLS
    return {**params, "embedding": {**params["embedding"],
                                    "word_embeddings": col}}


def _order(leaf):
    return tuple(leaf.format.layout.major_to_minor)


def _last_phase():
    return [p for p in compiles.phases() if p[0] == "table-rows"][-1]


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


def test_rule_reads_the_array_and_its_device():
    t = jax.device_put(jnp.arange(32.0).reshape(8, 4), jax.devices()[0])
    assert placement.rows_format(t) is None and placement.in_rows(t) is t
    col = jax.device_put(t, Format(Layout(major_to_minor=COLS), t.sharding))
    fmt = placement.rows_format(col)
    assert fmt.layout.major_to_minor == ROWS and fmt.sharding == t.sharding
    placed = placement.in_rows(col)
    assert _order(placed) == ROWS and placed is not col
    np.testing.assert_array_equal(np.asarray(placed), np.asarray(t))
    # in rows now: the rule leaves it be
    assert placement.in_rows(placed) is placed
    # no device to ask: a host array is left alone
    assert placement.rows_format(np.zeros((8, 4))) is None


def test_rule_answers_for_an_abstract_leaf():
    """No array to look at: the layout a ``ShapeDtypeStruct`` carries, else
    the default of its shape and dtype on its sharding's device."""
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    plain = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16, sharding=sh)
    assert placement.device_layout(plain).major_to_minor == ROWS
    assert placement.in_rows(plain) is plain
    pinned = jax.ShapeDtypeStruct(
        (8, 4), jnp.bfloat16,
        sharding=Format(Layout(major_to_minor=COLS), sh))
    placed = placement.in_rows(pinned)
    assert placed.format.layout.major_to_minor == ROWS
    assert (placed.shape, placed.dtype, placed.sharding) == (
        pinned.shape, pinned.dtype, sh)
    # a device whose default is column-major, as a v5e's is for Falcon's
    # table: the answer comes from the client, not from the shape
    cols = Layout(major_to_minor=COLS)
    with mock.patch.object(Layout, "from_pjrt_layout",
                           staticmethod(lambda _: cols)):
        assert placement.device_layout(plain) == cols
        assert placement.in_rows(plain).format.layout.major_to_minor == ROWS
    # no sharding, nothing to ask
    assert placement.rows_format(
        jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)) is None


def test_abstract_trees_take_the_engines_path():
    """``tools/tick_hlo_copies.py`` and ``tools/tick_digest.py`` hand their
    abstract parameters to the call the engine makes."""
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    tree = {"embedding": {"word_embeddings": jax.ShapeDtypeStruct(
        (VOCAB, HIDDEN), jnp.bfloat16, sharding=sh)}, "layers": {}}
    same, none = placement.tables_in_rows(tree, None)
    assert same is tree and none is None and _last_phase()[3] == {"bytes": 0}
    pinned = {**tree, "embedding": {"word_embeddings": jax.ShapeDtypeStruct(
        (VOCAB, HIDDEN), jnp.bfloat16,
        sharding=Format(Layout(major_to_minor=COLS), sh))}}
    placed, = placement.tables_in_rows(pinned)
    assert _table(placed).format.layout.major_to_minor == ROWS
    assert placed["layers"] is pinned["layers"]
    assert _last_phase()[3] == {"bytes": VOCAB * HIDDEN * 2}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _build(case, eight_devices, lay):
    """``(engine, the trees it was built on)`` of one case, every table
    laid out by ``lay`` first."""
    kw = dict(max_slots=2, max_seq=64)
    if case == "tied":
        cfg = _cfg("falcon", kv=1)
        assert cfg.model.tie_embed_logits
    else:
        cfg = _cfg()
        assert not cfg.model.tie_embed_logits
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    if case == "tp2":
        mesh = ps.build_mesh(tensor_model_parallel_size=2,
                             data_parallel_size=1, devices=eight_devices[:2])
        # born under their shardings, as the server CLI's are
        params = lay(jax.device_put(params, param_shardings(mesh, params)))
        with ps.global_mesh(mesh):
            return ContinuousBatchingEngine(cfg, params, None, mesh=mesh,
                                            **kw), (params,)
    if case == "draft":
        dcfg = _cfg(layers=1)
        draft = lay(init_model_params(dcfg, jax.random.PRNGKey(1)))
        params = lay(params)
        return ContinuousBatchingEngine(
            cfg, params, None, spec_k=2, spec_draft=DraftModel(dcfg, draft),
            **kw), (params, draft)
    params = lay(params)
    return ContinuousBatchingEngine(cfg, params, None, **kw), (params,)


@pytest.mark.parametrize("case", ["tied", "untied", "draft", "tp2"])
def test_engine_holds_its_tables_in_rows(case, eight_devices):
    # tables that lie in rows: the engine keeps the very objects
    eng, given = _build(case, eight_devices, lambda p: p)
    held = (eng.params, eng.draft_params)[:len(given)]
    for mine, theirs in zip(held, given):
        assert _table(mine) is _table(theirs)
        if case != "tp2":       # a mesh's engine places a tree of its own
            assert mine is theirs
    assert _last_phase()[3] == {"bytes": 0}
    # tables that do not: one copy each, same bytes, same sharding, and
    # nothing else of the tree touched
    eng, given = _build(case, eight_devices, _in_cols)
    held = (eng.params, eng.draft_params)[:len(given)]
    for mine, theirs in zip(held, given):
        assert _order(_table(theirs)) == COLS
        assert _order(_table(mine)) == ROWS
        assert _table(mine).sharding == _table(theirs).sharding
        np.testing.assert_array_equal(np.asarray(_table(mine)),
                                      np.asarray(_table(theirs)))
        if case != "tp2":
            assert mine["layers"] is theirs["layers"]
    assert _last_phase()[3] == {
        "bytes": sum(_table(t).nbytes for t in given)}
    if case == "tp2":
        assert _table(eng.params).sharding.spec == jax.sharding.PartitionSpec(
            ps.TP_AXIS, None)
    # and the engine serves from them
    req = eng.submit([5, 6, 7, 8], 4, top_k=1, use_eod_for_termination=False)
    eng.run_until_idle()
    assert len(req.result(timeout=120)[0]) == 8


def test_a_shared_table_is_copied_once():
    """A draft that IS its target (the tests' self-draft) holds one table."""
    cfg = _cfg()
    params = _in_cols(init_model_params(cfg, jax.random.PRNGKey(0)))
    a, b = placement.tables_in_rows(params, params)
    assert _table(a) is _table(b) and _order(_table(a)) == ROWS
    assert _last_phase()[3] == {"bytes": _table(params).nbytes}


def _run(eng):
    reqs = [eng.submit(p, 6, top_k=1, return_log_probs=True,
                       use_eod_for_termination=False)
            for p in ([5, 6, 7, 8, 9, 10], [11, 12, 13])]
    eng.run_until_idle()
    return [(r.generated, r.log_probs, r.prompt_log_probs) for r in reqs]


@pytest.mark.parametrize("family,kv", [("falcon", 1), ("llama2", 4)],
                         ids=["tied", "untied"])
def test_same_bits_before_and_after_the_placement(family, kv):
    """Greedy tokens, their log-probs and the prompts' from an engine that
    re-laid its table, from one whose table lay in rows all along, and from
    one that was left with the column-major table (the placement patched
    out): the same bits.  The three share ONE process-wide jitted tick
    (``gen.cached_jit``), called on two layouts of the leaf in turn: it
    compiles for each and fails on neither."""
    cfg = _cfg(family, kv=kv)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_slots=2, max_seq=64)
    rows = _run(ContinuousBatchingEngine(cfg, params, None, **kw))
    placed = ContinuousBatchingEngine(cfg, _in_cols(params), None, **kw)
    assert _order(_table(placed.params)) == ROWS
    with mock.patch.object(placement, "tables_in_rows", lambda *t: t):
        left = ContinuousBatchingEngine(cfg, _in_cols(params), None, **kw)
    assert _order(_table(left.params)) == COLS
    after, before = _run(placed), _run(left)
    assert after == rows
    assert after == before
    assert len({tuple(g) for g, _, _ in after}) == 2


def test_dense_path_runs_on_the_relaid_leaf():
    """``_legacy()`` hands the engine's tree to the dense single-stream
    path: its programs compile for the layout the leaf came in."""
    cfg = _cfg("falcon", kv=1)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_slots=2, max_seq=64)
    eng = ContinuousBatchingEngine(cfg, _in_cols(params), None, **kw)
    ref = ContinuousBatchingEngine(cfg, params, None, **kw)
    assert eng._legacy().params is eng.params
    tokens = np.asarray([[5, 6, 7, 8, 9, 10, 11, 12]], np.int32)
    got = gen.score_tokens(cfg, eng._legacy().params, tokens)
    want = gen.score_tokens(cfg, ref._legacy().params, tokens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_relaid_table_compiles_each_tick_program_once():
    """``jax.device_put`` with a ``Format`` COMMITS the table, a program's
    outputs are committed where one input is, and ``jax.jit`` keys its
    executables on which arguments are committed: the pool and what the
    engine uploads are therefore born committed beside such parameters
    (``placement.committed_to``), and every tick program is lowered and
    compiled once, not once a mixture (the first form of PR 64 compiled
    the Falcon cell's three programs three times each)."""
    import logging

    cfg = make_config(
        "falcon", num_layers=1, hidden_size=HIDDEN, num_attention_heads=4,
        num_attention_heads_kv=1, vocab_size=136, seq_length=64,
        max_position_embeddings=128, params_dtype="float32",
        use_flash_attn=False)      # a width of its own: nothing compiled yet
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    plain = ContinuousBatchingEngine(cfg, params, None, max_slots=2,
                                     max_seq=64)
    assert plain._repl is None and placement.committed_to(params) is None
    eng = ContinuousBatchingEngine(cfg, _in_cols(params), None, max_slots=2,
                                   max_seq=64)
    home = _table(eng.params).sharding
    assert _table(eng.params).committed and eng._repl == home
    assert eng.pool.kv.committed and eng._asarray(np.zeros(2)).committed

    compiled = []

    class Count(logging.Handler):
        def emit(self, record):
            if "Compiling jit(tick)" in record.getMessage():
                compiled.append(record.getMessage())

    logger, handler = logging.getLogger("jax._src.interpreters.pxla"), Count()
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            for _ in range(3):      # ticks with and without prompt rows
                for prompt in ([5, 6, 7, 8, 9, 10], [11, 12, 13]):
                    eng.submit(prompt, 6, top_k=1,
                               use_eod_for_termination=False)
                eng.run_until_idle()
    finally:
        logger.removeHandler(handler)
    assert len(eng._ragged_fns) == 2
    assert len(compiled) == len(eng._ragged_fns), len(compiled)
