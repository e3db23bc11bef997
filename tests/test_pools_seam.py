"""generation/pools.py: the one table of what a kind of per-sequence memory
does not carry (``NOT_CARRIED``, ``refuse_unserved``), and the module's
place under the scheduler.  The pools and the trie it holds are driven by
tests/test_pool_counters.py and tests/test_prefix_cache.py, the four
refusing kinds' engines by tests/test_commanda.py, tests/test_joyai.py,
tests/test_brumby.py and tests/test_gigachat35.py.
"""

import ast
import os
import subprocess
import sys

import jax
import pytest

from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation import pools
from megatron_llm_tpu.generation.pools import (
    FEATURES,
    KEEPS,
    NOT_CARRIED,
    memory_kind,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config

TINY = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
            num_attention_heads_kv=2, vocab_size=128, seq_length=128,
            max_position_embeddings=128, params_dtype="float32",
            use_flash_attn=False)
COMMANDA = dict(**{**TINY, "num_layers": 4, "kv_channels": 16},
                num_experts=4, moe_router_topk=2, moe_ffn_hidden_size=32,
                ffn_hidden_size=32, sliding_window_size=32)
# one tiny model a kind (the families' own suites hold their widths)
KINDS = {
    "paged": lambda: make_config("llama2", ffn_hidden_size=128, **TINY),
    "classes": lambda: make_config("commanda", **COMMANDA),
    "latent": lambda: make_config(
        "joyai", ffn_hidden_size=160, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, moe_router_topk=2, moe_ffn_hidden_size=40, **TINY),
    "indexed": lambda: make_config(
        "axk2", ffn_hidden_size=160, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        index_n_heads=4, index_head_dim=16, index_topk=16, gated_norm_rank=4,
        num_experts=4, moe_router_topk=2, moe_ffn_hidden_size=40,
        moe_n_group=2, moe_topk_group=1, **TINY),
    "state": lambda: make_config(
        "brumby", kv_channels=16, ffn_hidden_size=96, **TINY),
    "hybrid": lambda: make_config(
        "gigachat35", **{**TINY, "num_layers": 4}, dense_prefix_layers=1,
        ffn_hidden_size=128, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, num_experts=4,
        moe_router_topk=2, moe_ffn_hidden_size=32,
        rope_yarn_original_max_position=64),
    "blocks": lambda: make_config(
        "sdar_moe", **TINY, kv_channels=16, ffn_hidden_size=32, num_experts=4,
        moe_router_topk=2, moe_ffn_hidden_size=32, mask_token_id=127),
    "loop": lambda: make_config(
        "ouro", **TINY, kv_channels=16, ffn_hidden_size=96, loop_steps=4),
    "tails": lambda: make_config(
        "lfm2", **{k: v for k, v in TINY.items() if k != "num_layers"},
        sublayer_pattern="CD*ECE", kv_channels=16, ffn_hidden_size=96,
        num_experts=4, moe_router_topk=2, moe_ffn_hidden_size=32),
}


def _mesh(**axes):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**axes, data_parallel_size=1,
                      devices=jax.devices()[:2])


def _case(kind, feature):
    """(cfg, ``refuse_unserved``'s arguments, what the sentence must name)
    for one row of the table."""
    if kind == "share":
        cfg = make_config("commanda", **COMMANDA, moe_experts_held=2)
        return (cfg, dict(mesh=_mesh(tensor_model_parallel_size=2)),
                "moe_experts_held 2 of 4")
    cfg = KINDS[kind]()
    if feature == "pattern":
        # behind finalize's back: a stack the pool has no class for
        if kind == "state":
            cfg.model.sliding_window_layout = (1, 0)
        else:
            cfg.model.dense_prefix_layers = 1
        return cfg, {}, NOT_CARRIED[kind, feature].split(":")[0]
    asking, names = {
        "kv_dtype": (lambda: dict(kv_dtype="int8"), "--kv_dtype int8"),
        "tp": (lambda: dict(mesh=_mesh(tensor_model_parallel_size=2)),
               "tp 2"),
        "pp": (lambda: dict(mesh=_mesh(pipeline_model_parallel_size=2)),
               "pp 2"),
        "draft": (lambda: dict(draft=True), "--spec_k"),
        "handoff": (lambda: dict(handoff=True), "cross-replica KV handoff"),
        "log_probs": (lambda: dict(log_probs=True), "return_log_probs"),
    }[feature]
    return cfg, asking(), names


ROWS = sorted(NOT_CARRIED)


@pytest.mark.parametrize("kind, feature", ROWS,
                         ids=[f"{k}-{f}" for k, f in ROWS])
def test_every_row_refuses_in_a_sentence_that_names_its_flag(kind, feature):
    cfg, kw, names = _case(kind, feature)
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, **kw)
    said = str(e.value)
    assert names in said
    # the row's own reason, with what was asked filled in
    assert NOT_CARRIED[kind, feature].split("{")[0] in said
    if kind in KEEPS:
        assert KEEPS[kind].split("{")[0] in said
        assert said.endswith("Serve this model on one chip with --kv_dtype "
                             "bf16 and --spec_k 0.")


def test_the_table_has_no_row_without_a_case():
    """The test above is parametrised over the table itself, so a row added
    to the table is a case there (and fails there until ``_case`` knows what
    to ask for and which flag the sentence must name)."""
    mark, = test_every_row_refuses_in_a_sentence_that_names_its_flag.pytestmark
    assert set(mark.args[1]) == set(NOT_CARRIED)
    for row in NOT_CARRIED:
        _case(*row)
    kinds = {k for k, _ in NOT_CARRIED}
    assert kinds == {"share", "classes", "latent", "indexed", "state",
                     "hybrid", "tails", "blocks", "loop"}
    # latent rows under an indexer serve prompt scoring, as plain ones do
    assert {f for k, f in NOT_CARRIED if k == "indexed"} == (
        set(FEATURES) - {"log_probs"})
    # a state class beside a page class is SERVED (tests/test_gigachat35.py):
    # the row that refused it now says whose that is (the hybrid's, not
    # power retention's), and the hybrid has a row a feature
    assert "linear_layout" in NOT_CARRIED["state", "pattern"]
    assert {f for k, f in NOT_CARRIED if k == "hybrid"} == set(FEATURES)
    assert {f for k, f in NOT_CARRIED if k == "tails"} == set(FEATURES)
    assert {f for k, f in NOT_CARRIED if k == "blocks"} == set(FEATURES)
    # a looped stack serves prompt scoring
    assert {f for k, f in NOT_CARRIED if k == "loop"} == (
        set(FEATURES) - {"log_probs"})
    assert {f for _, f in NOT_CARRIED} <= set(FEATURES) | {"mesh", "pattern"}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_what_has_no_row_is_served(kind):
    cfg = KINDS[kind]()
    assert memory_kind(cfg) == kind
    refuse_unserved(cfg)                        # one chip, bf16: served
    asks = dict(kv_dtype=dict(kv_dtype="fp8"), draft=dict(draft=True),
                handoff=dict(handoff=True), log_probs=dict(log_probs=True))
    for feature, kw in asks.items():
        if (kind, feature) not in NOT_CARRIED:
            refuse_unserved(cfg, **kw)


def test_two_page_classes_refuse_log_probs_from_the_table_at_submit():
    cfg = KINDS["classes"]()
    eng = ContinuousBatchingEngine(
        cfg, init_model_params(cfg, jax.random.PRNGKey(0)), max_slots=2,
        page_size=8, max_seq=64, prefill_chunk=16)
    with pytest.raises(gen.InvalidRequest, match="two page classes") as e:
        eng.submit([1, 2, 3], 4, return_log_probs=True)
    assert NOT_CARRIED["classes", "log_probs"] in str(e.value)
    eng.submit([1, 2, 3], 4)                    # the request itself is fine


# ---- the seam ---------------------------------------------------------------

def test_pools_imports_nothing_of_the_scheduler():
    """The pools and the trie are data structures under the engine: a PR
    that touches the scheduler is not reviewed against them."""
    tree = ast.parse(open(pools.__file__).read())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules |= {f"{node.module}.{a.name}" for a in node.names}
    assert modules, "no import found: the walk is broken"
    assert not [m for m in modules if "generation.engine" in m
                or "generation.server" in m or "generation.scheduling" in m
                ], modules
    # and at run time: the file loads with none of the three loaded
    # (``import a.b.c`` runs ``a/b/__init__.py``, which imports the engine,
    # so a bare package of that name stands in for it)
    code = (
        "import sys, types\n"
        "import megatron_llm_tpu\n"
        "pkg = types.ModuleType('megatron_llm_tpu.generation')\n"
        "pkg.__path__ = [megatron_llm_tpu.__path__[0] + '/generation']\n"
        "sys.modules['megatron_llm_tpu.generation'] = pkg\n"
        "import megatron_llm_tpu.generation.pools as p\n"
        "bad = [m for m in sys.modules if m.startswith(\n"
        "    'megatron_llm_tpu.generation.') and m.split('.')[2] in\n"
        "    ('engine', 'server', 'scheduling')]\n"
        "assert not bad and p.PagedKVPool, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_the_engine_re_exports_the_pools_own_classes():
    from megatron_llm_tpu import generation
    from megatron_llm_tpu.generation import engine

    for name in ("PagedKVPool", "PrefixCache", "StatePool"):
        assert getattr(engine, name) is getattr(pools, name)
        assert getattr(generation, name) is getattr(pools, name)
    defined = {n.name for n in ast.parse(open(engine.__file__).read()).body
               if isinstance(n, (ast.ClassDef, ast.FunctionDef))}
    assert not defined & {"PagedKVPool", "StatePool", "PrefixCache",
                          "refuse_unserved"}
    # ONE refusal function in the package
    assert [n for n in dir(pools) if n.startswith("refuse_")] == [
        "refuse_unserved"]
    assert not [n for n in defined if n.startswith("refuse_")]


# ---- one per-sequence memory a class (PR 61) --------------------------------

def test_the_scheduler_names_no_kind_of_memory():
    """The engine and the block driver hold ``req._mem`` and
    ``engine._classes`` and loop over them: no list, table or ledger of one
    kind of memory has a name there, and the pools' own names (``wpool``,
    ``spool``) stay where they are built, in a compiled program's key and
    in the state sweep's counters."""
    from megatron_llm_tpu.generation import blocks, engine

    gone = {"_pages", "_max_pages", "_wpages", "_wfirst", "_wkeep",
            "_wprivate", "_wmax", "_wtables", "_stables", "_block_tables",
            "_committed", "_wcommitted", "_state_only",
            "_grant_window_locked"}
    may_name_pools = {"__init__", "_class_statics", "_note_state_rows"}
    for mod in (engine, blocks):
        tree = ast.parse(open(mod.__file__).read())
        names = {n.attr for n in ast.walk(tree)
                 if isinstance(n, ast.Attribute)} | {
            n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
            n.name for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
        assert {"_mem", "_classes"} <= names, "the walk is broken"
        assert not names & gone, (mod.__name__, names & gone)
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef) \
                    or fn.name in may_name_pools:
                continue
            named = {n.attr for n in ast.walk(fn)
                     if isinstance(n, ast.Attribute)} & {"wpool", "spool"}
            assert not named, (mod.__name__, fn.name, named)
    fields = {f.name for f in
              __import__("dataclasses").fields(engine.EngineRequest)}
    assert "_mem" in fields and not fields & (gone | {"_state"})


def test_a_window_class_and_a_state_class_together_on_the_host():
    """What no engine serves yet, at the pools' level: ONE sequence holds a
    windowed page class AND a state class, through admission, grants past
    the window, slides, a preemption's release and a re-admission, every
    step under the invariants the engines' suites hold (tests/parity.py);
    and a grant that one class refuses is refused whole."""
    import types

    from megatron_llm_tpu.generation.pools import (
        ClassMemory,
        PagedKVPool,
        StatePool,
    )
    from tests.parity import assert_memory, assert_memory_idle

    page, window, width, cap = 8, 16, 8, 5
    win = ClassMemory(
        PagedKVPool(KINDS["paged"](), 12, page, layers=1,
                    page_class="window"),
        2, width, window=window, cap=cap)
    st = ClassMemory(StatePool(KINDS["state"](), 1, page, layers=1,
                               page_class="state"), 2, 1)
    classes = [win, st]
    eng = types.SimpleNamespace(_classes=classes, _queue=[], max_slots=2,
                                _slots=[None, None])

    def admit(keep=0, cow=0, fill=4, total=width):
        want = (keep, cow, fill, total)
        if not all(cls.can_admit(*want) for cls in classes):
            return None
        return types.SimpleNamespace(_mem=[
            cls.admit([], *want) for cls in classes])

    for _ in range(2):          # admitted, preempted, admitted again
        req = admit()
        eng._slots[0] = req
        wmem, smem = req._mem
        # the window takes nothing at admission, the state its one slot
        assert (wmem.pages, wmem.max, len(smem.pages)) == ([], cap, 1)
        assert (win.committed, st.committed) == (cap, 0)
        assert_memory(eng)
        # a second sequence: the window class could, the state class has
        # no slot left, so neither grants
        assert win.can_admit(0, 0, 4, width)
        assert admit() is None and win.committed == cap
        # the prompt's rows, a tick's worth at a time, its rows not yet in
        # the table
        for last in (1, 2):
            assert [cls.grant(m, last) for cls, m in zip(classes, req._mem)
                    ] == [2 if last == 1 else 1, 0]
            assert_memory(eng)
        assert not win.table.any() and not st.table.any()
        for cls, m in zip(classes, req._mem):
            cls.install(0, m)
        assert_memory(eng)
        released = 0
        for pos in range(3 * page, width * page):
            released += sum(cls.slide(m, pos)
                            for cls, m in zip(classes, req._mem))
            granted = [cls.grant(m, pos // page)
                       for cls, m in zip(classes, req._mem)]
            assert granted == [int(pos % page == 0), 0]
            assert_memory(eng)
            assert win.held(wmem) <= -(-window // page) + 1 <= cap
            assert st.held(smem) == 1
            # the row mirrors the record: nulls behind the window
            assert not win.table[0, :wmem.first].any()
        assert released == wmem.first == (width * page - window) // page
        assert win.grant(wmem, 10 ** 6) == 0       # never past the table
        held = [cls.held(m) for cls, m in zip(classes, req._mem)]
        assert held == [width - released, 1]
        eng._slots[0] = None
        for cls, m in zip(classes, req._mem):
            cls.clear(0)
        assert [cls.release(m)
                for cls, m in zip(classes, req._mem)] == held
        assert not any(m.pages for m in req._mem)
        assert_memory(eng)
        assert_memory_idle(eng)


def test_a_state_slot_is_granted_whole_whatever_the_watermark():
    """``--page_watermark`` is slack for pages a sequence in flight still
    takes.  A state class takes ONE slot, once, at admission (an explicit
    demand of ``(1, 1)``, whatever the prompt fills), so its ledger stays
    at zero and it keeps no slack: every slot can be admitted to, alone or
    beside a page class, which does keep its watermark."""
    from megatron_llm_tpu.generation.pools import (
        ClassMemory,
        PagedKVPool,
        StatePool,
    )

    slots, page, width = 3, 8, 4
    st = ClassMemory(StatePool(KINDS["state"](), slots, page, layers=1,
                               page_class="state"),
                     slots, width, watermark=2)
    assert (st.state, st.width, st.cap, st.watermark) == (True, 1, 1, 0)
    assert st.table.shape == (slots, 1)
    mems = []
    for fill in (1, 4, 0):      # what the prompt fills is a page class's
        assert st.demand(0, 0, fill, width) == (1, 1)
        assert st.can_admit(0, 0, fill, width)
        mems.append(st.admit([], 0, 0, fill, width))
        assert (len(mems[-1].pages), mems[-1].private, mems[-1].max,
                st.committed) == (1, 1, 1, 0)
    assert len({m.pages[0] for m in mems}) == slots
    assert not st.can_admit(0, 0, 1, width)         # every slot is taken
    assert st.grant(mems[0], 10 ** 6) == 0          # and never a second
    assert [st.release(m) for m in mems] == [1] * slots
    assert st.can_admit(0, 0, 1, width) and st.committed == 0

    # a page class under the same watermark refuses the sequence that
    # would leave fewer than ``watermark`` pages beyond the ledger
    pg = ClassMemory(PagedKVPool(KINDS["paged"](), 1 + 2 * width + 1, page,
                                 layers=1, page_class="full"),
                     slots, width, watermark=2)
    assert (pg.state, pg.width, pg.watermark) == (False, width, 2)
    a = pg.admit([], 0, 0, 2, width)
    assert pg.committed == width - 2
    assert pg.can_admit(0, 0, 2, width - 1)          # 7 - 2 >= 2 + 1 + 2
    assert not pg.can_admit(0, 0, 2, width)          # 7 - 2 <  2 + 2 + 2
    pg.release(a)
    assert pg.can_admit(0, 0, 2, width) and pg.committed == 0
