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
    assert kinds == {"share", "classes", "latent", "state", "hybrid", "tails",
                     "blocks"}
    # a state class beside a page class is SERVED (tests/test_gigachat35.py):
    # the row that refused it now says whose that is (the hybrid's, not
    # power retention's), and the hybrid has a row a feature
    assert "linear_layout" in NOT_CARRIED["state", "pattern"]
    assert {f for k, f in NOT_CARRIED if k == "hybrid"} == set(FEATURES)
    assert {f for k, f in NOT_CARRIED if k == "tails"} == set(FEATURES)
    assert {f for k, f in NOT_CARRIED if k == "blocks"} == set(FEATURES)
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
