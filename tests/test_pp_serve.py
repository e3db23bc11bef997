"""Pipeline-parallel serving tick + vocab-parallel head ring (ISSUE 20,
parallel/pp_serve.py + the vocab_ring slots of parallel/overlap.py).

The parity contract the acceptance criteria name:

* engine greedy decode at pp=2/4 emits the SAME tokens as the flat
  (no-mesh) engine — the ragged tick,
  prefix cache on/off, speculative decoding on/off — with per-token
  log-probs within 5e-6 (microbatched stage scan: same GEMMs, but XLA
  may tile the per-stage programs differently → tolerance on log-probs,
  identity on tokens);
* preempt/resume churn under pp lands on the uninterrupted run's bits;
* per-stage KV storage is 1/pp of the tp-only pool (kv_stage_bytes);
* the vocab-ring head GEMM is machine-asserted in HLO (ppermute chain
  + ``vocab-ring-tp{N}`` scope), numerically matches the plain
  all-gather head, and keeps engine greedy tokens identical;
* pp/vocab-ring geometry rides in ``_mesh_statics`` so pp engines never
  reuse tp-only executables (cached_jit is process-wide);
* inert flags degrade BITWISE: a pp=1 mesh builds no stage machinery,
  ``--vocab_ring`` at tp=1 resolves to None;
* observables: the ``engine-pp-tick`` span in a trace dump, the
  ``stage-permute`` scope in the compiled tick program, and the
  ``mlt_engine_pp_stages`` / ``mlt_engine_kv_stage_bytes`` gauges.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.core import parallel_state as ps
from megatron_llm_tpu.generation.engine import ContinuousBatchingEngine
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.parallel import overlap as ovl_mod
from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

VOCAB = 512  # divisible by tp^2 for tp in {1, 2, 4} (vocab-ring columns)


def _toy_cfg(num_layers=4, tp=1, vocab_ring=False):
    cfg = make_config(
        "llama2", num_layers=num_layers, hidden_size=64,
        num_attention_heads=4, num_attention_heads_kv=4,
        ffn_hidden_size=128, seq_length=64, max_position_embeddings=256,
        vocab_size=VOCAB, hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False,
    )
    cfg.parallel.tensor_model_parallel_size = tp
    cfg.parallel.data_parallel_size = 1
    cfg.parallel.vocab_ring = vocab_ring
    return cfg


@pytest.fixture(scope="module")
def toy_params():
    return init_model_params(_toy_cfg(), jax.random.PRNGKey(0))


def _run_engine(cfg, params, mesh, n_req=3, tokens=8, **kw):
    eng = ContinuousBatchingEngine(cfg, params, None, max_slots=4,
                                   num_pages=64, page_size=16,
                                   mesh=mesh, **kw)
    prompts = [[2 + (7 * i + j) % (VOCAB - 2) for j in range(13)]
               for i in range(n_req)]
    reqs = [eng.submit(p, tokens, temperature=1.0, top_k=0, top_p=0.0,
                       seed=11 + i) for i, p in enumerate(prompts)]
    eng.run_until_idle()
    return eng, [(r.result()[0], list(r.log_probs)) for r in reqs]


def _check(base, other, label, atol=5e-6):
    for (t0, l0), (t1, l1) in zip(base, other):
        assert t0 == t1, (label, t0, t1)
        np.testing.assert_allclose(l0, l1, atol=atol, err_msg=label)


def _pp_mesh(devs, pp, tp=1):
    return ps.build_mesh(tensor_model_parallel_size=tp,
                         pipeline_model_parallel_size=pp,
                         data_parallel_size=1, devices=devs[:pp * tp])


# ---------------------------------------------------------------------------
# tentpole: pp=2/4 greedy parity vs the flat engine, all tick modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pp", [2, 4])
def test_engine_pp_token_identity(eight_devices, pp):
    """Ragged tick at pp stages: same greedy tokens as the flat engine,
    log-probs within 5e-6, per-stage KV bytes exactly pool/pp."""
    cfg = _toy_cfg()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    _, base = _run_engine(cfg, params, None)  # flat arm FIRST (GSPMD)
    eng, out = _run_engine(copy.deepcopy(cfg), params,
                           _pp_mesh(eight_devices, pp))
    _check(base, out, f"pp={pp} ragged")
    assert eng._pp == pp and eng._ppc is not None
    assert eng.pool.pp == pp
    assert eng.pool.kv_stage_bytes() == eng.pool.kv_pool_bytes() // pp


def test_engine_pp_tick_modes(eight_devices, toy_params):
    """pp=2 parity holds with the prefix cache off."""
    cfg = _toy_cfg()
    params = toy_params
    _, b_nocache = _run_engine(cfg, params, None, prefix_cache=False)
    mesh = _pp_mesh(eight_devices, 2)
    _, p = _run_engine(copy.deepcopy(cfg), params, mesh,
                       prefix_cache=False)
    _check(b_nocache, p, "pp2 cache off")


def test_engine_pp_speculative(eight_devices, toy_params):
    """Speculative decoding under pp: the 2-layer draft splits over the
    same stages; greedy output matches the flat spec engine."""
    from megatron_llm_tpu.generation.speculative import resolve_draft

    cfg = _toy_cfg()
    draft = resolve_draft(
        "llama2:num_layers=2,hidden_size=32,num_attention_heads=4,"
        "num_attention_heads_kv=4,ffn_hidden_size=64", cfg)
    _, base = _run_engine(cfg, toy_params, None, spec_k=2, spec_draft=draft)
    _, out = _run_engine(copy.deepcopy(cfg), toy_params,
                         _pp_mesh(eight_devices, 2),
                         spec_k=2, spec_draft=draft)
    _check(base, out, "pp2 spec on")


def test_engine_pp_preempt_resume(eight_devices, toy_params):
    """Preempt a decoding request mid-stream on a pp=2 engine, let it
    resume: tokens identical to the uninterrupted FLAT run, log-probs
    within the pp tolerance (resume is bitwise w.r.t. the same engine;
    the cross-arm comparison carries the usual 5e-6)."""
    cfg = _toy_cfg()
    prompt = [2 + (j * 7) % (VOCAB - 2) for j in range(13)]
    flat = ContinuousBatchingEngine(cfg, toy_params, None, max_slots=4,
                                    num_pages=64, page_size=16)
    ref = flat.submit(prompt, 24, temperature=1.0, top_k=0, top_p=0.0,
                      seed=5)
    flat.run_until_idle()
    t_ref, lp_ref = ref.result()[0], list(ref.log_probs)

    eng = ContinuousBatchingEngine(copy.deepcopy(cfg), toy_params, None,
                                   max_slots=4, num_pages=64, page_size=16,
                                   mesh=_pp_mesh(eight_devices, 2))
    req = eng.submit(prompt, 24, temperature=1.0, top_k=0, top_p=0.0,
                     seed=5)
    while len(req.generated) < 9:
        eng.step()
    assert eng.preempt(req)
    assert req._phase == "queued" and not req._mem[0].pages
    eng.run_until_idle()
    assert req.result()[0] == t_ref
    np.testing.assert_allclose(list(req.log_probs), lp_ref, atol=5e-6)
    assert eng.preemptions == 1


# ---------------------------------------------------------------------------
# gating, inert flags, executable-cache geometry
# ---------------------------------------------------------------------------


def test_serve_params_gating(eight_devices):
    """serve_params builds exactly when the mesh has a pp extent; pp
    engines reject layouts the stage scan cannot serve."""
    cfg = _toy_cfg()
    assert pp_serve_mod.serve_params(cfg, None) is None
    mesh1 = ps.build_mesh(devices=eight_devices[:1])
    assert pp_serve_mod.serve_params(cfg, mesh1) is None
    mesh2 = _pp_mesh(eight_devices, 2)
    ppc = pp_serve_mod.serve_params(cfg, mesh2)
    assert ppc is not None and ppc.pp == 2
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    # num_layers must split evenly over the stages
    bad = _toy_cfg(num_layers=3)
    with pytest.raises(AssertionError):
        ContinuousBatchingEngine(bad, init_model_params(
            bad, jax.random.PRNGKey(0)), None, max_slots=4, num_pages=64,
            page_size=16, mesh=mesh2)


def test_inert_flags_degrade_bitwise(eight_devices, toy_params):
    """A pp=1 mesh (flag set, one stage) builds no stage machinery and is
    BITWISE the no-mesh engine; --vocab_ring at tp=1 likewise resolves
    to None."""
    cfg = _toy_cfg()
    _, base = _run_engine(cfg, toy_params, None)
    mesh1 = ps.build_mesh(devices=eight_devices[:1])
    c_pp = copy.deepcopy(cfg)
    c_pp.parallel.pipeline_model_parallel_size = 1
    eng, one = _run_engine(c_pp, toy_params, mesh1)
    assert eng._ppc is None and eng._pp == 1
    for (t0, l0), (t1, l1) in zip(base, one):
        assert t0 == t1
        assert l0 == l1  # bitwise: no stages, no ring, no collectives
    c_vr = _toy_cfg(vocab_ring=True)
    assert ovl_mod.overlap_params(c_vr, mesh1) is None
    eng, vr1 = _run_engine(c_vr, toy_params, mesh1)
    assert not eng._vocab_ring
    for (t0, l0), (t1, l1) in zip(base, vr1):
        assert t0 == t1
        assert l0 == l1


def test_mesh_statics_pin_pp_and_vocab_ring_geometry(eight_devices,
                                                    toy_params):
    """Regression: pp / vocab-ring geometry lands in _mesh_statics so a
    pp engine never reuses a tp-only executable, and the tuple tail stays
    ("tp_overlap", mode) for the PR 15 key contract."""
    cfg = _toy_cfg()
    e_flat = ContinuousBatchingEngine(cfg, toy_params, None, max_slots=4,
                                      num_pages=64, page_size=16)
    assert e_flat._mesh_statics == (
        "mesh", None, "vocab_ring", "off", "tp_overlap", "off")
    mesh_tp2 = ps.build_mesh(tensor_model_parallel_size=2,
                             data_parallel_size=1,
                             devices=eight_devices[:2])
    mesh_pp2 = _pp_mesh(eight_devices, 2)
    e_tp = ContinuousBatchingEngine(_toy_cfg(tp=2), toy_params, None,
                                    max_slots=4, num_pages=64,
                                    page_size=16, mesh=mesh_tp2)
    e_pp = ContinuousBatchingEngine(copy.deepcopy(cfg), toy_params, None,
                                    max_slots=4, num_pages=64,
                                    page_size=16, mesh=mesh_pp2)
    # build_mesh materializes every axis: the shape tuple alone separates
    # a (pp=2, tp=1) engine from a (pp=1, tp=2) engine on the same chips
    assert e_tp._mesh_statics != e_pp._mesh_statics
    assert e_pp._mesh_statics != e_flat._mesh_statics
    assert dict(e_pp._mesh_statics[1])["pp"] == 2
    assert e_pp._mesh_statics[-2:] == ("tp_overlap", "off")
    # vocab_ring flips its own component without disturbing the tail
    e_vr = ContinuousBatchingEngine(_toy_cfg(tp=2, vocab_ring=True),
                                    toy_params, None, max_slots=4,
                                    num_pages=64, page_size=16,
                                    mesh=mesh_tp2)
    assert e_vr._vocab_ring
    assert e_vr._mesh_statics[2:4] == ("vocab_ring", "ring")
    assert e_tp._mesh_statics[2:4] == ("vocab_ring", "off")
    assert e_vr._mesh_statics[-2:] == ("tp_overlap", "off")
    assert e_vr._mesh_statics != e_tp._mesh_statics


# ---------------------------------------------------------------------------
# vocab-parallel head ring
# ---------------------------------------------------------------------------


def test_vocab_ring_hlo_and_numeric_parity(eight_devices, toy_params):
    """Mechanism, not vibes: the ring head program carries the
    vocab-ring-tp2 scope and a ppermute chain (>= 2*tp-2 hops), and its
    logits match the plain all-gather head within 1e-5."""
    from megatron_llm_tpu.models.language_model import (
        compute_logits, head_weight,
    )
    from megatron_llm_tpu.parallel.tp import param_shardings

    mesh = ps.build_mesh(tensor_model_parallel_size=2,
                         data_parallel_size=1, devices=eight_devices[:2])
    cfg_off = _toy_cfg(tp=2)
    cfg_vr = _toy_cfg(tp=2, vocab_ring=True)
    with ps.global_mesh(mesh):
        ovl = ovl_mod.overlap_params(cfg_vr, mesh)
        assert ovl is not None and ovl.vocab_ring and not ovl.ring_rows
        sharded = jax.device_put(toy_params,
                                 param_shardings(mesh, toy_params))
        x = jnp.asarray(np.random.RandomState(0).randn(3, 1, 64),
                        jnp.float32)

        def head(p, h):
            with ovl_mod.activate(ovl):
                return compute_logits(cfg_vr, p, h)

        hlo = jax.jit(head).lower(sharded, x).compile().as_text()
        assert ovl_mod.vocab_scope_name(2) in hlo, "ring scope missing"
        assert hlo.count("collective-permute") >= 2  # 2*tp - 2 hops
        assert head_weight(cfg_vr, sharded) is not None
        plain = jax.jit(
            lambda p, h: compute_logits(cfg_off, p, h))(sharded, x)
        ring = jax.jit(head)(sharded, x)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(ring),
                                   atol=1e-5, rtol=1e-5)


def test_vocab_ring_engine_token_identity(eight_devices, toy_params):
    """--vocab_ring at tp=2: same greedy tokens as the plain tp engine
    (the head pays an all-gather-matmul ring every decode step; the
    tolerance-vs-bitwise story is the overlap.py chunked-GEMM one)."""
    mesh = ps.build_mesh(tensor_model_parallel_size=2,
                         data_parallel_size=1, devices=eight_devices[:2])
    _, off = _run_engine(_toy_cfg(tp=2), toy_params, mesh)
    eng, vr = _run_engine(_toy_cfg(tp=2, vocab_ring=True), toy_params,
                          mesh)
    assert eng._vocab_ring
    _check(off, vr, "vocab ring tp2")


def test_pp_tp_vocab_ring_compose(eight_devices, toy_params):
    """The full ISSUE 20 layout: pp=2 x tp=2 with the vocab ring on the
    head — greedy parity vs the flat single-chip engine."""
    cfg = _toy_cfg()
    _, base = _run_engine(cfg, toy_params, None)
    mesh = _pp_mesh(eight_devices, 2, tp=2)
    _, out = _run_engine(_toy_cfg(tp=2, vocab_ring=True), toy_params,
                         mesh)
    _check(base, out, "pp2 x tp2 + vocab ring")


# ---------------------------------------------------------------------------
# observables: span, scope, gauges
# ---------------------------------------------------------------------------


def test_pp_observables(eight_devices, toy_params):
    """engine-pp-tick span in a trace dump; stage-permute scope stamped
    on the compiled tick program; pp gauges report the stage geometry."""
    from megatron_llm_tpu.generation.engine import PagedState
    from megatron_llm_tpu.models.language_model import (
        make_rope_cache, model_forward,
    )
    from megatron_llm_tpu.observability import registry as obs_registry
    from megatron_llm_tpu.observability import trace as obs_trace

    cfg = _toy_cfg()
    tracer = obs_trace.configure()
    eng, _ = _run_engine(copy.deepcopy(cfg), toy_params,
                         _pp_mesh(eight_devices, 2))
    names = {e[1] for e in tracer.snapshot()}
    assert "engine-pp-tick" in names, sorted(names)
    obs_trace.disable()
    reg = obs_registry.get_registry()
    assert reg.gauge("mlt_engine_pp_stages").value == 2
    assert (reg.gauge("mlt_engine_kv_stage_bytes").value
            == eng.pool.kv_stage_bytes())

    # the stage-boundary ppermutes run under the stage-permute scope —
    # lower the engine's own tick forward and read the compiled program
    bt = np.zeros((eng.max_slots, eng.pages_per_seq), np.int32)
    pos = np.zeros((eng.max_slots,), np.int32)
    toks = np.full((eng.max_slots,), 2, np.int32)

    def tickish(params, pool_kv):
        rope = make_rope_cache(cfg)
        with pp_serve_mod.activate(eng._ppc):
            logits, _ = model_forward(
                cfg, params, jnp.asarray(toks)[:, None],
                position_ids=jnp.asarray(pos)[:, None], rope_cache=rope,
                kv_caches=pool_kv,
                paged=PagedState(jnp.asarray(bt), jnp.asarray(pos)))
        return logits

    hlo = jax.jit(tickish).lower(
        eng.params, eng.pool.kv).compile().as_text()
    assert pp_serve_mod.STAGE_PERMUTE_SCOPE in hlo, "stage scope missing"
    assert "collective-permute" in hlo
