"""Cross-replica KV page handoff (serving/handoff/, ISSUE 19).

Four layers, mirroring the subsystem: the wire format round-trips every
storage dtype byte-for-byte (scale rows and draft leaves included), the
engine export→import→re-export path is bit-identical with migrated
prefixes indistinguishable from locally cached ones (token/log-prob
parity + trie-hit proof), the replica kv_push endpoint's role/overload/
malformed-blob contract, and an end-to-end prefill+decode+unified fleet
behind the disagg router asserting routed responses are token-identical
to a unified replica with one trace id visible on every tier.
"""

import json
import time
import urllib.error
import urllib.request

import jax
import ml_dtypes
import numpy as np
import pytest

from megatron_llm_tpu.generation import EngineOverloaded
from megatron_llm_tpu.generation.engine import ContinuousBatchingEngine
from megatron_llm_tpu.generation.server import MegatronServer
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.serving.handoff import wire
from megatron_llm_tpu.serving.handoff.transfer import (
    KVPushError,
    push_pages,
)
from megatron_llm_tpu.serving.router.server import RouterServer

from tests.parity import assert_same_generations
from tests.test_generation import VOCAB, ToyTokenizer

GREEDY = dict(top_k=1, use_eod_for_termination=False)
PS = 16  # the engines below keep the default page size


@pytest.fixture(scope="module")
def models():
    from megatron_llm_tpu.generation import DraftModel

    kw = dict(hidden_size=64, num_attention_heads=4,
              num_attention_heads_kv=2, ffn_hidden_size=128,
              vocab_size=VOCAB, seq_length=256,
              max_position_embeddings=256, hidden_dropout=0.0,
              attention_dropout=0.0, params_dtype="float32",
              use_flash_attn=False)
    cfg = make_config("llama2", num_layers=2, **kw)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    dcfg = make_config("llama2", num_layers=1, **kw)
    dparams = init_model_params(dcfg, jax.random.PRNGKey(1))
    return {"cfg": cfg, "params": params,
            "draft": DraftModel(dcfg, dparams)}


def _engine(models, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 256)
    return ContinuousBatchingEngine(models["cfg"], models["params"],
                                    ToyTokenizer(), **kw)


def _ids(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(2, VOCAB, n)]


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------


def _synthetic_leaves(kv_dtype, n_pages):
    rng = np.random.default_rng(7)
    shape = (2, n_pages, PS, 2, 16)
    if kv_dtype == "bf16":
        return {"k": rng.normal(size=shape).astype(ml_dtypes.bfloat16),
                "v": rng.normal(size=shape).astype(ml_dtypes.bfloat16)}
    q_dtype = (np.int8 if kv_dtype == "int8"
               else ml_dtypes.float8_e4m3fn)
    out = {}
    for name in ("k", "v"):
        out[f"{name}.q"] = rng.integers(
            -100, 100, shape).astype(q_dtype)
        out[f"{name}.scale"] = rng.uniform(
            1e-3, 1.0, (2, n_pages, 2)).astype(np.float32)
    return out


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_wire_roundtrip_byte_identity(kv_dtype):
    """encode→decode reproduces every leaf byte-for-byte — values,
    per-page scale rows, extended dtypes — and the header metadata the
    receiving trie keys on."""
    tokens = _ids(3 * PS)
    leaves = _synthetic_leaves(kv_dtype, 3)
    blob = wire.encode_pages(tokens, PS, kv_dtype, leaves)
    payload = wire.decode_pages(blob)
    assert payload.tokens == tokens
    assert payload.page_size == PS and payload.n_pages == 3
    assert payload.kv_dtype == kv_dtype
    assert set(payload.leaves) == set(leaves)
    for name, arr in leaves.items():
        got = payload.leaves[name]
        assert got.dtype == np.asarray(arr).dtype and got.shape == arr.shape
        assert got.tobytes() == np.ascontiguousarray(arr).tobytes(), name
    # and a re-encode of the decoded payload is the identical blob
    assert wire.encode_pages(payload.tokens, PS, kv_dtype,
                             payload.leaves) == blob


def test_wire_rejects_malformed():
    tokens = _ids(2 * PS)
    leaves = _synthetic_leaves("bf16", 2)
    blob = wire.encode_pages(tokens, PS, "bf16", leaves)
    with pytest.raises(ValueError, match="magic"):
        wire.decode_pages(b"XXXXXXXX" + blob[8:])
    with pytest.raises(ValueError, match="truncated"):
        wire.decode_pages(blob[:-10])
    with pytest.raises(ValueError, match="trailing"):
        wire.decode_pages(blob + b"\0")
    # sender-side invariants: page alignment and leaf page counts
    with pytest.raises(ValueError, match="page-aligned"):
        wire.encode_pages(tokens[:-1], PS, "bf16", leaves)
    with pytest.raises(ValueError, match="pages on axis 1"):
        wire.encode_pages(tokens, PS, "bf16",
                          {"k": leaves["k"][:, :1]})


# ---------------------------------------------------------------------------
# Engine export → import → re-export
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_export_import_reexport_bit_identical(models, kv_dtype):
    """The full migration path never re-quantizes: the receiver's
    re-export of an imported prefix is the sender's blob byte-for-byte,
    and decoding from the migrated pages is token- and log-prob-
    identical to prefilling locally, with the trie hit proving the
    migrated pages (not a recompute) served the prompt."""
    ids = _ids(5 * PS + 1)
    sender = _engine(models, kv_dtype=kv_dtype)
    blob, info = sender.prefill_and_export(ids, trace_id="exp")
    assert info["pages"] == 5 and info["tokens"] == 5 * PS
    assert info["bytes"] == len(blob)
    names = set(wire.decode_pages(blob).leaves)
    if kv_dtype == "bf16":
        assert names == {"k", "v"}
    else:
        assert names == {"k.q", "k.scale", "v.q", "v.scale"}

    receiver = _engine(models, kv_dtype=kv_dtype)
    receipt = receiver.import_kv(blob, trace_id="imp")
    assert receipt == {"pages": 5, "installed": 5, "deduped": 0,
                       "tokens": 5 * PS}
    blob2, n = receiver.export_cached_kv(ids[:5 * PS])
    assert n == 5 and blob2 == blob

    # migrated pages serve decode exactly like local prefill
    req = receiver.submit(ids, 12, trace_id="mig", **GREEDY)
    receiver.run_until_idle()
    got = req.result(timeout=120)
    fresh = _engine(models, kv_dtype=kv_dtype)
    ref = fresh.submit(ids, 12, **GREEDY)
    fresh.run_until_idle()
    assert got == ref.result(timeout=120)
    rec = receiver.flight.lookup("mig")[0]
    assert rec["hit_tokens"] == 5 * PS


def test_import_dedup_is_idempotent(models):
    """Re-pushing a blob costs nothing: trie incumbents win every
    position, the receipt says so, and the pool's free count is
    unchanged (release-after-insert leaves pages cached-idle)."""
    ids = _ids(4 * PS + 1, seed=3)
    sender = _engine(models)
    blob, _ = sender.prefill_and_export(ids)
    receiver = _engine(models)
    first = receiver.import_kv(blob)
    assert first["installed"] == 4 and first["deduped"] == 0
    free_after = len(receiver.pool._free)
    again = receiver.import_kv(blob)
    assert again == {"pages": 4, "installed": 0, "deduped": 4,
                     "tokens": 4 * PS}
    assert len(receiver.pool._free) == free_after


def test_import_rejects_incompatible_blobs(models):
    ids = _ids(3 * PS + 1, seed=4)
    sender = _engine(models)
    blob, _ = sender.prefill_and_export(ids)
    with pytest.raises(ValueError, match="page_size"):
        _engine(models, page_size=32).import_kv(blob)
    with pytest.raises(ValueError, match="kv_dtype"):
        _engine(models, kv_dtype="int8").import_kv(blob)
    with pytest.raises(ValueError, match="prefix cache"):
        _engine(models, prefix_cache=False).import_kv(blob)
    with pytest.raises(ValueError):
        sender.import_kv(b"not a handoff blob at all")


def test_import_overload_is_structured(models):
    """A pool that cannot hold the pushed pages answers EngineOverloaded
    with a drain hint — the sender degrades to unified serving instead
    of half-installing."""
    ids = _ids(5 * PS + 1, seed=5)
    blob, _ = _engine(models).prefill_and_export(ids)
    tiny = _engine(models, max_slots=1, num_pages=4)
    free_before = len(tiny.pool._free)
    with pytest.raises(EngineOverloaded) as ei:
        tiny.import_kv(blob)
    assert ei.value.retry_after > 0
    assert len(tiny.pool._free) == free_before  # nothing leaked


def test_spec_draft_leaves_ride_the_wire(models):
    """A speculating sender ships its draft-model KV alongside the
    target's; a speculating receiver re-exports it bit-identically; a
    non-speculating receiver refuses the blob (leaf mismatch) instead
    of silently dropping the draft pages."""
    ids = _ids(4 * PS + 1, seed=6)
    sender = _engine(models, spec_k=2, spec_draft=models["draft"])
    blob, info = sender.prefill_and_export(ids)
    assert info["pages"] == 4
    assert set(wire.decode_pages(blob).leaves) == {
        "k", "v", "draft_k", "draft_v"}
    receiver = _engine(models, spec_k=2, spec_draft=models["draft"])
    assert receiver.import_kv(blob)["installed"] == 4
    blob2, n = receiver.export_cached_kv(ids[:4 * PS])
    assert n == 4 and blob2 == blob
    with pytest.raises(ValueError, match="leaves"):
        _engine(models).import_kv(blob)


def test_preempted_request_migrates_token_identical(models):
    """The preempt→migrate→resume-elsewhere path: a preempted request's
    cached pages (prompt AND generated-so-far) export via
    export_cached_kv, install on a second engine, and the re-submitted
    request finishes with the tokens (and, to a few fp32 ulps —
    tests/parity.py — the log-probs) of the sender's own resume, with the
    trie hit proving the migrated pages carried the resume."""
    ids = _ids(3 * PS, seed=8)
    sender = _engine(models, max_slots=1)
    victim = sender.submit(ids, 24, trace_id="victim", **GREEDY)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        sender.step()
        if victim._phase == "decode" and len(victim.generated) >= 8:
            break
    assert sender.preempt(victim)
    seq = ids + [int(t) for t in victim.generated]
    blob, n_pages = sender.export_cached_kv(seq)
    assert n_pages >= 3  # at least the full prompt pages migrated

    receiver = _engine(models)
    assert receiver.import_kv(blob)["pages"] == n_pages
    moved = receiver.submit(ids, 24, trace_id="moved", **GREEDY)
    receiver.run_until_idle()
    got = moved.result(timeout=120)

    sender.run_until_idle()  # the sender's own resume is the reference
    assert_same_generations([got], [victim.result(timeout=120)])
    assert receiver.flight.lookup("moved")[0]["hit_tokens"] > 0


def test_handoff_phase_decomposition_sums(models):
    """A prefill_only request's flight record lands in the ``handoff``
    phase bucket, carries the kv_export event, and its decomposition
    still partitions the measured latency exactly."""
    eng = _engine(models)
    eng.prefill_and_export(_ids(3 * PS + 1, seed=9), trace_id="hand")
    rec = eng.flight.lookup("hand")[0]
    assert rec["outcome"] == "handoff"
    assert rec["decomposition"]["handoff_s"] >= 0.0
    assert abs(sum(rec["decomposition"].values())
               - rec["latency_s"]) < 1e-5
    kinds = [e["kind"] for e in rec["events"]]
    assert "kv_export" in kinds


# ---------------------------------------------------------------------------
# Replica endpoint: POST /admin/kv_push + /health role
# ---------------------------------------------------------------------------


def _server(models, role, **ekw):
    srv = MegatronServer(_engine(models, **ekw), role=role)
    port = srv.start_background(port=0)
    return srv, f"http://127.0.0.1:{port}"


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_kv_push_endpoint_contract(models):
    """Decode-role install → trie-hit serving; prefill-role refusal;
    malformed-blob 400; the advertised role in /health."""
    ids = _ids(5 * PS + 1, seed=10)
    blob, _ = _engine(models).prefill_and_export(ids)
    dec, dec_url = _server(models, "decode")
    pre, pre_url = _server(models, "prefill")
    try:
        assert _get_json(dec_url + "/health")["role"] == "decode"
        assert _get_json(pre_url + "/health")["role"] == "prefill"

        receipt = push_pages(dec_url, blob, trace_id="push-1")
        assert receipt["pages"] == 5 and receipt["installed"] == 5
        assert receipt["replica_id"] == dec.replica_id

        # a prefill-role replica is a KV sender, never a sink
        with pytest.raises(KVPushError) as ei:
            push_pages(pre_url, blob)
        assert ei.value.status == 400
        # bytes that are not a handoff blob are a 400, not a 500
        with pytest.raises(KVPushError) as ei:
            push_pages(dec_url, b"garbage bytes")
        assert ei.value.status == 400
    finally:
        dec.stop()
        pre.stop()
    with pytest.raises(ValueError, match="role"):
        MegatronServer(_engine(models), role="bogus")


def test_kv_push_overload_503_with_retry_after(models):
    ids = _ids(5 * PS + 1, seed=11)
    blob, _ = _engine(models).prefill_and_export(ids)
    srv, url = _server(models, "decode", max_slots=1, num_pages=4)
    try:
        with pytest.raises(KVPushError) as ei:
            push_pages(url, blob)
        assert ei.value.status == 503
        assert ei.value.retry_after is not None
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# End to end: prefill + decode + router vs a unified replica
# ---------------------------------------------------------------------------


def _put(url, payload, trace=None, timeout=600):
    hdrs = {"Content-Type": "application/json"}
    if trace:
        hdrs["X-MLT-Trace-Id"] = trace
    req = urllib.request.Request(
        url + "/api", data=json.dumps(payload).encode(),
        method="PUT", headers=hdrs)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), json.loads(r.read())


def test_disagg_fleet_end_to_end(models):
    """A real 3-replica fleet over HTTP: long prompts route prefill →
    kv_push → decode through the disagg router and come back token- and
    log-prob-identical to a unified replica, under ONE trace id visible
    in all three tiers' flight recorders; the streamed variant matches
    too; short prompts skip the hop entirely."""
    pre, pre_url = _server(models, "prefill")
    dec, dec_url = _server(models, "decode")
    uni, uni_url = _server(models, "unified")
    router = RouterServer([pre_url, dec_url], policy="disagg",
                          policy_kwargs={"long_prompt_chars": 64},
                          poll_interval=0.25, forward_timeout_s=600.0)
    rurl = f"http://127.0.0.1:{router.start_background()}"
    long_prompt = "".join(chr(97 + (i * 7) % 26) for i in range(120))
    body = {"prompts": [long_prompt], "tokens_to_generate": 8,
            "top_k": 1, "random_seed": 1234}
    try:
        _, _, ref = _put(uni_url, body)

        st, hdrs, out = _put(rurl, body, trace="trace-e2e-1")
        assert st == 200 and hdrs.get("X-MLT-Trace-Id") == "trace-e2e-1"
        assert out["text"] == ref["text"]
        assert out["segments"] == ref["segments"]
        assert router._handoffs.value == 1
        assert router._handoff_failures.value == 0

        # the decode replica served the prompt from migrated pages
        assert _get_json(dec_url + "/health")["prefix_hit_tokens"] > 0
        # one trace id, three tiers
        q = "/debug/requests?trace_id=trace-e2e-1"
        fleet = _get_json(rurl + q)["fleet"]
        assert sum(v.get("count", 0) for v in fleet.values()) > 0
        assert _get_json(pre_url + q)["count"] > 0
        assert _get_json(dec_url + q)["count"] > 0

        # streamed through the same path: identical terminal body
        import http.client
        from urllib.parse import urlparse

        p = urlparse(rurl)
        conn = http.client.HTTPConnection(p.hostname, p.port, timeout=600)
        conn.request("PUT", "/api",
                     json.dumps({**body, "stream": True}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        raw = resp.read().decode()
        conn.close()
        done = json.loads([ln for ln in raw.splitlines()
                           if ln.startswith("data:")][-1][5:])
        assert done["text"] == ref["text"]
        assert router._handoffs.value == 2

        # a short prompt never pays for the hop
        _put(rurl, {"prompts": ["hi"], "tokens_to_generate": 4,
                    "top_k": 1})
        assert router._handoffs.value == 2
    finally:
        router.stop()
        for s in (pre, dec, uni):
            s.stop()
