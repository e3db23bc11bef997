"""The percentile rule, the seeded schedules and the FLOP/byte arithmetic
against figures worked out by hand."""

import json
import os

import pytest

from benchmark.lib import flops, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_percentile_rule_by_hand():
    xs = [10, 20, 30, 40, 50]            # ranks 0..4
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 90) == pytest.approx(46.0)   # rank 3.6
    assert stats.percentile(xs, 95) == pytest.approx(48.0)   # rank 3.8
    assert stats.percentile([7], 90) == 7
    assert stats.percentile([], 90) is None
    assert stats.percentile([1, 2], 75) == pytest.approx(1.75)
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


CHAT = {   # an open-loop mix of the selftest's own: no cell runs one yet
    "kind": "open_loop", "rate_per_s": 1.0, "ramp_s": 10, "draw_seed": 7,
    "prompt_len": {"dist": "lognormal", "median": 384, "sigma": 0.9,
                   "min": 32, "max": 3072},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 8, "max": 512},
    "shared_prefix": {"share": 0.3, "count": 4, "tokens": 256}}


def _plan(mix, seed, seconds=40, vocab=32000):
    if isinstance(mix, str):
        mix = traffic.load(mix)
    return traffic.request_plan(mix, seed, seconds, vocab)["requests"]


def _sizes(reqs):
    return [(len(r["prompt"]), r["n_out"]) for r in reqs]


@pytest.mark.parametrize("mix", [CHAT, "batch_closed"])
def test_same_seed_same_schedule_other_seed_other_schedule(mix):
    a, b, c = _plan(mix, 2 ** 31 + 5), _plan(mix, 2 ** 31 + 5), _plan(mix, 6)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert _sizes(a) != _sizes(c)
    # the same work in another order: block by block the same sizes
    n = traffic.SHUFFLE_BLOCK
    assert len(a) == len(c)
    for i in range(0, len(a), n):
        assert sorted(_sizes(a[i:i + n])) == sorted(_sizes(c[i:i + n]))
    if a[0]["due_s"] is not None:
        da, dc = [r["due_s"] for r in a], [r["due_s"] for r in c]
        assert da != dc and da == sorted(da) and dc == sorted(dc)
        # the gaps of a block are the same set, so block ends coincide
        assert da[n - 1::n] == pytest.approx(dc[n - 1::n])


def test_chat_mix_shape():
    reqs = _plan(CHAT, 3, seconds=2000)
    assert abs(len(reqs) / 2010.0 - 1.0) < 0.1        # Poisson at the rate
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 2010
    body = sorted(len(r["prompt"]) - (256 if r["prefix"] is not None else 0)
                  for r in reqs)
    assert 330 < body[len(body) // 2] < 440            # median 384
    assert body[0] >= 32 and max(len(r["prompt"]) for r in reqs) <= 3072
    outs = sorted(r["n_out"] for r in reqs)
    assert 8 <= outs[0] and outs[-1] <= 512 and 110 < outs[len(outs) // 2] < 150
    share = sum(r["prefix"] is not None for r in reqs) / len(reqs)
    assert 0.25 < share < 0.35
    firsts = {tuple(r["prompt"][:256]) for r in reqs if r["prefix"] is not None}
    assert len(firsts) == CHAT["shared_prefix"]["count"]
    assert all(0 not in r["prompt"] for r in reqs)     # 0 is end-of-document


def test_batch_mix_shape():
    reqs = _plan("batch_closed", 3)
    assert len(reqs) == 4096 and all(r["due_s"] is None for r in reqs)
    lens = sorted(len(r["prompt"]) for r in reqs)
    assert lens[0] >= 32 and lens[-1] <= 1024 and 235 < lens[2048] < 280
    outs = [r["n_out"] for r in reqs]
    assert min(outs) >= 128 and max(outs) <= 384 and 245 < sum(outs) / 4096 < 267
    assert all(r["prefix"] is None and 0 not in r["prompt"] for r in reqs)


def test_train_batches_seeded():
    mix = traffic.load("pack4k")
    a = next(traffic.train_batches(mix, 2 ** 31 + 9, 2, 32000))
    b = next(traffic.train_batches(mix, 2 ** 31 + 9, 2, 32000))
    c = next(traffic.train_batches(mix, 1, 2, 32000))
    assert (a["tokens"] == b["tokens"]).all() and (a["tokens"] != c["tokens"]).any()
    assert a["tokens"].shape == (2, 4096) and a["tokens"].min() >= 1
    assert (a["labels"][:, :-1] == a["tokens"][:, 1:]).all()


def _model(name):
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))
    return {**cfg, **cfg["derived"]}


def test_mistral_arithmetic_by_hand():
    m = _model("mistral-7b.train")
    # qkv 4096 x 6144, out 4096 x 4096, SwiGLU 3 x 4096 x 14336
    assert flops.layer_matmul_params(m) == 25165824 + 16777216 + 176160768
    assert flops.matmul_params(m) == 2 * 218103808 + 4096 * 32000
    assert flops.total_params(m) == 698351616          # PERF.md: 0.698 B
    assert flops.causal_keys(4096, 4096) == 4096 * 4097 // 2
    assert flops.causal_keys(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    # per token: 3 x (2 x 567.28 M + 2 layers x 4 x 32 x 128 x 2048.5)
    per_token = 3 * (2 * 567279616 + 2 * 16384 * 2048.5)
    assert flops.train_flops_per_token(m, 4096) == pytest.approx(per_token)
    assert per_token * 4096 == pytest.approx(14.766e12, rel=1e-3)   # a step
    assert flops.kv_bytes_per_token(dict(m, num_hidden_layers=16)) == 65536
    cost = flops.flash_train_cost(m, 4096, 1, layers=1)
    assert cost["flops"] == pytest.approx(3 * 4 * 32 * 128 * 4096 * 4097 / 2)
    # q and o 32 MiB each, k and v 8 MiB each: fwd 80 MiB, bwd 160 MiB
    assert cost["bytes"] == 240 * 2 ** 20


def test_falcon_arithmetic_by_hand():
    f = _model("falcon-7b")
    # qkv 4544 x (71 + 2) x 64, out 4544 x 4544, MLP 2 x 4544 x 18176
    assert flops.layer_matmul_params(f) == 21229568 + 20647936 + 165183488
    assert flops.total_params(f) == 24 * 207060992 + 4544 * 65024   # tied
    assert flops.kv_bytes_per_token(f) == 2 * 1 * 64 * 2 * 24 == 6144
    assert flops.visible_keys(5000, None) == 5000
    assert flops.visible_keys(5000, 4096) == 4096


def test_roofline_bound():
    from benchmark.lib import peaks

    p = peaks.peaks_for("TPU v5 lite")
    t, bound = flops.roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = flops.roofline_seconds(1.0, 819e9, p)
    assert bound == "bandwidth" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
