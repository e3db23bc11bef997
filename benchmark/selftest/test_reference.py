"""The plain float32 references against the program's own forward at a
tiny size on the CPU, float32 weights: the two must agree to float32
rounding, which shows that the references read the program's parameter
layout (group-major QKV, [h, 2, ffn] SwiGLU, interleaved RoPE) rightly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common, falcon_block, llama_block


def _program(name, **over):
    from megatron_llm_tpu.models import init_model_params, make_config, model_forward

    cfg = make_config(name, params_dtype="float32", use_flash_attn=False,
                      vocab_size=512, seq_length=64, **over)
    params = init_model_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 48), 1, 512)
    logits, _ = model_forward(cfg, params, tokens)
    return cfg, params, tokens, logits


def test_llama_block_matches_program():
    cfg, params, tokens, logits = _program(
        "mistral", num_layers=3, hidden_size=128, num_attention_heads=8,
        num_attention_heads_kv=2, ffn_hidden_size=256, sliding_window_size=16)
    model = {"num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
             "rms_norm_eps": cfg.model.layernorm_epsilon, "rope_theta": 10000.0,
             "sliding_window": 16, "tie_word_embeddings": False}
    ref = llama_block.logits(params, tokens, model)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits),
                               rtol=2e-4, atol=2e-5)
    lp = common.token_log_probs(ref, tokens)
    assert lp.shape == (2, 47) and bool(jnp.isfinite(lp).all())
    # the window matters: a reference without it must disagree
    wide = llama_block.logits(params, tokens, dict(model, sliding_window=None))
    assert float(jnp.abs(wide - logits).max()) > 1e-3


def test_falcon_block_matches_program():
    cfg, params, tokens, logits = _program(
        "falcon", num_layers=2, hidden_size=128, num_attention_heads=4,
        num_attention_heads_kv=1, ffn_hidden_size=512)
    model = {"num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 32,
             "layer_norm_epsilon": cfg.model.layernorm_epsilon,
             "rope_theta": 10000.0}
    ref = falcon_block.logits(params, tokens, model)
    # the program's GELU is the tanh approximation, the published one exact:
    # they differ by at most 5e-4 an activation, far inside the tolerance
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("workload", ["mistral7b_train_4k", "falcon7b_batch_decode"])
def test_compare_rule(workload):
    """Each configuration's own limits: a mean just inside passes, a mean
    just outside fails, and so does one token beyond the largest allowed."""
    from benchmark.lib import cells, check

    cell = cells.Cell(workload)
    tol = cell.config["tolerance"]
    a = np.linspace(-10, -9, 100)
    assert check.compare(cell, a, a + 0.9 * tol["mean_abs_nats"])["reference_ok"]
    assert not check.compare(cell, a, a + 1.1 * tol["mean_abs_nats"])["reference_ok"]
    b = a.copy()
    b[3] += 1.1 * tol["max_abs_nats"]
    assert not check.compare(cell, a, b)["reference_ok"]        # one token far off
    assert not check.compare(cell, a, a[:-1])["reference_ok"]
    b = a.copy()
    b[0] = np.nan
    assert not check.compare(cell, a, b)["reference_ok"]


def test_compare_takes_the_statistics_a_configuration_limits():
    """SmallThinker limits the mean and the median and not the largest
    difference (no upper reading: PERF.md section 4): the median alone
    fails a run, one far-off token alone does not, and it is still read."""
    from benchmark.lib import cells, check

    cell = cells.Cell("smallthinker_train_16k")
    tol = cell.config["tolerance"]
    assert "max_abs_nats" not in tol and tol["median_abs_nats"] < tol["mean_abs_nats"]
    a = np.linspace(-11, -10, 100)
    shift = np.full(100, 1.1 * tol["median_abs_nats"])
    shift[:20] = 0.0                      # mean 0.88 x its limit's median
    assert shift.mean() < tol["mean_abs_nats"]
    out = check.compare(cell, a, a + shift)
    assert not out["reference_ok"]
    assert out["reference_median_abs_diff"] == pytest.approx(1.1 * tol["median_abs_nats"])
    b = a.copy()
    b[3] += 0.9
    out = check.compare(cell, a, b)
    assert out["reference_ok"] and out["reference_max_abs_diff"] == pytest.approx(0.9)
    assert not check.compare(cell, a, a + 1.1 * tol["mean_abs_nats"])["reference_ok"]


def test_serving_probes_reach_the_prefix_cache():
    from benchmark.lib import check

    probes = {p["name"]: p for p in check.serve_probes(2 ** 31 + 5, 65024, (192, 256), 16)}
    first = probes["first"]["prompt"]
    assert len(probes["alone"]["prompt"]) == 192 and len(first) == 256
    # the pages `first` leaves cached: every one its last token does not touch
    assert probes["whole_hit"]["prompt"] == first[:240]
    part = probes["part_hit"]["prompt"]
    assert len(part) == 256 and part[:232] == first[:232] and part[232:] != first[232:]
    assert [p["after"] for p in probes.values()] == [None, None, "first", "first"]
    assert probes == {p["name"]: p for p in check.serve_probes(
        2 ** 31 + 5, 65024, (192, 256), 16)}
