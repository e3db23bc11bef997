"""Flash attention kernel numerics vs the exact XLA attention
(reference analog: fused_kernels/tests/test_fused_kernels.py — fused kernels
vs unfused within tolerance). Runs in pallas interpret mode on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.ops.attention import make_attention_bias, xla_attention
from megatron_llm_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(key, b=1, s=256, n=4, nkv=2, d=128, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n, d), dtype)
    k = jax.random.normal(kk, (b, s, nkv, d), dtype)
    v = jax.random.normal(kv, (b, s, nkv, d), dtype)
    return q, k, v


def _ref(q, k, v, sliding_window=None, segment_ids=None, causal=True):
    bias = make_attention_bias(
        q.shape[1], k.shape[1], causal=causal, sliding_window=sliding_window,
        segment_ids_q=segment_ids, segment_ids_kv=segment_ids,
    )
    return xla_attention(q, k, v, bias=bias)


@pytest.mark.parametrize("nkv", [4, 2, 1])
def test_fwd_matches_reference(nkv):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), nkv=nkv)
    out = flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_fwd_sliding_window():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), s=256)
    out = flash_attention(q, k, v, sliding_window=64, block_q=64, block_kv=64,
                          interpret=True)
    ref = _ref(q, k, v, sliding_window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_fwd_segment_ids():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), s=128)
    seg = jnp.concatenate(
        [jnp.zeros((1, 64), jnp.int32), jnp.ones((1, 64), jnp.int32)], axis=1
    )
    out = flash_attention(q, k, v, segment_ids=seg, block_q=64, block_kv=64,
                          interpret=True)
    ref = _ref(q, k, v, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sliding_window", [None, 96])
def test_grads_match_reference(sliding_window):
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), s=256, n=4, nkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, sliding_window=sliding_window,
                            block_q=64, block_kv=64, interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, sliding_window=sliding_window) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_grads_segment_ids():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), s=128, n=2, nkv=2, d=128)
    seg = jnp.concatenate(
        [jnp.zeros((1, 48), jnp.int32), jnp.ones((1, 80), jnp.int32)], axis=1
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids=seg, block_q=64,
                                       block_kv=64, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, segment_ids=seg) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


def test_bf16_fwd_close():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
    ref = _ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


# ---------------------------------------------------------------------------
# bidirectional (causal=False) — the BERT / T5-encoder path
# ---------------------------------------------------------------------------


def _ref_bidir(q, k, v, segment_ids=None):
    return _ref(q, k, v, segment_ids=segment_ids, causal=False)


def test_fwd_bidirectional_matches_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5))
    out = flash_attention(q, k, v, causal=False, block_q=128, block_kv=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_bidir(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_fwd_bidirectional_segment_ids():
    """Non-causal + segment gating: the pipelined-BERT padding formulation."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), s=256)
    seg = (jnp.arange(256)[None, :] >= 200).astype(jnp.int32)  # pads seg 1
    out = flash_attention(q, k, v, causal=False, segment_ids=seg,
                          block_q=64, block_kv=64, interpret=True)
    ref = _ref_bidir(q, k, v, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_grads_bidirectional_match_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), s=128, d=64)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=False, block_q=64,
                                       block_kv=64, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_ref_bidir(q_, k_, v_) ** 2)

    g1 = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_grads_bidirectional_segment_ids():
    """Backward under the exact pipelined-BERT/T5-encoder training config:
    non-causal attention with pads expressed as segment ids."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), s=128, d=64)
    seg = (jnp.arange(128)[None, :] >= 100).astype(jnp.int32)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=False,
                                       segment_ids=seg, block_q=64,
                                       block_kv=64, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_ref_bidir(q_, k_, v_, segment_ids=seg) ** 2)

    g1 = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_env_block_override(monkeypatch):
    """MLT_FLASH_BLOCK_Q/KV (tools/mfu_sweep.py retune rows): applied when
    it divides the call's seq, is a 128-lane-tile multiple, and respects
    the VMEM cap (ADVICE r4 #2); ignored with a note otherwise; numerics
    unchanged either way."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    q, k, v = _rand_qkv(jax.random.PRNGKey(9), s=256, d=64)
    base = flash_attention(q, k, v, interpret=True)

    monkeypatch.setenv("MLT_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MLT_FLASH_BLOCK_KV", "128")
    assert fa._env_block("MLT_FLASH_BLOCK_Q", 256) == 128
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=2e-5, rtol=2e-5)

    monkeypatch.setenv("MLT_FLASH_BLOCK_Q", "100")  # does not divide 256
    assert fa._env_block("MLT_FLASH_BLOCK_Q", 256) is None
    # ADVICE r4 #2: a divisor that is NOT a 128-multiple (passes the old
    # check, dies as an opaque Mosaic/VMEM error later) is now rejected...
    monkeypatch.setenv("MLT_FLASH_BLOCK_Q", "64")
    assert fa._env_block("MLT_FLASH_BLOCK_Q", 256) is None
    # ...as is one above the VMEM cap the caller would auto-pick under
    monkeypatch.setenv("MLT_FLASH_BLOCK_Q", "1024")
    assert fa._env_block("MLT_FLASH_BLOCK_Q", 2048, cap=512) is None
    out2 = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(base),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the grid follows the static mask (live_blocks)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "seq,causal,window,live,total,cut",
    [
        (16384, True, None, 136, 256, 16),   # SmallThinker's full layer
        (16384, True, 4096, 70, 256, 28),    # its window layers
        (4096, True, 4096, 10, 16, 4),       # Mistral: the window cuts nothing
        (16384, False, None, 256, 256, 0),   # bidirectional: the rectangle
        (8192, True, 256, 15, 64, 15),       # a window far under a block
    ],
)
@pytest.mark.parametrize("outer,members", [("q", 1), ("kv", 7)])
def test_live_blocks_counts(seq, causal, window, live, total, cut, outer,
                            members):
    """The list IS the mechanism's engagement counter: live of all, cut of
    live, and the walk's order and brackets."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    blocks = fa.live_blocks(seq, seq, 1024, 1024, causal, window, outer,
                            members)
    assert (blocks.live, blocks.total, blocks.cut) == (live, total, cut)
    steps = blocks.steps
    assert len(steps) == live * members and fa.DEAD not in blocks.kinds
    assert int(((steps & fa.KIND) == fa.CUT).sum()) == cut * members
    # the order the kernels accumulate in: outer block, member, inner block
    walk = [(int(fa._outer(w)), int(fa._member(w)), int(fa._inner(w)))
            for w in steps]
    assert walk == sorted(walk) and len(set(walk)) == len(walk)
    # every outer block opens once and closes once, at its two ends
    outers = np.array([w[0] for w in walk])
    first = np.flatnonzero(steps & fa.FIRST)
    last = np.flatnonzero(steps & fa.LAST)
    assert len(first) == len(last) == seq // 1024
    np.testing.assert_array_equal(
        first, np.flatnonzero(np.r_[True, outers[1:] != outers[:-1]]))
    np.testing.assert_array_equal(
        last, np.flatnonzero(np.r_[outers[1:] != outers[:-1], True]))


def test_live_blocks_dead_outer_block():
    """A query block past the window's reach of the last key, and a key
    block past the last query under the causal mask: one DEAD step each
    (the output is written, nothing is computed)."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    blocks = fa.live_blocks(1024, 512, 128, 128, True, 320)
    dead = [int(fa._outer(w)) for w in blocks.steps if w & fa.KIND == fa.DEAD]
    assert dead == [7] and blocks.kinds == (fa.DEAD, fa.WHOLE, fa.CUT)
    blocks = fa.live_blocks(512, 1024, 128, 128, True, None, "kv", 4)
    dead = [int(fa._outer(w)) for w in blocks.steps if w & fa.KIND == fa.DEAD]
    assert dead == [4, 5, 6, 7]
    assert all(w & fa.FIRST and w & fa.LAST for w in blocks.steps
               if w & fa.KIND == fa.DEAD)


def _flash_heads(q, k, v, seg_q, seg_kv, causal, window, block=128):
    """The kernels as the ring calls them: [b, s, n, d] in, separate
    segment ids for the two sides, sq and skv free."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    seg = [None if s is None else s.astype(jnp.int32)[:, None, :]
           for s in (seg_q, seg_kv)]
    out = fa._flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), *seg, q.shape[-1] ** -0.5,
                    causal, window, block, block, True)
    return out.transpose(0, 2, 1, 3)


def _band_case(seed, sq, skv, n, nkv, segmented):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (1, sq, n, 64), jnp.float32)
    k = jax.random.normal(kk, (1, skv, nkv, 64), jnp.float32)
    v = jax.random.normal(kv, (1, skv, nkv, 64), jnp.float32)
    seg_q = seg_kv = None
    if segmented:
        # three documents whose ends are no block's end
        seg_q = jnp.searchsorted(jnp.array([200, 650]), jnp.arange(sq),
                                 side="right")[None]
        seg_kv = jnp.searchsorted(jnp.array([200, 650]), jnp.arange(skv),
                                  side="right")[None]
    return q, k, v, seg_q, seg_kv


@pytest.mark.parametrize(
    "sq,skv,n,nkv,causal,window,segmented",
    [
        # 8 x 8 blocks of 128, a window of 2.5 blocks: the band is narrower
        # than the rectangle and no edge of it is a block's edge
        (1024, 1024, 8, 2, True, 320, False),   # GQA, group 4
        (1024, 1024, 4, 1, True, 320, True),    # MQA, packed documents
        (1024, 1024, 8, 2, True, None, True),   # causal alone, packed
        (1024, 1024, 4, 1, False, 320, False),  # the window's one edge alone
        (1024, 1024, 8, 2, False, None, True),  # nothing static to kill
        # sq != skv, as the ring's chunk calls may be: keys past the last
        # query are dead key blocks (dkv writes zeros there)
        (512, 1024, 8, 2, True, 320, True),
        (1024, 512, 4, 1, False, None, False),
    ],
)
def test_band_matches_reference(sq, skv, n, nkv, causal, window, segmented):
    """Forward and all three gradients where the live blocks are a band:
    dropped blocks, whole blocks without a mask and cut blocks with one."""
    q, k, v, seg_q, seg_kv = _band_case(11, sq, skv, n, nkv, segmented)
    bias = make_attention_bias(sq, skv, causal=causal, sliding_window=window,
                               segment_ids_q=seg_q, segment_ids_kv=seg_kv)

    def flash(q_, k_, v_):
        out = _flash_heads(q_, k_, v_, seg_q, seg_kv, causal, window)
        return jnp.sum(out ** 2), out

    def ref(q_, k_, v_):
        out = xla_attention(q_, k_, v_, bias=bias)
        return jnp.sum(out ** 2), out

    (_, out_f), g_f = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(q, k, v)
    (_, out_r), g_r = jax.value_and_grad(ref, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    for a, b, name in zip(g_f, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


def _rectangle_walk(fa):
    """``live_blocks`` that visits EVERY block: the live ones as the real
    list has them, the dead ones computed and masked as a cut block is (the
    walk of a kernel that drops nothing)."""
    real = fa.live_blocks

    def walk(sq, skv, block_q, block_kv, causal, window, outer="q", members=1):
        live = real(sq, skv, block_q, block_kv, causal, window, outer, members)
        rect = real(sq, skv, block_q, block_kv, False, None, outer, members)
        where = ~np.int32(fa.KIND | fa.FIRST | fa.LAST)
        kind = {int(w & where): int(w & fa.KIND) for w in live.steps}
        steps = [(int(w) & ~fa.KIND) | kind.get(int(w & where), fa.CUT)
                 for w in rect.steps]
        assert len(steps) > len(live.steps)
        return live._replace(
            steps=np.asarray(steps, np.int64).astype(np.int32))

    return walk


@pytest.mark.parametrize("segmented", [False, True])
def test_live_list_equals_full_rectangle_to_the_bit(monkeypatch, segmented):
    """Dropping the dead blocks changes no bit of the output or of a
    gradient: a dead block, walked and masked, adds exact zeros, and the
    live ones are visited in the same order."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    q, k, v, seg_q, seg_kv = _band_case(12, 1024, 1024, 8, 2, segmented)

    def run():
        def loss(q_, k_, v_):
            out = _flash_heads(q_, k_, v_, seg_q, seg_kv, True, 320)
            return jnp.sum(out ** 2), out
        (_, out), grads = jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    listed = run()
    monkeypatch.setattr(fa, "live_blocks", _rectangle_walk(fa))
    for a, b, name in zip(listed, run(), ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_dead_query_block_writes_zeros(monkeypatch):
    """A query block no key reaches (sq > skv under a window) is one DEAD
    step: zeros out, the log-sum-exp of nothing, as the rectangle's walk
    gives them."""
    from megatron_llm_tpu.ops.pallas import flash_attention as fa

    q, k, v, _, _ = _band_case(13, 1024, 512, 4, 2, False)
    args = (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), None, None, 0.125, True, 320, 128, 128,
            True)
    out, lse = fa._fwd(*args)
    assert not np.asarray(out[:, :, 896:]).any()
    assert np.all(np.asarray(lse[:, :, 896:]) == fa.NEG_INF)
    monkeypatch.setattr(fa, "live_blocks", _rectangle_walk(fa))
    out_r, lse_r = fa._fwd(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_r))
