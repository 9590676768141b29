"""Flash-attention kernel + chunked oracle vs naive attention."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.schedule import Schedule, concretize
from repro.core.workload import KernelInstance
from repro.kernels import flash_attention as fa
from repro.kernels import ref


def _data(b, hq, hkv, sq, skv, d, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(r.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(r.normal(size=(b, hkv, skv, d)), dtype)
    return q, k, v


def _cs(sq, skv, bq, bkv, cls="flash_attention_causal", **p):
    inst = KernelInstance.make(cls, Q=sq, KV=skv, dtype="float32", **p)
    return concretize(Schedule.make(cls, {"Q": bq, "KV": bkv}), inst, mode="adaptive")


@given(sq=st.sampled_from([8, 16, 32]), bq=st.sampled_from([4, 8, 16]),
       bkv=st.sampled_from([4, 8, 16]), causal=st.booleans(),
       window=st.sampled_from([0, 8]), softcap=st.sampled_from([0.0, 20.0]),
       group=st.sampled_from([1, 2]))
@settings(max_examples=24, deadline=None)
def test_kernel_matches_naive(sq, bq, bkv, causal, window, softcap, group):
    b, hkv, d = 2, 2, 16
    hq = hkv * group
    q, k, v = _data(b, hq, hkv, sq, sq, d)
    cs = _cs(sq, sq, bq, bkv)
    y = fa.flash_attention(q, k, v, cs, causal=causal, window=window, softcap=softcap,
                           interpret=True)
    yr = ref.attention(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)


@given(chunk=st.sampled_from([4, 8, 16, 32]), causal=st.booleans(),
       window=st.sampled_from([0, 8]))
@settings(max_examples=16, deadline=None)
def test_chunked_oracle_matches_naive(chunk, causal, window):
    """The XLA fallback path must be numerically identical to softmax attn."""
    q, k, v = _data(2, 4, 2, 24, 24, 16, seed=3)
    yr = ref.attention(q, k, v, causal=causal, window=window)
    yc = ref.chunked_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    np.testing.assert_allclose(yc, yr, rtol=2e-5, atol=2e-5)


def test_decode_q1_with_offset():
    q, k, v = _data(2, 4, 2, 1, 32, 16, seed=4)
    cs = _cs(1, 32, 1, 8)
    for off in (0, 7, 31):
        y = fa.flash_attention(q, k, v, cs, causal=True, q_offset=off, interpret=True)
        yr = ref.attention(q, k, v, causal=True, q_offset=off)
        np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)


def test_cross_attention_lengths_differ():
    q, k, v = _data(1, 4, 4, 8, 40, 16, seed=5)
    cs = _cs(8, 40, 4, 8, cls="flash_attention_cross")
    y = fa.flash_attention(q, k, v, cs, causal=False, interpret=True)
    yr = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)


def test_fully_masked_rows_are_finite():
    """Window smaller than block: rows with no visible kv must not NaN."""
    q, k, v = _data(1, 2, 2, 16, 16, 8, seed=6)
    cs = _cs(16, 16, 8, 8)
    y = fa.flash_attention(q, k, v, cs, causal=True, window=2, interpret=True)
    assert bool(jnp.isfinite(y).all())


def test_bf16_kernel():
    q, k, v = _data(1, 2, 2, 16, 16, 16, seed=7, dtype=jnp.bfloat16)
    cs = _cs(16, 16, 8, 8)
    y = fa.flash_attention(q, k, v, cs, causal=True, interpret=True)
    yr = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# Decode attention over a layer-indexed cache stack
# ---------------------------------------------------------------------------

from repro.kernels import decode_attention as da  # noqa: E402


@pytest.mark.parametrize(
    "group,size,pos,ring,softcap,layers,layer,block_kv,cache_dtype", [
        # full caches, four KV blocks, slots at 0, the middle and size - 1
        (3, 32, (0, 5, 31, 17), False, 0.0, 1, 0, 8, jnp.float32),
        # ring caches (size = window) before the wrap: unwritten slots masked
        (3, 16, (0, 3, 15, 9), True, 0.0, 1, 0, 8, jnp.float32),
        # ring caches after the wrap: every slot live
        (3, 16, (16, 40, 23, 100), True, 0.0, 1, 0, 8, jnp.float32),
        # the G-layer softcap
        (3, 32, (2, 30, 31, 11), False, 2.0, 1, 0, 16, jnp.float32),
        # 8 and 9 query rows per KV head (9: no multiple of 8, full extent)
        (8, 32, (0, 7, 31, 20), False, 0.0, 1, 0, 8, jnp.float32),
        (9, 24, (23, 0, 12, 1), False, 0.0, 1, 0, 24, jnp.float32),
        # a stack of three layers: the traced index must pick layer 1
        (3, 32, (4, 31, 0, 19), False, 0.0, 3, 1, 8, jnp.float32),
        (9, 16, (15, 16, 3, 40), True, 0.0, 4, 3, 16, jnp.float32),
        # bf16 q and cache: exact q.k products, p as a bf16 pair in p.v,
        # float32 out (one bf16 pass over p would miss by about 2e-3)
        (3, 32, (0, 9, 31, 26), False, 0.0, 2, 1, 8, jnp.bfloat16),
    ], ids=["full", "ring-before-wrap", "ring-after-wrap", "softcap",
            "group8", "group9", "stack-layer1", "ring-stack-layer3",
            "bf16-cache"])
def test_decode_attention_matches_masked_einsum(group, size, pos, ring, softcap,
                                                 layers, layer, block_kv,
                                                 cache_dtype):
    """The interpret-mode kernel, reading layer ``layer`` of a (L, B, KV, T,
    hd) stack in blocks of ``block_kv`` rows, equals the float32 einsum
    (``ref.masked_attention``) over that layer with the masks the
    decode step used: ``slot <= pos`` for a full cache, ``slot < min(pos +
    1, size)`` for a ring."""
    b, hkv, d = len(pos), 2, 16
    r = np.random.default_rng(size + group + layers)
    q = jnp.asarray(r.normal(size=(b, hkv * group, 1, d)) * 2, cache_dtype)
    k = jnp.asarray(r.normal(size=(layers, b, hkv, size, d)), cache_dtype)
    v = jnp.asarray(r.normal(size=(layers, b, hkv, size, d)), cache_dtype)
    p = jnp.asarray(pos, jnp.int32)
    slots = jnp.arange(size)[None, :]
    valid = (slots < jnp.minimum(p + 1, size)[:, None] if ring
             else slots <= p[:, None])
    want = ref.masked_attention(q.astype(jnp.float32),
                                k[layer].astype(jnp.float32),
                                v[layer].astype(jnp.float32), valid[:, None, :],
                                softcap)
    got = da.decode_attention(q.reshape(b, hkv, group, d), k, v,
                              jnp.minimum(p + 1, size), layer=jnp.int32(layer),
                              softcap=softcap, block_kv=block_kv, interpret=True)
    np.testing.assert_allclose(got.reshape(want.shape), want,
                               rtol=2e-5, atol=2e-5)


def _decode_mask():
    """A decode step's (B, 1, T) mask: slot b attends rows <= pos[b]."""
    pos = np.array([0, 7, 15])
    return (np.arange(16)[None, :] <= pos[:, None])[:, None, :], 3, 1


def _ring_chunk_mask():
    """A chunk at offset 11 over [ring of 8 ‖ chunk of 4], window 8: the
    (C, T) mask every lane shares (``models.attention.attn_chunk``)."""
    off, size, c, window = 11, 8, 4, 8
    slots = np.arange(size)
    ring_pos = off - 1 - np.mod(off - 1 - slots, size)
    kv_pos = np.concatenate([ring_pos, off + np.arange(c)])
    q_pos = off + np.arange(c)
    ok = ((kv_pos[None, :] >= 0) & (kv_pos[None, :] <= q_pos[:, None])
          & (kv_pos[None, :] > q_pos[:, None] - window))
    return ok, 2, c


def _verify_mask():
    """A verify step's per-lane (B, C, T) mask: lane b's C draft rows sit at
    off[b] + i and attend rows <= their position."""
    positions = np.array([2, 9])[:, None] + np.arange(3)
    return np.arange(16)[None, None, :] <= positions[:, :, None], 2, 3


@pytest.mark.parametrize("mask_fn,softcap", [
    (_decode_mask, 0.0), (_ring_chunk_mask, 20.0), (_verify_mask, 0.0),
], ids=["decode", "ring-chunk", "verify"])
def test_masked_attention_matches_naive_on_masked_rows(mask_fn, softcap):
    """``ref.masked_attention`` equals ``ref.attention``'s naive path run,
    row by row, over just the key rows that row's mask admits."""
    valid, b, c = mask_fn()
    t = valid.shape[-1]
    q, k, v = _data(b, 4, 2, c, t, 16, seed=t + c)
    got = np.asarray(ref.masked_attention(q, k, v, jnp.asarray(valid), softcap))
    full = np.broadcast_to(valid, (b, c, t))
    for bi in range(b):
        for i in range(c):
            rows = np.flatnonzero(full[bi, i])
            want = ref.attention(q[bi:bi + 1, :, i:i + 1], k[bi:bi + 1, :, rows],
                                 v[bi:bi + 1, :, rows], causal=False,
                                 softcap=softcap)
            np.testing.assert_allclose(got[bi:bi + 1, :, i:i + 1], want,
                                       rtol=1e-5, atol=1e-5)
