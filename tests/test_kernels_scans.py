"""Recurrent-scan kernels (rwkv6 wkv, RG-LRU) vs lax.scan oracles."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.schedule import Schedule, concretize
from repro.core.workload import KernelInstance
from repro.kernels import ref
from repro.kernels import rglru_scan as rg
from repro.kernels import rwkv6_scan as rw


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


@given(t=st.sampled_from([4, 8, 16]), ct=st.sampled_from([2, 4, 8]),
       h=st.sampled_from([1, 3]), d=st.sampled_from([4, 8]))
@settings(max_examples=16, deadline=None)
def test_rwkv6_kernel_matches_oracle(t, ct, h, d):
    b = 2
    r_ = np.random.default_rng(t * 37 + ct)
    mk = lambda: jnp.asarray(r_.normal(size=(b, h, t, d)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(_sigmoid(r_.normal(size=(b, h, t, d))) * 0.9 + 0.05, jnp.float32)
    u = jnp.asarray(r_.normal(size=(h, d)), jnp.float32)
    s0 = jnp.asarray(r_.normal(size=(b, h, d, d)), jnp.float32)
    inst = KernelInstance.make("rwkv6_scan", T=t, C=h * d, D=d, B=b, dtype="float32")
    cs = concretize(Schedule.make("rwkv6_scan", {"T": ct, "C": h * d}, order=("C", "T")),
                    inst, mode="adaptive")
    y, sT = rw.rwkv6_scan(r, k, v, w, u, s0, cs, interpret=True)
    yr, sTr = ref.rwkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sT, sTr, rtol=1e-5, atol=1e-5)


@given(t=st.sampled_from([4, 8, 16]), ct=st.sampled_from([2, 4, 8]),
       c=st.sampled_from([8, 12]), bc=st.sampled_from([4, 8]))
@settings(max_examples=16, deadline=None)
def test_rglru_kernel_matches_oracle(t, ct, c, bc):
    b = 2
    r_ = np.random.default_rng(t * 11 + c)
    x = jnp.asarray(r_.normal(size=(b, t, c)), jnp.float32)
    a = jnp.asarray(_sigmoid(r_.normal(size=(b, t, c))), jnp.float32)
    h0 = jnp.asarray(r_.normal(size=(b, c)), jnp.float32)
    inst = KernelInstance.make("rglru_scan", T=t, C=c, B=b, dtype="float32")
    cs = concretize(Schedule.make("rglru_scan", {"T": ct, "C": bc}, order=("C", "T")),
                    inst, mode="adaptive")
    y, hT = rg.rglru_scan(x, a, h0, cs, interpret=True)
    yr, hTr = ref.rglru_scan(x, a, h0)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hTr, rtol=1e-5, atol=1e-5)


def test_chunking_invariance():
    """Different T tiles must give bit-identical recurrences (state carry)."""
    b, h, t, d = 1, 2, 16, 4
    r_ = np.random.default_rng(0)
    mk = lambda: jnp.asarray(r_.normal(size=(b, h, t, d)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(_sigmoid(r_.normal(size=(b, h, t, d))), jnp.float32)
    u = jnp.asarray(r_.normal(size=(h, d)), jnp.float32)
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    outs = []
    for ct in (2, 4, 16):
        inst = KernelInstance.make("rwkv6_scan", T=t, C=h * d, D=d, B=b, dtype="float32")
        cs = concretize(Schedule.make("rwkv6_scan", {"T": ct, "C": h * d},
                                      order=("C", "T")), inst)
        y, _ = rw.rwkv6_scan(r, k, v, w, u, s0, cs, interpret=True)
        outs.append(np.asarray(y))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_state_continuation():
    """Scanning [0:t1] then [t1:t] must equal one scan (serving contract)."""
    b, t, c = 2, 12, 8
    r_ = np.random.default_rng(1)
    x = jnp.asarray(r_.normal(size=(b, t, c)), jnp.float32)
    a = jnp.asarray(_sigmoid(r_.normal(size=(b, t, c))), jnp.float32)
    h0 = jnp.zeros((b, c), jnp.float32)
    y_full, h_full = ref.rglru_scan(x, a, h0)
    y1, h1 = ref.rglru_scan(x[:, :5], a[:, :5], h0)
    y2, h2 = ref.rglru_scan(x[:, 5:], a[:, 5:], h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, rtol=1e-6)
    np.testing.assert_allclose(h2, h_full, rtol=1e-6)
