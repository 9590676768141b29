"""Pallas matmul kernel vs pure-jnp oracle: shape/dtype/schedule sweeps."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.schedule import Schedule, concretize
from repro.core.workload import KernelInstance
from repro.kernels import matmul as mk
from repro.kernels import ref

DIMS = st.sampled_from([16, 32, 48, 64, 96])
TILES = st.sampled_from([8, 16, 32])
ORDERS = st.sampled_from([("M", "N", "K"), ("N", "M", "K"), ("M", "K", "N"),
                          ("K", "M", "N"), ("N", "K", "M")])


def _data(m, n, k, dtype):
    r = np.random.default_rng(m * 131 + n * 17 + k)
    x = jnp.asarray(r.normal(size=(m, k)), dtype)
    w = jnp.asarray(r.normal(size=(k, n)), dtype)
    return x, w


@given(m=DIMS, n=DIMS, k=DIMS, tm=TILES, tn=TILES, tk=TILES, order=ORDERS,
       cw=st.booleans())
@settings(max_examples=25, deadline=None)
def test_matmul_matches_oracle(m, n, k, tm, tn, tk, order, cw):
    x, w = _data(m, n, k, jnp.float32)
    inst = KernelInstance.make("matmul", M=m, N=n, K=k, dtype="float32")
    sched = Schedule.make("matmul", {"M": tm, "N": tn, "K": tk}, order=order,
                          cache_write=cw)
    cs = concretize(sched, inst, mode="adaptive")
    y = mk.matmul(x, w, cs, interpret=True)
    np.testing.assert_allclose(y, ref.matmul(x, w, "matmul"), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("class_id,needs", [
    ("matmul_bias", "bias"),
    ("matmul_bias_gelu", "bias"),
    ("matmul_silu_glu", None),
    ("matmul_gelu_glu", None),
    ("matmul_residual", "residual"),
    ("matmul_lmhead", None),
    ("matmul_lmhead_softcap", None),
])
def test_epilogues_match_oracle(class_id, needs):
    m, n, k = 32, 64, 48
    x, w = _data(m, n, k, jnp.float32)
    r = np.random.default_rng(5)
    bias = jnp.asarray(r.normal(size=(n,)), jnp.float32) if needs == "bias" else None
    out_n = n // 2 if "glu" in class_id else n
    residual = jnp.asarray(r.normal(size=(m, out_n)), jnp.float32) if needs == "residual" else None
    softcap = 30.0 if "softcap" in class_id else 0.0
    inst = KernelInstance.make(class_id, M=m, N=n, K=k, dtype="float32")
    # GLU tiles hold whole (gate, up) pairs: at n=64 that is the full width
    tn = n if "glu" in class_id else 16
    cs = concretize(Schedule.make(class_id, {"M": 16, "N": tn, "K": 16}), inst)
    y = mk.matmul(x, w, cs, class_id=class_id, bias=bias, residual=residual,
                  softcap=softcap, interpret=True)
    yr = ref.matmul(x, w, class_id, bias=bias, residual=residual, softcap=softcap)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("class_id", ["matmul_silu_glu", "matmul_gelu_glu"])
@pytest.mark.parametrize("tn", [256, 512])
def test_glu_chunk_pairs_match_oracle(class_id, tn):
    """n=512 packs two (gate, up) pairs of 128-column chunks: a 256-wide N
    tile runs one pair per block, the full width both in one block; M and K
    run several blocks, the last M block partial."""
    m, n, k = 40, 512, 48
    x, w = _data(m, n, k, jnp.float32)
    inst = KernelInstance.make(class_id, M=m, N=n, K=k, dtype="float32")
    cs = concretize(Schedule.make(class_id, {"M": 16, "N": tn, "K": 16}), inst)
    assert cs.t["N"] == tn and cs.g["N"] == n // tn
    y = mk.matmul(x, w, cs, class_id=class_id, interpret=True)
    assert y.shape == (m, n // 2)
    np.testing.assert_allclose(y, ref.matmul(x, w, class_id), rtol=2e-4, atol=2e-4)


def test_glu_packing_matches_separate_projections():
    """pack_glu's chunk interleave + the GLU epilogue equal act(x@g) * (x@u)."""
    from repro.models.common import pack_glu

    m, k, f = 16, 32, 256
    r = np.random.default_rng(11)
    x, wg, wu = (jnp.asarray(r.normal(size=s), jnp.float32)
                 for s in ((m, k), (k, f), (k, f)))
    w = pack_glu(wg, wu)
    inst = KernelInstance.make("matmul_silu_glu", M=m, N=2 * f, K=k, dtype="float32")
    cs = concretize(Schedule.make("matmul_silu_glu", {"M": 8, "N": 256, "K": 16}), inst)
    y = mk.matmul(x, w, cs, class_id="matmul_silu_glu", interpret=True)
    want = jax.nn.silu(x @ wg) * (x @ wu)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(ref.matmul(x, w, "matmul_silu_glu"), want,
                               rtol=2e-4, atol=2e-4)


def test_bfloat16_tolerance():
    m, n, k = 64, 64, 64
    x, w = _data(m, n, k, jnp.bfloat16)
    inst = KernelInstance.make("matmul", M=m, N=n, K=k, dtype="bfloat16")
    cs = concretize(Schedule.make("matmul", {"M": 16, "N": 32, "K": 16}), inst)
    y = mk.matmul(x, w, cs, interpret=True)
    yr = ref.matmul(x, w, "matmul")
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_grouped_matmul_matches_vmapped_oracle():
    e, m, n, k = 4, 32, 48, 32
    r = np.random.default_rng(9)
    x = jnp.asarray(r.normal(size=(e, m, k)), jnp.float32)
    w = jnp.asarray(r.normal(size=(e, k, n)), jnp.float32)
    inst = KernelInstance.make("moe_gemm", M=m, N=n, K=k, E=e, dtype="float32")
    cs = concretize(Schedule.make("moe_gemm", {"M": 16, "N": 16, "K": 16, "E": 1},
                                  order=("E", "M", "N", "K")), inst)
    y = mk.grouped_matmul(x, w, cs, interpret=True)
    yr = jax.vmap(lambda a, b: ref.matmul(a, b, "moe_gemm"))(x, w)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)


def test_glu_forces_scratch_on_bad_order():
    """GLU epilogues silently canonicalize to K-inner scratch accumulation
    (two N blocks of one (gate, up) chunk pair each)."""
    m, n, k = 32, 512, 32
    x, w = _data(m, n, k, jnp.float32)
    inst = KernelInstance.make("matmul_silu_glu", M=m, N=n, K=k, dtype="float32")
    cs = concretize(Schedule.make("matmul_silu_glu", {"M": 16, "N": 256, "K": 16},
                                  order=("K", "M", "N"), cache_write=False), inst)
    y = mk.matmul(x, w, cs, class_id="matmul_silu_glu", interpret=True)
    np.testing.assert_allclose(y, ref.matmul(x, w, "matmul_silu_glu"),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m", [8, 200])
@pytest.mark.parametrize("class_id,n,tn", [
    ("matmul", 64, 32),
    ("matmul_bias", 64, 32),
    ("matmul_bias_gelu", 64, 32),
    ("matmul_silu_glu", 512, 256),
])
def test_layer_indexed_matmul_reads_the_stack_in_place(class_id, n, tn, m):
    """The weight as a whole (L=3, K, N) stack with a traced layer index
    equals the kernel on the sliced w[l], at every layer: three K blocks and,
    at M=200, a partial last M tile."""
    k, layers = 48, 3
    r = np.random.default_rng(m + n)
    x = jnp.asarray(r.normal(size=(m, k)), jnp.float32)
    stack = jnp.asarray(r.normal(size=(layers, k, n)), jnp.float32)
    bias = (jnp.asarray(r.normal(size=(n,)), jnp.float32)
            if "bias" in class_id else None)
    inst = KernelInstance.make(class_id, M=m, N=n, K=k, dtype="float32")
    cs = concretize(Schedule.make(class_id, {"M": 64, "N": tn, "K": 16}), inst)
    assert cs.g["K"] == 3
    in_place = jax.jit(lambda x, w, l: mk.matmul(
        x, w, cs, layer=l, class_id=class_id, bias=bias, interpret=True))
    for layer in range(layers):
        got = in_place(x, stack, layer)
        want = mk.matmul(x, stack[layer], cs, class_id=class_id, bias=bias,
                         interpret=True)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Ragged grouped matmul: rows sorted by expert
# ---------------------------------------------------------------------------


def test_ragged_visits_skip_empty_groups_and_revisit_shared_tiles():
    """Groups of 5, 0, 17 and 2 rows in tiles of 8: expert 0 takes tile 0,
    the empty expert 1 no visit, expert 2 tiles 0-2 and expert 3 tile 2;
    the table pads to the bound 24 // 8 + 4 with the last visit."""
    groups, tiles, visits, starts, ends = mk.ragged_visits(
        jnp.asarray([5, 0, 17, 2], jnp.int32), 24, 8)
    assert int(visits[0]) == 5
    assert groups.shape == tiles.shape == (7,)
    assert groups.tolist() == [0, 2, 2, 2, 3, 3, 3]
    assert tiles.tolist() == [0, 0, 1, 2, 2, 2, 2]
    assert starts.tolist() == [0, 5, 5, 22] and ends.tolist() == [5, 5, 22, 24]


@pytest.mark.parametrize("sizes", [(5, 0, 17, 2), (0, 24, 0, 0), (7, 7, 7, 3)],
                         ids=["uneven_with_an_empty_expert", "one_expert_takes_every_row",
                              "sizes_that_do_not_divide_the_tile"])
@pytest.mark.parametrize("class_id,n,tn", [("moe_gemm", 48, 16),
                                           ("moe_gemm_silu_glu", 512, 256)])
def test_ragged_matmul_matches_a_per_expert_loop(sizes, class_id, n, tn):
    """Each expert's rows times its own weight, read in place from layer 1
    of a (2, E, K, N) stack, in row tiles of 8 over two K blocks; three rows
    past the groups belong to no expert."""
    e, k = len(sizes), 32
    m = sum(sizes) + 3
    r = np.random.default_rng(sum(sizes) + n)
    x = jnp.asarray(r.normal(size=(m, k)), jnp.float32)
    stack = jnp.asarray(r.normal(size=(2, e, k, n)), jnp.float32)
    inst = KernelInstance.make(class_id, M=m, N=n, K=k, E=e, dtype="float32")
    cs = concretize(Schedule.make(class_id, {"M": 8, "N": tn, "K": 16, "E": 1}), inst)
    got = jax.jit(lambda x, w, g: mk.ragged_matmul(
        x, w, g, cs, layer=1, class_id=class_id, interpret=True))(
            x, stack, jnp.asarray(sizes, jnp.int32))
    bounds = np.cumsum((0,) + sizes)
    want = jnp.concatenate([ref.matmul(x[lo:hi], stack[1, i], class_id)
                            for i, (lo, hi) in enumerate(zip(bounds, bounds[1:]))])
    np.testing.assert_allclose(got[:bounds[-1]], want, rtol=2e-4, atol=2e-4)
