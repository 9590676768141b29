"""Compile-only checks of the main-path kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at gemma2-2b's real widths and asks
the TPU compiler (Mosaic) for a chip that is described, not attached.  That
catches what interpret mode cannot — blocks Mosaic refuses, VMEM overflow,
values captured into a kernel — at no chip time.  Alongside, the legality
rule (:mod:`repro.core.legality`) must reject exactly the schedules the
compiler would refuse, before they reach it.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library.
"""
import random

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.core import legality
from repro.core.autoscheduler import random_schedule
from repro.core.cost_model import measure
from repro.core.resolution import ResolutionPipeline, StaticMapStage, DefaultStage, plan_serving
from repro.core.schedule import Schedule, ScheduleInvalid, concretize
from repro.core.workload import KernelInstance
from repro.hw.specs import TPU_V5E
from repro.kernels import flash_attention as fa
from repro.kernels import matmul as mk
from repro.kernels import rglru_scan as rg
from repro.kernels import rwkv6_scan as rw

GEMMA = get_arch("gemma2-2b")
D, F, V, HD = GEMMA.d_model, GEMMA.d_ff, GEMMA.vocab_size, GEMMA.head_dim
HQ, HKV = GEMMA.n_heads, GEMMA.n_kv_heads


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Compiles for a described chip cannot be read back without one: keep
    # them out of any persistent cache another test may have switched on.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _matmul_cs(class_id, m, n, k, tiles, **kw):
    inst = KernelInstance.make(class_id, M=m, N=n, K=k)
    cs = concretize(Schedule.make(class_id, tiles, **kw), inst)
    legality.check(cs, TPU_V5E)
    return cs


def _compile_matmul(one_chip, cs, softcap=0.0):
    p = cs.instance.p
    m, n, k = p["M"], p["N"], p["K"]
    return _compile(
        lambda x, w: mk.matmul(x, w, cs, class_id=cs.instance.class_id,
                               softcap=softcap, interpret=False),
        _sds(one_chip, (m, k)), _sds(one_chip, (k, n)))


@pytest.mark.parametrize("class_id,m,n,k,tiles,softcap", [
    # attention q projection, 256-token prefill bucket
    ("matmul", 256, HQ * HD, D, {"M": 128, "N": 256, "K": 384}, 0.0),
    # GeGLU up-projection (interleaved gate/up), 8-slot decode batch
    ("matmul_gelu_glu", 8, 2 * F, D, {"M": 8, "N": 1024, "K": 768}, 0.0),
    # softcapped tied lm head over the full vocabulary
    ("matmul_lmhead_softcap", 8, V, D, {"M": 8, "N": 1024, "K": D}, 30.0),
])
def test_matmul_classes_compile(one_chip, class_id, m, n, k, tiles, softcap):
    cs = _matmul_cs(class_id, m, n, k, tiles)
    compiled = _compile_matmul(one_chip, cs, softcap)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("class_id,window,softcap", [
    ("flash_attention_causal", 0, 0.0),
    ("flash_attention_local", GEMMA.window, 0.0),
    ("flash_attention_softcap", 0, GEMMA.attn_softcap),
])
def test_flash_attention_compiles_with_traced_offset(one_chip, class_id, window,
                                                     softcap):
    sq = skv = 256
    inst = KernelInstance.make(class_id, Q=sq, KV=skv, H=HQ, D=HD, B=1)
    cs = concretize(Schedule.make(class_id, {"Q": 128, "KV": 128}), inst)
    legality.check(cs, TPU_V5E)
    # q_offset is traced, as in chunked prefill: it must enter the kernel as
    # an operand, not as a captured constant
    _compile(lambda q, k, v, off: fa.flash_attention(
        q, k, v, cs, causal=True, window=window, softcap=softcap, q_offset=off,
        interpret=False),
        _sds(one_chip, (1, HQ, sq, HD)), _sds(one_chip, (1, HKV, skv, HD)),
        _sds(one_chip, (1, HKV, skv, HD)), _sds(one_chip, (), jnp.int32))


def test_rglru_scan_compiles_batched(one_chip):
    b, t, c = 2, 256, 2560   # recurrentgemma-2b width, batch > 1
    inst = KernelInstance.make("rglru_scan", T=t, C=c, B=b)
    cs = concretize(Schedule.make("rglru_scan", {"T": 128, "C": 512},
                                  order=("C", "T")), inst)
    legality.check(cs, TPU_V5E)
    _compile(lambda x, a, s: rg.rglru_scan(x, a, s, cs, interpret=False),
             _sds(one_chip, (b, t, c)), _sds(one_chip, (b, t, c)),
             _sds(one_chip, (b, c), jnp.float32))


def test_rwkv6_scan_compiles_multihead(one_chip):
    b, h, t, d = 2, 32, 256, 64   # rwkv6-1.6b heads, batch > 1
    inst = KernelInstance.make("rwkv6_scan", T=t, C=h * d, D=d, B=b)
    cs = concretize(Schedule.make("rwkv6_scan", {"T": 64, "C": h * d},
                                  order=("C", "T")), inst)
    legality.check(cs, TPU_V5E)
    x = _sds(one_chip, (b, h, t, d))
    _compile(lambda r, k, v, w, u, s: rw.rwkv6_scan(r, k, v, w, u, s, cs,
                                                    interpret=False),
             x, x, x, x, _sds(one_chip, (h, d)),
             _sds(one_chip, (b, h, d, d), jnp.float32))


def test_schedule_at_vmem_budget_edge_compiles(one_chip):
    """The largest tiles the rule admits compile under the budget every
    pallas_call hands Mosaic; one step larger is refused by the rule."""
    cs = _matmul_cs("matmul", 4096, 8192, D, {"M": 1024, "N": 4096, "K": D})
    assert legality.vmem_bytes(cs, TPU_V5E) > 0.9 * TPU_V5E.vmem_capacity
    _compile_matmul(one_chip, cs)
    with pytest.raises(ScheduleInvalid, match="VMEM"):
        _matmul_cs("matmul", 4096, 8192, D, {"M": 2048, "N": 4096, "K": D})


def test_illegal_tile_rejected_before_the_compiler(one_chip):
    """bm=3 breaks the (8, 128) block rule: the legality rule, the cost model
    and the resolution pipeline all reject it; the compiler agrees."""
    inst = KernelInstance.make("matmul", M=256, N=D, K=D)
    bad = Schedule.make("matmul", {"M": 3, "N": 256, "K": 256})
    cs = concretize(bad, inst)   # shape-valid: 3 is a maskable row tile
    with pytest.raises(ScheduleInvalid, match="multiple of 8"):
        legality.check(cs, TPU_V5E)
    assert not measure(inst, bad).valid
    pipe = ResolutionPipeline([StaticMapStage({inst.workload_key(): bad}),
                               DefaultStage()])
    res = pipe.resolve(inst)
    assert res.tier == "default" and pipe.stats()["rejected_illegal"] == 1
    legality.check(res.concrete, TPU_V5E)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile_matmul(one_chip, cs)


@pytest.fixture(scope="module")
def serving_instances():
    """gemma2-2b's serving plan: 8 decode slots, prompts bucketed to 8, 256."""
    plan = plan_serving(GEMMA, ResolutionPipeline.build(), slots=8, max_len=256,
                        prefill_lengths=[8, 256])
    return sorted({u.instance for u in plan.uses}, key=lambda i: i.workload_key())


def _compile_attention(one_chip, cs):
    p = cs.instance.p
    b, sq, skv = p["B"], p["Q"], p["KV"]
    # gemma2 softcaps every layer's logits; local layers add the window
    return _compile(lambda q, k, v, off: fa.flash_attention(
        q, k, v, cs, causal=True, window=p.get("window", 0),
        softcap=GEMMA.attn_softcap, q_offset=off, interpret=False),
        _sds(one_chip, (b, HQ, sq, HD)), _sds(one_chip, (b, HKV, skv, HD)),
        _sds(one_chip, (b, HKV, skv, HD)), _sds(one_chip, (), jnp.int32))


@pytest.mark.parametrize("class_id", [
    "matmul", "matmul_gelu_glu", "matmul_lmhead_softcap",
    "flash_attention_local", "flash_attention_softcap"])
def test_tuner_schedules_for_serving_shapes_compile(one_chip, serving_instances,
                                                    class_id):
    """For every gemma2-2b serving instance of the class, a schedule the
    tuner proposes either passes the rule and compiles, or is ScheduleInvalid
    before the compiler.  Attention also compiles the smallest KV tile the
    rule admits, which sets the lane width of the (Q, KV) score tile."""
    insts = [i for i in serving_instances if i.class_id == class_id]
    assert insts
    rng = random.Random(0)
    for inst in insts:
        legal = []
        for _ in range(32):
            s = random_schedule(inst, rng)
            try:
                cs = concretize(s, inst)
                legality.check(cs, TPU_V5E)
            except ScheduleInvalid:
                continue   # refused before any compile: a -1 bar
            legal.append(cs)
        assert legal, inst
        if inst.family == "matmul":
            softcap = GEMMA.final_softcap if "softcap" in class_id else 0.0
            _compile_matmul(one_chip, legal[0], softcap)
            continue
        _compile_attention(one_chip, legal[0])
        unit = legality.axis_units(inst, TPU_V5E)["KV"]
        smallest = concretize(Schedule.make(
            class_id, {"Q": legal[0].t["Q"], "KV": unit}), inst)
        legality.check(smallest, TPU_V5E)
        _compile_attention(one_chip, smallest)


def test_kernels_carry_their_class_id_as_their_hlo_op_name(one_chip):
    """Inside a layer scan, as the models run them, a matmul and a flash
    attention call are named by their class in the compiled HLO, the name
    the TPU's op line in a profile gives them (not ``closed_call``)."""
    import re

    mm = _matmul_cs("matmul_bias_gelu", 256, 1024, 512,
                    {"M": 128, "N": 256, "K": 256})
    inst = KernelInstance.make("flash_attention_causal", Q=256, KV=256, H=8,
                               D=HD, B=1)
    fa_cs = concretize(Schedule.make("flash_attention_causal",
                                     {"Q": 128, "KV": 128}), inst)
    legality.check(fa_cs, TPU_V5E)

    def layers(x, w, b, q, k, v):
        def body(carry, _):
            y = mk.matmul(carry, w, mm, class_id="matmul_bias_gelu", bias=b,
                          interpret=False)
            o = fa.flash_attention(q, k, v, fa_cs,
                                   class_id="flash_attention_causal",
                                   interpret=False)
            return carry + (y[:, :512] + o.reshape(256, -1)[:, :512]).astype(
                carry.dtype), None
        return jax.lax.scan(body, x, None, length=2)[0]

    text = _compile(layers, _sds(one_chip, (256, 512)), _sds(one_chip, (512, 1024)),
                    _sds(one_chip, (1024,)), _sds(one_chip, (1, 8, 256, HD)),
                    _sds(one_chip, (1, 2, 256, HD)),
                    _sds(one_chip, (1, 2, 256, HD))).as_text()
    names = {re.sub(r"[.\d]+$", "", m) for m in re.findall(
        r"^\s*(?:ROOT )?%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text, re.M)}
    assert names == {"matmul_bias_gelu", "flash_attention_causal"}


@pytest.mark.parametrize("in_place", [True, False])
def test_layer_scan_reads_stacked_weights_in_place(one_chip, in_place):
    """A layer scan that closes over a (L, K, N) weight stack and hands the
    matmul the layer index, as the serving scans do, compiles with no
    dynamic-slice copying a layer out of the stack; scanning the stack as
    the scan's input (the control) compiles to that copy."""
    import re

    cs = _matmul_cs("matmul", 8, D, D, {"M": 8, "N": 256, "K": 256})

    def layers(x, stack):
        def body(h, xs):
            w, layer = (stack, xs) if in_place else (xs, 0)
            return mk.matmul(h, w, cs, layer=layer, interpret=False), None
        xs = jnp.arange(stack.shape[0]) if in_place else stack
        return jax.lax.scan(body, x, xs)[0]

    text = _compile(layers, _sds(one_chip, (8, D)),
                    _sds(one_chip, (4, D, D))).as_text()
    assert "tpu_custom_call" in text
    copies = re.findall(rf"%[\w.-]*dynamic-slice[\w.-]* = bf16\[(?:1,)?{D},{D}\]",
                        text)
    assert bool(copies) != in_place


@pytest.mark.parametrize("class_id,n,k,tiles", [
    ("moe_gemm_silu_glu", 2 * 896, 2304, {"M": 128, "N": 256, "K": 256, "E": 1}),
    ("moe_gemm", 2304, 896, {"M": 128, "N": 384, "K": 128, "E": 1}),
])
def test_ragged_expert_gemm_compiles_reading_the_stack_in_place(one_chip, class_id,
                                                               n, k, tiles):
    """mellum2-12b-a2.5b's expert GEMMs over a 2048-token prefill's 16,384
    routed rows, each expert's weights read in place from a (3, 64, K, N)
    stack at a traced layer, with no copy of the stack."""
    import re

    m = 2048 * 8
    inst = KernelInstance.make(class_id, M=m, N=n, K=k, E=64)
    cs = concretize(Schedule.make(class_id, tiles, order=("E", "M", "N", "K")), inst)
    legality.check(cs, TPU_V5E)
    text = _compile(
        lambda x, w, g, layer: mk.ragged_matmul(x, w, g, cs, layer=layer,
                                                class_id=class_id, interpret=False),
        _sds(one_chip, (m, k)), _sds(one_chip, (3, 64, k, n)),
        _sds(one_chip, (64,), jnp.int32), _sds(one_chip, (), jnp.int32)).as_text()
    assert "tpu_custom_call" in text
    assert not re.findall(rf"= bf16\[(?:3,|1,)?64,{k},{n}\]\S* (?:copy|dynamic-slice|fusion)",
                          text)


@pytest.mark.parametrize("arch,slots,size,softcap", [
    ("minitron-4b", 8, 2048, 0.0),          # 3 query rows a KV head
    ("starcoder2-7b", 16, 4096, 0.0),       # 9: no multiple of 8
    ("mellum2-12b-a2.5b", 16, 1024, 0.0),   # a ring layer of the window
    ("gemma2-2b", 8, 2048, GEMMA.attn_softcap),
])
def test_decode_attention_compiles_reading_the_cache_stack(one_chip, arch, slots,
                                                           size, softcap):
    """The decode-attention kernel at each served configuration's widths,
    reading layer ``l`` (traced) of a (3, B, KV, T, hd) cache stack in
    place: it compiles, and no copy or slice of the stack or of a layer's
    cache is made."""
    import re

    from repro.kernels import decode_attention as da

    cfg = get_arch(arch)
    kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    layer = (slots, kv, size, cfg.head_dim)
    text = _compile(
        lambda q, k, v, lim, l: da.decode_attention(q, k, v, lim, layer=l,
                                                    softcap=softcap,
                                                    interpret=False),
        _sds(one_chip, (slots, kv, g, cfg.head_dim)),
        _sds(one_chip, (3,) + layer), _sds(one_chip, (3,) + layer),
        _sds(one_chip, (slots,), jnp.int32), _sds(one_chip, (), jnp.int32)).as_text()
    assert "tpu_custom_call" in text
    dims = ",".join(map(str, layer))
    assert not re.findall(rf"= bf16\[(?:3,|1,)?{dims}\]\S* (?:copy|dynamic-slice|fusion)",
                          text)


def test_engine_decode_donates_the_cache_and_copies_none_of_it(one_chip,
                                                              monkeypatch):
    """The slot engine's jitted decode step at minitron-4b's widths (two
    layers, 8 slots of 2048 positions), compiled for a v5e where the kernels
    compile: the donated cache is aliased to the new cache whole, the step
    needs no temporary the size of a layer's cache, no cache stack is
    copied or a layer's cache sliced out, and the attention is the
    ``decode_attention`` kernel."""
    import dataclasses
    import re

    from repro.kernels import ops
    from repro.models import build_model
    from repro.serving import ServingEngine

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_arch("minitron-4b"), n_layers=2)
    model = build_model(cfg)
    eng = ServingEngine(model, None, slots=1, max_len=8)
    slots, max_len = 8, 2048

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: model.init_cache(slots, max_len)))
    with ops.use_backend("pallas"):
        compiled = eng._decode.lower(params, cache,
                                     _sds(one_chip, (slots,), jnp.int32)).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    layer_bytes = slots * cfg.n_kv_heads * max_len * cfg.head_dim * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < layer_bytes
    text = compiled.as_text()
    stack = f"2,{slots},{cfg.n_kv_heads},{max_len},{cfg.head_dim}"
    one = f"{slots},{cfg.n_kv_heads},{max_len},{cfg.head_dim}"
    assert not re.findall(rf"= bf16\[{stack}\]\S* copy\(", text)
    assert not re.findall(rf"= bf16\[(?:1,)?{one}\]\S* (?:copy|dynamic-slice|fusion)\(",
                          text)
    assert re.search(r"%decode_attention[\w.-]* = [^\n]*tpu_custom_call", text)
