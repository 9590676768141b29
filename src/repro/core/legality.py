"""What the TPU's Pallas compiler (Mosaic) accepts, as one rule.

Search (the cost model's :func:`~repro.core.cost_model.evaluate`) and
resolution (:class:`~repro.core.resolution.ResolutionPipeline`) both call
:func:`check`, so a schedule Mosaic would refuse is ``ScheduleInvalid`` — the
paper's −1 bar, "does not compile" — before it reaches the compiler.  The
rule has three parts, the last two built from the target's
:class:`~repro.hw.specs.ChipSpec`:

* **order** — the reduction axis runs innermost.  The kernels keep their
  accumulator in VMEM across the whole reduction and realize no other
  order, so the search and the cost model see only the one that runs;
* **alignment** — the last two dims of every block a kernel builds are
  multiples of ``(vreg_sublanes, vreg_lanes)`` or equal the array's full
  extent.  :data:`AXIS_ROLE` says which of the two each schedule axis lands
  on, mirroring the BlockSpecs in :mod:`repro.kernels`;
* **VMEM** — the double-buffered in/out blocks plus scratch and the kernel
  body's f32 temporaries, padded to the (sublane, lane) tile, fit
  ``vmem_capacity``, which every ``pallas_call`` also passes to Mosaic as
  ``vmem_limit_bytes``.

Classes no Pallas kernel realizes (the implicit-GEMM CNN classes of the
paper's §4.2 study) are priced, never compiled: the alignment part does not
apply to them.
"""
from __future__ import annotations

import math

from repro.core.schedule import (GLU_CLASSES, REDUCTION_AXIS, ConcreteSchedule,
                                 ScheduleInvalid, glu_chunk)
from repro.core.workload import KernelInstance
from repro.hw.specs import ChipSpec

SUBLANE, LANE = "sublane", "lane"

#: class -> axis -> the block dim the axis tiles (see the kernels' BlockSpecs).
_MATMUL_ROLE = {"M": SUBLANE, "K": LANE, "N": LANE}
AXIS_ROLE: dict[str, dict[str, str]] = {
    **{c: _MATMUL_ROLE for c in (
        "matmul", "matmul_bias", "matmul_bias_gelu", "matmul_silu_glu",
        "matmul_gelu_glu", "matmul_residual", "matmul_lmhead",
        "matmul_lmhead_softcap", "moe_gemm_silu_glu", "moe_gemm", "moe_router")},
    **{c: {"Q": SUBLANE, "KV": SUBLANE} for c in (
        "flash_attention_causal", "flash_attention_swa", "flash_attention_local",
        "flash_attention_softcap", "flash_attention_bidir",
        "flash_attention_cross")},
    "rglru_scan": {"T": SUBLANE, "C": LANE},
    "rwkv6_scan": {"T": SUBLANE},   # the kernel blocks whole heads: C is free
}

_BIAS_CLASSES = ("matmul_bias", "matmul_bias_gelu")
_ESIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def axis_units(instance: KernelInstance, spec: ChipSpec) -> dict[str, int]:
    """Per-axis tile multiple a kernel block needs (a full extent always
    passes).  A GLU N tile holds whole (gate, up) chunk pairs, each chunk one
    vreg's lanes wide (or the whole width when it is not a multiple)."""
    unit = {SUBLANE: spec.vreg_sublanes, LANE: spec.vreg_lanes}
    out = {a: unit[r] for a, r in AXIS_ROLE.get(instance.class_id, {}).items()}
    if instance.class_id in GLU_CLASSES:
        out["N"] = 2 * glu_chunk(instance.extent("N") // 2)
    return out


def _block_extents(cs: ConcreteSchedule) -> dict[str, tuple[int, int]]:
    """axis -> (tile the kernel builds, extent of the array dim it tiles)."""
    p = cs.instance.p
    ext = {a: p[a] for a in cs.instance.axes}
    # Expert GEMMs: the ragged kernel tiles all M routed rows, the grouped
    # (capacity) kernel each expert's M / E; a tile that passes against M
    # passes against M / E, and fills no less VMEM.
    return {a: (min(cs.t[a], e), e) for a, e in ext.items()}


def _padded(rows: int, cols: int, esize: int, spec: ChipSpec) -> int:
    """Bytes of a (rows, cols) VMEM buffer padded to whole vreg tiles
    (sub-32-bit dtypes pack ``4 // esize`` rows per sublane)."""
    sub = spec.vreg_sublanes * max(1, 4 // esize)
    return (math.ceil(rows / sub) * sub * math.ceil(cols / spec.vreg_lanes)
            * spec.vreg_lanes * esize)


def vmem_bytes(cs: ConcreteSchedule, spec: ChipSpec) -> int:
    """VMEM the kernel allocates for this schedule: 2× every in/out block
    (Pallas double-buffers both), scratch, and the body's f32 temporaries."""
    inst = cs.instance
    es = _ESIZE[inst.dtype]
    b = {a: t for a, (t, _) in _block_extents(cs).items()}

    def buf(rows, cols, esize=es):
        return _padded(rows, cols, esize, spec)

    if inst.family == "matmul":
        bm, bn, bk = b["M"], b["N"], b["K"]
        bn_out = bn // 2 if inst.class_id in GLU_CLASSES else bn
        io = buf(bm, bk) + buf(bk, bn) + buf(bm, bn_out)
        if inst.class_id in _BIAS_CLASSES:
            io += buf(1, bn)
        if inst.class_id == "matmul_residual":
            io += buf(bm, bn_out)
        scratch = buf(bm, bn, 4) if (cs.schedule.cache_write
                                     or inst.class_id in GLU_CLASSES
                                     or "E" in inst.p) else 0
        return 2 * io + scratch + buf(bm, bn, 4)          # + f32 dot result
    if inst.family == "attention":
        bq, bkv, d = b["Q"], b["KV"], inst.p.get("D", 128)
        io = 2 * buf(bq, d) + 2 * buf(bkv, d)              # q, out, k, v
        scratch = buf(bq, d, 4) + 2 * buf(bq, 1, 4)        # acc, m, l
        temps = buf(bq, d, 4) + 2 * buf(bkv, d, 4) + 2 * buf(bq, bkv, 4)
        return 2 * io + scratch + temps
    ct = b["T"]
    if inst.class_id == "rglru_scan":
        bc = b["C"]
        io = 3 * buf(ct, bc) + 2 * buf(1, bc, 4)           # x, a, y, h0, hT
        return 2 * io + 2 * buf(ct, bc, 4) + buf(1, bc, 4)
    d = inst.p.get("D", 64)                                # rwkv6_scan
    io = 5 * buf(ct, d) + buf(1, d) + 2 * buf(d, d, 4)     # r k v w y, u, s0 sT
    return 2 * io + 4 * buf(ct, d, 4) + 4 * buf(d, d, 4)


def check(cs: ConcreteSchedule, spec: ChipSpec) -> None:
    """Raise :class:`ScheduleInvalid` unless Mosaic compiles ``cs`` on
    ``spec``'s chip within its VMEM budget."""
    reduction = REDUCTION_AXIS[cs.instance.family]
    if cs.order[-1] != reduction:
        raise ScheduleInvalid(
            f"reduction axis {reduction} is not innermost in {cs.order}")
    units = axis_units(cs.instance, spec)
    blocks = _block_extents(cs)
    for axis, unit in units.items():
        tile, extent = blocks[axis]
        if tile != extent and tile % unit:
            raise ScheduleInvalid(
                f"block {axis}={tile} is neither a multiple of {unit} nor the "
                f"full extent {extent}")
    vmem = vmem_bytes(cs, spec)
    if vmem > spec.vmem_capacity:
        raise ScheduleInvalid(f"VMEM overflow: {vmem} > {spec.vmem_capacity}")
