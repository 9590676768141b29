"""Schedule-driven Pallas matmul kernel (fused epilogues).

The kernel realizes a :class:`repro.core.schedule.ConcreteSchedule` on TPU:

* ``tiles``      → BlockSpec block shapes (bm, bn, bk);
* ``order``      → grid order of the M and N axes (Pallas iterates the last
                    grid dim fastest).  The reduction axis K always runs
                    innermost: Pallas on TPU writes an output block back when
                    its index changes and never reads it in again, so an
                    accumulator revisited across a K-outer loop would restart
                    from stale VMEM.  The legality rule refuses K-outer
                    orders (:mod:`repro.core.legality`);
* ``cache_write``→ f32 VMEM scratch accumulator; otherwise partial sums are
                    accumulated in the output block (out dtype);
* epilogues (bias/gelu/glu/residual/softcap) are applied on the final
  reduction step, inside the kernel.

The weight operand is always a stack (L, K, N) with the layer index as a
scalar-prefetch operand, so a layer scan hands the kernel the whole stacked
parameter and its blocks are read in place, never sliced out into a copy
first (a custom call cannot fuse a slice of its operand).  The schedule is
the same at any layer: the blocks fetched are the same bytes at another base.

GLU epilogues use *chunk-interleaved* packing — columns alternate (gate, up)
in chunks of ``glu_chunk`` (one vreg's 128 lanes) — so one N-block holds
complete pairs and can emit its (bm, bn/2) output block independently.
Shape-changing epilogues therefore always accumulate in the f32 scratch.

Validated against :mod:`repro.kernels.ref` in interpret mode (tests sweep
shapes × dtypes × schedules).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import ConcreteSchedule, glu_chunk
from repro.hw.specs import TPU_V5E

SHAPE_CHANGING = ("matmul_silu_glu", "matmul_gelu_glu", "moe_gemm_silu_glu")


def _glu_block(y: jax.Array, act, chunk: int) -> jax.Array:
    """GLU over one (bm, bn) block of chunk-interleaved (gate, up) columns:
    static, chunk-aligned lane slices (Mosaic lowers no strided ones)."""
    return jnp.concatenate(
        [act(y[:, j:j + chunk]) * y[:, j + chunk:j + 2 * chunk]
         for j in range(0, y.shape[1], 2 * chunk)], axis=1)


def _epilogue_fn(class_id: str, softcap: float,
                 chunk: int) -> Callable[..., jax.Array]:
    def f(acc, bias=None, residual=None):
        y = acc
        if bias is not None:
            y = y + bias
        if class_id == "matmul_bias_gelu":
            y = jax.nn.gelu(y)
        elif class_id in ("matmul_silu_glu", "moe_gemm_silu_glu"):
            y = _glu_block(y, jax.nn.silu, chunk)
        elif class_id == "matmul_gelu_glu":
            y = _glu_block(y, jax.nn.gelu, chunk)
        elif class_id == "matmul_residual":
            y = y + residual
        elif class_id == "matmul_lmhead_softcap":
            y = jnp.tanh(y / softcap) * softcap
        return y

    return f


def _kernel(layer_ref, x_ref, w_ref, *rest, class_id: str, softcap: float,
            k_pos: int, k_trips: int, use_scratch: bool, has_bias: bool,
            has_residual: bool, chunk: int, out_dtype):
    """Kernel body shared by all matmul classes.

    ``layer_ref`` (scalar prefetch) is read only by the weight's index map.
    rest = (*optional bias_ref, *optional residual_ref, o_ref, *optional acc_ref)
    """
    del layer_ref
    i = 0
    bias_ref = rest[i] if has_bias else None
    i += int(has_bias)
    residual_ref = rest[i] if has_residual else None
    i += int(has_residual)
    o_ref = rest[i]
    acc_ref = rest[i + 1] if use_scratch else None

    k_idx = pl.program_id(k_pos)
    epilogue = _epilogue_fn(class_id, softcap, chunk)
    partial = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    def emit(acc):
        bias = bias_ref[...].astype(jnp.float32) if bias_ref is not None else None
        res = residual_ref[...].astype(jnp.float32) if residual_ref is not None else None
        o_ref[...] = epilogue(acc, bias, res).astype(out_dtype)

    if use_scratch:
        @pl.when(k_idx == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += partial

        @pl.when(k_idx == k_trips - 1)
        def _():
            emit(acc_ref[...])
    else:
        if k_trips == 1:
            emit(partial)
        else:
            # read-modify-write accumulation in the output block (out dtype)
            @pl.when(k_idx == 0)
            def _():
                o_ref[...] = partial.astype(out_dtype)

            @pl.when((k_idx > 0) & (k_idx < k_trips - 1))
            def _():
                o_ref[...] = (o_ref[...].astype(jnp.float32) + partial).astype(out_dtype)

            @pl.when(k_idx == k_trips - 1)
            def _():
                emit(o_ref[...].astype(jnp.float32) + partial)


def build_call(
    m: int,
    n: int,
    k: int,
    cs: ConcreteSchedule,
    *,
    class_id: str = "matmul",
    softcap: float = 0.0,
    has_bias: bool = False,
    has_residual: bool = False,
    groups: int = 0,
    out_dtype=jnp.float32,
    interpret: bool,
    vmem_limit_bytes: int,
):
    """Build a pallas_call for (layer, x:(M,K), w:(L,K,N), +epilogue inputs)
    -> x @ w[layer].

    The weight is a stack and ``layer`` a (1,) int32 scalar-prefetch operand:
    the weight's index map picks that layer's (bk, bn) blocks, so the DMA
    reads them in place from the stack with no sliced copy in between.  A
    plain (K,N) weight is a stack of one at layer 0.

    ``groups`` > 0 builds the grouped (MoE) variant: x:(E,M,K), w:(E,K,N),
    the leading weight axis indexed by the expert grid axis (``layer`` is
    unused).  Shape-changing (GLU) epilogues emit N//2 columns.
    """
    bm, bn, bk = cs.t["M"], cs.t["N"], cs.t["K"]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    order = [a for a in cs.order if a in ("M", "N")] + ["K"]
    trips = {"M": pl.cdiv(m, bm), "N": pl.cdiv(n, bn), "K": pl.cdiv(k, bk)}
    shape_changing = class_id in SHAPE_CHANGING
    use_scratch = cs.schedule.cache_write or shape_changing
    chunk = glu_chunk(n // 2)
    if shape_changing and bn != n and bn % (2 * chunk):
        raise ValueError(f"GLU epilogue needs whole (gate, up) pairs of "
                         f"{2 * chunk} columns per N tile, got {bn}")

    pos = {a: i for i, a in enumerate(order)}
    g = int(groups > 0)  # leading expert grid dim for grouped matmul
    grid = ((groups,) if g else ()) + tuple(trips[a] for a in order)

    def idx(*axes, weight: bool = False):
        # index maps take the grid ids, then the scalar-prefetch layer ref
        def f(*args):
            *pids, layer = args
            base = {a: pids[g + pos[a]] for a in ("M", "N", "K")}
            lead = (pids[0],) if g else (layer[0],) if weight else ()
            return lead + tuple(base[a] for a in axes)

        return f

    lead_blk = (None,) if g else ()   # None: squeezed out of the kernel's refs
    in_specs = [
        pl.BlockSpec(lead_blk + (bm, bk), idx("M", "K")),
        pl.BlockSpec((None, bk, bn), idx("K", "N", weight=True)),
    ]
    n_out = n // 2 if shape_changing else n
    bn_out = bn // 2 if shape_changing else bn
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda *a: (0, a[g + pos["N"]])))
    if has_residual:
        in_specs.append(pl.BlockSpec(lead_blk + (bm, bn_out), idx("M", "N")))

    out_specs = pl.BlockSpec(lead_blk + (bm, bn_out), idx("M", "N"))

    kernel = functools.partial(
        _kernel,
        class_id=class_id,
        softcap=softcap,
        k_pos=g + pos["K"],
        k_trips=trips["K"],
        use_scratch=use_scratch,
        has_bias=has_bias,
        has_residual=has_residual,
        chunk=chunk,
        out_dtype=out_dtype,
    )

    out_shape = jax.ShapeDtypeStruct(((groups,) if g else ()) + (m, n_out), out_dtype)
    return pl.pallas_call(
        kernel,
        name=class_id,     # the op's name in the HLO and the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                            if use_scratch else []),
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )


def _layer_operand(layer) -> jax.Array:
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def matmul(x: jax.Array, w: jax.Array, cs: ConcreteSchedule, *,
           layer: jax.Array | int = 0, class_id: str = "matmul",
           bias: jax.Array | None = None, residual: jax.Array | None = None,
           softcap: float = 0.0, interpret: bool,
           vmem_limit_bytes: int = TPU_V5E.vmem_capacity) -> jax.Array:
    """Run the kernel: x (M,K) @ w (K,N) with fused epilogue.  ``w`` may be a
    stack (L,K,N), read in place at ``layer`` (an int or a traced scalar)."""
    m, k = x.shape
    stack = w[None] if w.ndim == 2 else w   # a free bitcast
    n = stack.shape[2]
    call = build_call(
        m, n, k, cs, class_id=class_id, softcap=softcap,
        has_bias=bias is not None, has_residual=residual is not None,
        out_dtype=x.dtype, interpret=interpret,
        vmem_limit_bytes=vmem_limit_bytes,
    )
    args = [_layer_operand(layer), x, stack]
    if bias is not None:
        args.append(bias.reshape(1, -1))
    if residual is not None:
        args.append(residual)
    return call(*args)


# ---------------------------------------------------------------------------
# Ragged grouped matmul: rows sorted by expert, one expert's weights a visit
# ---------------------------------------------------------------------------


def ragged_visits(group_sizes: jax.Array, m: int, bm: int):
    """The (row tile, group) pairs a ragged matmul over ``m`` rows in tiles
    of ``bm`` computes: every tile a non-empty group's rows touch, groups in
    order, so a tile two groups share is visited twice in a row and an
    empty group is never visited.  Returns ``(groups, tiles, visits,
    starts, ends)``: the group and row tile of each grid step, padded to
    the static bound ``m // bm + min(E, m)`` by repeating the last visit;
    the number of real visits, (1,); each group's first and past-last row."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // bm
    count = jnp.where(sizes > 0, (ends - 1) // bm - first + 1, 0)
    vend = jnp.cumsum(count)
    visits = vend[-1]
    steps = m // bm + min(e, m)
    v = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), jnp.maximum(visits - 1, 0))
    groups = jnp.minimum(jnp.sum(vend[None, :] <= v[:, None], axis=1), e - 1)
    tiles = first[groups] + v - (vend - count)[groups]
    return (groups.astype(jnp.int32), tiles.astype(jnp.int32),
            jnp.reshape(visits, (1,)), starts, ends)


def _ragged_kernel(layer_ref, groups_ref, tiles_ref, visits_ref, starts_ref,
                   ends_ref, x_ref, w_ref, o_ref, acc_ref, *, class_id: str,
                   bm: int, k_trips: int, chunk: int, out_dtype):
    """One K step of one visit (tile, group).  At the last K step the rows of
    the tile that belong to the group take the result; the first visit of a
    tile zeroes the rest, a later visit keeps what the earlier ones wrote."""
    del layer_ref
    v, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(v < visits_ref[0])
    def _():
        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(kk == k_trips - 1)
        def _():
            group, tile = groups_ref[v], tiles_ref[v]
            rows = tile * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
            mine = (rows >= starts_ref[group]) & (rows < ends_ref[group])
            y = _epilogue_fn(class_id, 0.0, chunk)(acc_ref[...])
            first = (v == 0) | (tiles_ref[jnp.maximum(v - 1, 0)] != tile)
            kept = jnp.where(first, 0.0, o_ref[...].astype(jnp.float32))
            o_ref[...] = jnp.where(mine, y, kept).astype(out_dtype)


def ragged_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                  cs: ConcreteSchedule, *, layer: jax.Array | int = 0,
                  class_id: str = "moe_gemm", interpret: bool,
                  vmem_limit_bytes: int = TPU_V5E.vmem_capacity) -> jax.Array:
    """Ragged grouped (MoE expert) matmul: row ``i`` of ``x`` (M, K), sorted
    by group, times the weight of its group; ``group_sizes`` (E,) counts
    each group's rows, and rows past their sum belong to none (their output
    rows are zero or never written).  ``w`` is (E, K, N), or a stack
    (L, E, K, N) read in place at ``layer``.  Shape-changing (GLU)
    epilogues emit N//2 columns.

    The grid runs N tiles outermost, then the visits of
    :func:`ragged_visits`, then K: a tile's visits are consecutive, so its
    output block stays in VMEM between them.  The visit table, group rows
    and layer are scalar-prefetched; the weight's index map picks the
    visit's group.  Grid steps past the real visits compute nothing and
    keep the last real visit's blocks, so they fetch nothing either.  The
    schedule's M, N and K tiles are honoured; its order is not (the visits
    must run inside the N loop) and the accumulator is always the f32
    scratch."""
    m, k = x.shape
    stack = w[None] if w.ndim == 3 else w
    n = stack.shape[3]
    bm, bn, bk = min(cs.t["M"], m), min(cs.t["N"], n), min(cs.t["K"], k)
    if k % bk:
        raise ValueError(f"K tile {bk} does not divide K={k}")
    shape_changing = class_id in SHAPE_CHANGING
    chunk = glu_chunk(n // 2)
    if shape_changing and bn != n and bn % (2 * chunk):
        raise ValueError(f"GLU epilogue needs whole (gate, up) pairs of "
                         f"{2 * chunk} columns per N tile, got {bn}")
    bn_out = bn // 2 if shape_changing else bn
    k_trips = k // bk
    groups, tiles, visits, starts, ends = ragged_visits(group_sizes, m, bm)

    def live(v, kk, visits_ref):
        # a step past the real visits keeps the last real step's blocks
        return jnp.where(v < visits_ref[0], kk, k_trips - 1)

    def x_map(nn, v, kk, layer_ref, groups_ref, tiles_ref, visits_ref, *_):
        return tiles_ref[v], live(v, kk, visits_ref)

    def w_map(nn, v, kk, layer_ref, groups_ref, tiles_ref, visits_ref, *_):
        return layer_ref[0], groups_ref[v], live(v, kk, visits_ref), nn

    def o_map(nn, v, kk, layer_ref, groups_ref, tiles_ref, *_):
        return tiles_ref[v], nn

    kernel = functools.partial(_ragged_kernel, class_id=class_id, bm=bm,
                               k_trips=k_trips, chunk=chunk, out_dtype=x.dtype)
    call = pl.pallas_call(
        kernel,
        name=class_id,     # the op's name in the HLO and the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(pl.cdiv(n, bn), groups.shape[0], k_trips),
            in_specs=[pl.BlockSpec((bm, bk), x_map),
                      pl.BlockSpec((None, None, bk, bn), w_map)],
            out_specs=pl.BlockSpec((bm, bn_out), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n // 2 if shape_changing else n),
                                       x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )
    return call(_layer_operand(layer), groups, tiles, visits, starts, ends, x, stack)


def grouped_matmul(x: jax.Array, w: jax.Array, cs: ConcreteSchedule, *,
                   class_id: str = "moe_gemm", interpret: bool,
                   vmem_limit_bytes: int = TPU_V5E.vmem_capacity) -> jax.Array:
    """Grouped (MoE expert) matmul: x (E,M,K) @ w (E,K,N) -> (E,M,out)."""
    e, m, k = x.shape
    n = w.shape[2]
    call = build_call(
        m, n, k, cs, class_id=class_id, groups=e, out_dtype=x.dtype,
        interpret=interpret, vmem_limit_bytes=vmem_limit_bytes,
    )
    return call(_layer_operand(0), x, w)
