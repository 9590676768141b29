import os
# Host-only: 512 virtual CPU devices, never a chip a parent process may hold.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse        # noqa: E402
import json            # noqa: E402
import math            # noqa: E402
import re              # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402

from repro.configs.base import all_cells, get_arch, get_shape, shape_applicable  # noqa: E402
from repro.distributed import sharding as shd                                    # noqa: E402
from repro.distributed.context import activation_sharding, set_remat_policy, set_sharding_rules  # noqa: E402
from repro.hw.specs import TPU_V5E                                               # noqa: E402
from repro.launch import steps as steps_mod                                      # noqa: E402
from repro.launch.mesh import make_production_mesh                               # noqa: E402
from repro.models.build import build_model                                       # noqa: E402
from repro.optim.adamw import AdamWConfig                                        # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware:
  * ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed on
    the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh;
  * ``compiled.memory_analysis()`` proves the per-device footprint fits;
  * ``compiled.cost_analysis()`` + the post-SPMD HLO collective scan feed
    the roofline table (EXPERIMENTS.md §Roofline).

Artifacts are cached as JSON under benchmarks/results/dryrun/ so the sweep
is resumable and the roofline benchmark is a pure read.
"""

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[^\]]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> dict:
    """Per-device collective operand bytes from post-SPMD HLO text."""
    stats: dict[str, dict] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_bytes = _shape_bytes(m.group(1))
        op = m.group(2)
        gl = _GROUPS_LIST_RE.search(line)
        gi = _GROUPS_IOTA_RE.search(line)
        if gl:
            gsize = len(gl.group(1).split(","))
        elif gi:
            gsize = int(gi.group(2))
        else:
            gsize = 1
        if op == "all-gather":
            operand = result_bytes // max(gsize, 1)
        elif op == "reduce-scatter":
            operand = result_bytes * max(gsize, 1)
        else:
            operand = result_bytes
        s = stats.setdefault(op, {"count": 0, "operand_bytes": 0, "result_bytes": 0})
        s["count"] += 1
        s["operand_bytes"] += operand
        s["result_bytes"] += result_bytes
    stats["total_operand_bytes"] = sum(
        v["operand_bytes"] for k, v in stats.items() if isinstance(v, dict)
    )
    return stats


def _sharded_bytes(abstract_tree, shardings_tree, mesh) -> int:
    """Analytic per-device bytes of a sharded pytree."""
    total = 0
    flat = jax.tree_util.tree_leaves(abstract_tree)
    shards = jax.tree_util.tree_leaves(
        shardings_tree, is_leaf=lambda x: hasattr(x, "spec"))
    for leaf, sh in zip(flat, shards):
        n_shards = 1
        for axes in sh.spec:
            if axes is None:
                continue
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                n_shards *= mesh.shape[a]
        total += math.ceil(leaf.size / n_shards) * leaf.dtype.itemsize
    return total


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, *,
             remat_policy_name: str = "full", grad_accum: int = 1,
             seq_parallel: bool = False) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    model = build_model(cfg)
    abstract_params = model.abstract_params()
    dp_only = shd.dp_dominant(cfg, mesh, kind=shape.kind,
                              global_batch=shape.global_batch)
    p_shard = shd.param_shardings(abstract_params, cfg, mesh, dp_only)
    specs = model.input_specs(shape)
    b_shard = shd.batch_shardings(specs, cfg, mesh, dp_only)
    act_shard = shd.activation_sharding(mesh, cfg, dp_only,
                                        seq_parallel and shape.kind == "prefill")

    t0 = time.monotonic()
    set_sharding_rules(shd.internal_sharding_rules(mesh, cfg))
    set_remat_policy(remat_policy_name)
    with activation_sharding(act_shard):
        if shape.kind == "train":
            opt = jax.eval_shape(steps_mod.init_opt_state, abstract_params)
            o_shard = shd.opt_state_shardings(p_shard, mesh)
            step = steps_mod.make_train_step(model, AdamWConfig(), grad_accum=grad_accum)
            jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(abstract_params, opt, specs)
        elif shape.kind == "prefill":
            step = steps_mod.make_prefill_step(model, max_len=shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(abstract_params, specs)
        else:  # decode
            step = steps_mod.make_decode_step(model)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, b_shard["cache"], b_shard["tokens"]),
                             out_shardings=(None, b_shard["cache"]),
                             donate_argnums=(1,))
            lowered = jitted.lower(abstract_params, specs["cache"], specs["tokens"])
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
    set_sharding_rules(None)
    set_remat_policy(None)

    cost = dict(compiled.cost_analysis() or {})
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU client may not implement it
        mem_d = {"error": str(e)}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    param_bytes = _sharded_bytes(abstract_params, p_shard, mesh)
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))

    # roofline terms (per assignment formulas; cost_analysis is per-device
    # post-SPMD, so the chips factor is already applied)
    compute_s = flops / TPU_V5E.peak_flops_bf16
    memory_s = hbm_bytes / TPU_V5E.hbm_bandwidth
    collective_s = coll["total_operand_bytes"] / TPU_V5E.ici_bandwidth

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    # XLA-CPU lowers dots to oneDNN custom-calls whose flops cost_analysis
    # does not count; the analytic term (8·N·D train with full remat
    # recompute, 2·N·D inference) is the TPU-faithful compute bound.
    train_factor = 6 if remat_policy_name == "dots" else 8  # dots: no fwd recompute
    analytic_flops = (train_factor if shape.kind == "train" else 2) * n_active * tokens
    compute_analytic_s = analytic_flops / (chips * TPU_V5E.peak_flops_bf16)

    return {
        "status": "ok",
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "strategy": "dp_only" if dp_only else "fsdp+tp",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_analysis": {k: cost[k] for k in sorted(cost) if isinstance(cost[k], (int, float))},
        "memory_analysis": mem_d,
        "collectives": coll,
        "param_bytes_per_device": param_bytes,
        "roofline": {
            "compute_s": max(compute_s, compute_analytic_s),
            "compute_hlo_s": compute_s,
            "compute_analytic_s": compute_analytic_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max(
                [("compute", max(compute_s, compute_analytic_s)),
                 ("memory", memory_s), ("collective", collective_s)],
                key=lambda kv: kv[1],
            )[0],
            "model_flops_total": model_flops,
            "hlo_flops_per_device": flops,
            "useful_flops_ratio": model_flops / max(
                max(flops, analytic_flops / chips) * chips, 1.0),
        },
    }


def cell_path(arch: str, shape: str, mesh: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh}.json")


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--remat-policy", choices=["full", "dots"], default="full")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true",
                    help="prefill context parallelism experiment (§Perf it-8)")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = [(a, s) for a, s, _ok, _w in all_cells()
             if (args.arch is None or a == args.arch)
             and (args.shape is None or s == args.shape)]

    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        for multi in meshes:
            mesh_name = "2x16x16" if multi else "16x16"
            path = cell_path(arch, shape, mesh_name)
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    prev = json.load(f)
                print(f"[cached] {arch} {shape} {mesh_name}: {prev['status']}")
                n_ok += prev["status"] == "ok"
                n_skip += prev["status"] == "skipped"
                n_fail += prev["status"] == "failed"
                continue
            print(f"[run] {arch} {shape} {mesh_name} ...", flush=True)
            try:
                res = run_cell(arch, shape, multi,
                               remat_policy_name=args.remat_policy,
                               grad_accum=args.grad_accum,
                               seq_parallel=args.seq_parallel)
            except Exception as e:
                res = {"status": "failed", "arch": arch, "shape": shape,
                       "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            if res["status"] == "ok":
                n_ok += 1
                r = res["roofline"]
                print(f"  ok: compile={res['compile_s']}s "
                      f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
                      f"coll={r['collective_s']*1e3:.2f}ms dom={r['dominant']} "
                      f"params/dev={res['param_bytes_per_device']/2**30:.2f}GiB", flush=True)
            elif res["status"] == "skipped":
                n_skip += 1
                print(f"  skipped: {res['reason']}")
            else:
                n_fail += 1
                print(f"  FAILED: {res['error']}")
    print(f"\ndry-run summary: ok={n_ok} skipped={n_skip} failed={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
