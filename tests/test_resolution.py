"""Resolution pipeline: stage order, per-tier accounting, generation-keyed
memoization (+ migration), and execution plans."""
import dataclasses
import json
import threading

import pytest

from repro.core.database import Record
from repro.core.resolution import (
    DefaultStage,
    ResolutionPipeline,
    ServiceStage,
    StaticMapStage,
    plan_model,
    plan_uses,
)
from repro.core.runner import AnalyticalRunner, CachedRunner
from repro.core.schedule import Schedule, default_schedule
from repro.core.workload import KernelInstance, KernelUse
from repro.kernels.ops import ScheduleProvider
from repro.service import ScheduleRegistry, TuningService


def make_instance(m=64, n=256, k=256, dtype="float32"):
    return KernelInstance.make("matmul", M=m, N=n, K=k, dtype=dtype)


def make_schedule(tm=32, tn=128, tk=128, **kw):
    return Schedule.make("matmul", tiles={"M": tm, "N": tn, "K": tk}, **kw)


def make_service(tmp_path, name="svc", **kw):
    registry = ScheduleRegistry(str(tmp_path / name))
    kw.setdefault("runner", CachedRunner(AnalyticalRunner()))
    kw.setdefault("max_workers", 0)
    kw.setdefault("probe_candidates", 0)
    return registry, TuningService(registry, model_id="serving", **kw)


def publish(registry, inst, sched, seconds=1e-6, model_id="donor",
            target="tpu-v5e", mode="strict"):
    registry.publish([Record(instance=inst, schedule=sched, seconds=seconds,
                             model_id=model_id, target=target)], mode=mode)


# ---------------------------------------------------------------------------
# Stage order + per-tier accounting
# ---------------------------------------------------------------------------


def test_stage_order_service_beats_static_beats_default(tmp_path):
    inst = make_instance()
    svc_sched = make_schedule(32, 128, 128)
    static_sched = make_schedule(16, 256, 256)
    registry, service = make_service(tmp_path)
    publish(registry, inst, svc_sched)

    pipe = ResolutionPipeline.build(
        schedule_map={inst.workload_key(): static_sched}, service=service)
    res = pipe.resolve(inst)
    assert res.tier == "exact" and res.schedule == svc_sched

    pipe_static = ResolutionPipeline.build(
        schedule_map={inst.workload_key(): static_sched})
    res = pipe_static.resolve(inst)
    assert res.tier == "static" and res.schedule == static_sched

    pipe_empty = ResolutionPipeline.build()
    res = pipe_empty.resolve(inst)
    assert res.tier == "default"
    assert res.schedule == default_schedule(inst)


def test_default_tier_service_answer_is_not_a_hit(tmp_path):
    """A service lookup answering the untuned-default tier falls through and
    is counted as a default resolution, never exact/transfer (the old
    provider's hit/miss pair conflated this)."""
    inst = make_instance()
    _, service = make_service(tmp_path)  # empty registry: every lookup misses
    provider = ScheduleProvider(service=service)
    provider.get(inst)
    stats = provider.stats()
    assert stats["served_exact"] == 0
    assert stats["served_transfer"] == 0
    assert stats["served_default"] == 1
    assert provider.hits == 0 and provider.misses == 1


def test_per_tier_counts_reported(tmp_path):
    inst_hit, inst_miss = make_instance(64), make_instance(128)
    registry, service = make_service(tmp_path)
    publish(registry, inst_hit, make_schedule())
    pipe = ResolutionPipeline.build(service=service)
    pipe.resolve(inst_hit)
    pipe.resolve(inst_miss)
    stats = pipe.stats()
    assert stats["served_exact"] == 1
    assert stats["served_default"] == 1
    assert stats["resolves"] == 2


# ---------------------------------------------------------------------------
# Memo cache: steady state, invalidation, migration
# ---------------------------------------------------------------------------


def test_steady_state_is_one_dict_hit(tmp_path):
    inst = make_instance()
    registry, service = make_service(tmp_path)
    publish(registry, inst, make_schedule())
    pipe = ResolutionPipeline.build(service=service)
    first = pipe.resolve(inst)
    for _ in range(5):
        assert pipe.resolve(inst) is first
    stats = pipe.stats()
    assert stats["cache_misses"] == 1 and stats["cache_hits"] == 5
    # the service was consulted exactly once — repeats never touch its lock
    assert service.stats()["lookups"] == 1


def test_generation_bump_invalidates_and_upgrades(tmp_path):
    inst = make_instance()
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    assert pipe.resolve(inst).tier == "default"

    better = make_schedule()
    publish(registry, inst, better)  # external writer: generation bump
    res = pipe.resolve(inst)
    assert res.tier == "exact" and res.schedule == better
    assert res.generation == pipe.generation()


def test_changed_since_migrates_unchanged_entries(tmp_path):
    inst_a, inst_b = make_instance(64), make_instance(128)
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    pipe.resolve(inst_a)
    pipe.resolve(inst_b)

    # Publish through the service: the pipeline can attribute the bump.
    sched = make_schedule(64, 256, 256)
    service._publish(inst_a, sched,
                     service.runner.seconds(inst_a, sched), "donor")
    assert pipe.resolve(inst_a).tier == "exact"
    stats = pipe.stats()
    assert stats["migrated"] >= 1          # inst_b carried across generations
    assert stats["invalidations"] == 0     # no full clear
    # migrated entry still serves without re-walking stages
    lookups_before = service.stats()["lookups"]
    assert pipe.resolve(inst_b).tier == "default"
    assert service.stats()["lookups"] == lookups_before


def test_two_generation_bearing_stages_attribute_independently(tmp_path):
    """Each stage's changed_since is asked against its OWN last generation:
    with two service stages, a publish through either invalidates exactly
    that workload (summed generations would misattribute the bump)."""
    inst = make_instance()
    _, svc_a = make_service(tmp_path, "a")
    _, svc_b = make_service(tmp_path, "b")
    pipe = ResolutionPipeline([ServiceStage(svc_a), ServiceStage(svc_b),
                               DefaultStage()])
    assert pipe.resolve(inst).tier == "default"

    sched = make_schedule()
    svc_b._publish(inst, sched, svc_b.runner.seconds(inst, sched), "donor")
    res = pipe.resolve(inst)
    assert res.tier == "exact" and res.schedule == sched
    assert pipe.stats()["invalidations"] == 0  # attributed, not cleared


def test_external_publish_clears_cache_conservatively(tmp_path):
    inst_a, inst_b = make_instance(64), make_instance(128)
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    pipe.resolve(inst_a)
    pipe.resolve(inst_b)
    publish(registry, inst_a, make_schedule())  # bypasses the service
    pipe.resolve(inst_b)
    stats = pipe.stats()
    assert stats["invalidations"] == 1 and stats["migrated"] == 0


# ---------------------------------------------------------------------------
# Cache-key dimensions: mode / target / generation
# ---------------------------------------------------------------------------


def test_cache_key_mode_dimension():
    inst = make_instance(64, 256, 768)
    # 512 does not divide 768 on the reduction axis: strict-invalid, adaptive
    # snaps it to 384 (still lane-aligned, so legal on the chip)
    sched = make_schedule(32, 128, 512)
    pipe = ResolutionPipeline.build(
        schedule_map={inst.workload_key(): sched})
    assert pipe.resolve(inst, mode="strict").tier == "default"
    assert pipe.resolve(inst, mode="adaptive").tier == "static"
    keys = set(pipe._cache)
    assert (inst.workload_key(), "strict", pipe.target, 0) in keys
    assert (inst.workload_key(), "adaptive", pipe.target, 0) in keys


def test_cache_key_target_dimension(tmp_path):
    inst = make_instance()
    registry = ScheduleRegistry(str(tmp_path / "reg"))
    publish(registry, inst, make_schedule(), target="tpu-v5e")
    runner_kw = dict(max_workers=0, probe_candidates=0)
    svc_server = TuningService(registry, target="tpu-v5e", **runner_kw)
    svc_edge = TuningService(registry, target="tpu-v5e-lite", **runner_kw)
    pipe_server = ResolutionPipeline.build(service=svc_server)
    pipe_edge = ResolutionPipeline.build(service=svc_edge)
    assert pipe_server.target == "tpu-v5e"
    assert pipe_edge.target == "tpu-v5e-lite"
    # a record tuned for the server chip never serves the edge namespace
    assert pipe_server.resolve(inst).tier == "exact"
    assert pipe_edge.resolve(inst).tier == "default"
    assert next(iter(pipe_edge._cache))[2] == "tpu-v5e-lite"


def test_cache_key_generation_dimension(tmp_path):
    inst = make_instance()
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    pipe.resolve(inst)
    g0 = pipe.generation()
    publish(registry, inst, make_schedule())
    pipe.resolve(inst)
    g1 = pipe.generation()
    assert g1 > g0
    assert all(key[3] == g1 for key in pipe._cache)  # stale keys pruned


# ---------------------------------------------------------------------------
# Thread safety
# ---------------------------------------------------------------------------


def test_concurrent_resolution_accounting(tmp_path):
    instances = [make_instance(64 * (i + 1)) for i in range(4)]
    registry, service = make_service(tmp_path)
    publish(registry, instances[0], make_schedule())
    pipe = ResolutionPipeline.build(service=service)
    errors = []

    def worker():
        try:
            for _ in range(50):
                for inst in instances:
                    pipe.resolve(inst)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = pipe.stats()
    assert stats["resolves"] == 8 * 50 * len(instances)
    assert sum(stats[f"served_{t}"] for t in
               ("exact", "transfer", "static", "default")) == stats["resolves"]


# ---------------------------------------------------------------------------
# Execution plans
# ---------------------------------------------------------------------------


def test_plan_model_covers_and_matches_pipeline(tmp_path):
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    plan = plan_model("minitron-4b", pipe, "train_4k", dp=16, tp=16)
    assert len(plan) == len(plan.uses) > 0
    assert sum(plan.tier_counts().values()) == len(plan)
    for u, res in plan.items():
        direct = pipe.resolve(u.instance)
        assert (json.dumps(res.schedule.to_json(), sort_keys=True)
                == json.dumps(direct.schedule.to_json(), sort_keys=True))
    assert plan.generation == pipe.generation()


def test_plan_refresh_picks_up_upgrade_and_keeps_old_plan_frozen(tmp_path):
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    uses = [KernelUse(make_instance())]
    plan = plan_uses(uses, pipe)
    inst = uses[0].instance
    assert plan.lookup(inst).tier == "default"

    better = make_schedule()
    publish(registry, inst, better)
    plan2 = plan.refresh(pipe)
    assert plan.lookup(inst).tier == "default"      # old plan untouched
    assert plan2.lookup(inst).tier == "exact"
    assert plan2.lookup(inst).schedule == better
    assert plan2.generation > plan.generation


def test_provider_consults_plan_before_pipeline(tmp_path):
    registry, service = make_service(tmp_path)
    pipe = ResolutionPipeline.build(service=service)
    inst = make_instance()
    plan = plan_uses([KernelUse(inst)], pipe)
    provider = ScheduleProvider(pipeline=pipe, plan=plan)
    lookups = service.stats()["lookups"]
    cs = provider.get(inst)
    assert provider.plan_hits == 1
    assert service.stats()["lookups"] == lookups    # plan hit: no service call
    assert cs.schedule == plan.lookup(inst).schedule
    # a default-tier plan answer is an untuned kernel, not a hit (misses
    # count the planning-time pipeline resolve plus the plan-served call)
    assert provider.hits == 0 and provider.misses == 2
    # unplanned instance falls back to the pipeline (and the gap is counted)
    other = make_instance(256)
    provider.get(other)
    assert provider.plan_hits == 1
    assert provider.stats()["plan_misses"] == 1
    assert provider.stats()["served_default"] >= 1

    # after an upgrade, an exact-tier plan answer does count as a hit
    publish(registry, inst, make_schedule())
    provider.plan = plan.refresh(pipe)
    provider.get(inst)
    assert provider.stats()["plan_served"]["exact"] == 1
    assert provider.hits == 2  # the re-planning resolve + the plan-served call


def test_in_place_weights_keep_the_serving_plan_keys():
    """Reading each layer's weights in place from the stacks keys schedules
    on (class, M, N, K) alone, as slicing did, never on the layer: the
    traced decode step asks for plan_serving's decode matmuls, each a plan
    hit, and the plan's tiers are unchanged."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, reduced
    from repro.core.resolution import plan_serving
    from repro.kernels.ops import use_backend
    from repro.models import build_model

    cfg = reduced(get_arch("minitron-4b"))
    pipe = ResolutionPipeline.build()
    plan = plan_serving(cfg, pipe, slots=2, max_len=16, prefill_lengths=[8])
    assert plan.tier_counts() == {"exact": 0, "transfer": 0, "static": 0,
                                  "default": 10}
    asked = []

    class Recording(ScheduleProvider):
        def get(self, instance):
            asked.append(instance)
            return super().get(instance)

    provider = Recording(pipeline=pipe, plan=plan)
    model = build_model(cfg)
    with use_backend("pallas"):
        jax.make_jaxpr(lambda p, c, t: model.decode_step(p, c, t, provider=provider))(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            jax.eval_shape(lambda: model.init_cache(2, 16)),
            jnp.zeros((2,), jnp.int32))
    assert {(i.class_id, i.p["M"], i.p["N"], i.p["K"]) for i in asked} == {
        ("matmul", 2, 64, 64), ("matmul", 2, 64, 128),
        ("matmul_bias_gelu", 2, 128, 64), ("matmul_lmhead", 2, 512, 64)}
    assert all(set(i.p) == {"M", "N", "K"} for i in asked)
    assert ({i.workload_key() for i in asked}
            <= {u.instance.workload_key() for u in plan.uses})
    assert provider.stats()["plan_misses"] == 0


# ---------------------------------------------------------------------------
# Service generation / changed-workload notification
# ---------------------------------------------------------------------------


def test_service_generation_and_changed_since(tmp_path):
    inst = make_instance()
    registry, service = make_service(tmp_path)
    g0 = service.generation()
    assert service.changed_since(g0) == set()

    sched = make_schedule()
    service._publish(inst, sched, service.runner.seconds(inst, sched), "donor")
    g1 = service.generation()
    assert g1 > g0
    assert service.changed_since(g0) == {inst.workload_key()}
    assert service.changed_since(g1) == set()

    publish(registry, make_instance(128), make_schedule())  # external writer
    assert service.changed_since(g0) is None
    assert service.changed_since(g1) is None
