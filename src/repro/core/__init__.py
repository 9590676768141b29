"""Transfer-tuning core: the paper's contribution as a composable library.

Public API:
    KernelInstance / KernelUse / kernel classes ............ workload.py
    Schedule / concretize / default_schedule ............... schedule.py
    measure / evaluate / model_seconds (v5e cost model) .... cost_model.py
    MeasureRunner / Analytical|Cached|PruningRunner ........ runner.py
    tune_kernel / tune_model (Ansor analogue) .............. autoscheduler.py
    ScheduleDB / Record (target-namespaced) ................ database.py
    transfer_tune / transfer_matrix / cross_target_transfer  transfer.py
    select_donor / top_donors (Eq. 1) ...................... heuristic.py
    extract_kernels (model config -> kernel workloads) ..... extract.py
    ResolutionPipeline / ExecutionPlan / plan_model ........ resolution.py
    Target / get_target / resolve_target ................... repro.targets
"""
from repro.core.autoscheduler import ModelTuneResult, TuneResult, tune_kernel, tune_model
from repro.core.cost_model import (
    CostBreakdown,
    Measurement,
    class_proportions,
    evaluate,
    kernel_seconds,
    measure,
    model_seconds,
)
from repro.core.database import Record, ScheduleDB
from repro.core.heuristic import DonorScore, donor_scores, select_donor, top_donors
from repro.core.resolution import (
    DefaultStage,
    ExecutionPlan,
    Resolution,
    ResolutionPipeline,
    ResolutionStage,
    ServiceStage,
    StaticMapStage,
    plan_model,
    plan_serving,
    plan_uses,
)
from repro.core.runner import (
    AnalyticalRunner,
    CachedRunner,
    MeasureRunner,
    PruningRunner,
    RunnerStats,
    default_runner,
)
from repro.core.schedule import ConcreteSchedule, Schedule, ScheduleInvalid, concretize, default_schedule
from repro.core.transfer import (
    KernelTransfer,
    TransferResult,
    cross_target_transfer,
    transfer_matrix,
    transfer_tune,
)
from repro.core.workload import KERNEL_CLASSES, KernelInstance, KernelUse, classes_in, dedup_uses
from repro.targets import DEFAULT_TARGET, Target, get_target, list_targets, resolve_target

__all__ = [
    "DEFAULT_TARGET",
    "KERNEL_CLASSES",
    "AnalyticalRunner",
    "CachedRunner",
    "ConcreteSchedule",
    "CostBreakdown",
    "DefaultStage",
    "DonorScore",
    "ExecutionPlan",
    "Target",
    "MeasureRunner",
    "PruningRunner",
    "Resolution",
    "ResolutionPipeline",
    "ResolutionStage",
    "RunnerStats",
    "ServiceStage",
    "StaticMapStage",
    "KernelInstance",
    "KernelTransfer",
    "KernelUse",
    "Measurement",
    "ModelTuneResult",
    "Record",
    "Schedule",
    "ScheduleDB",
    "ScheduleInvalid",
    "TransferResult",
    "TuneResult",
    "class_proportions",
    "classes_in",
    "concretize",
    "cross_target_transfer",
    "dedup_uses",
    "default_runner",
    "default_schedule",
    "donor_scores",
    "evaluate",
    "extract_kernels",
    "get_target",
    "kernel_seconds",
    "list_targets",
    "measure",
    "model_seconds",
    "plan_model",
    "plan_serving",
    "plan_uses",
    "resolve_target",
    "select_donor",
    "top_donors",
    "transfer_matrix",
    "transfer_tune",
    "tune_kernel",
    "tune_model",
]


def extract_kernels(*args, **kwargs):  # lazy import: configs depend on models
    from repro.core.extract import extract_kernels as _ek

    return _ek(*args, **kwargs)
