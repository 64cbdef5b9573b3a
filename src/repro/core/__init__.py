"""Pipelined sharding — the paper's contribution as a composable module."""
from repro.core.costmodel import Placement, Plan, TimingEstimator  # noqa: F401
from repro.core.engine import SubLayerEngine  # noqa: F401
from repro.core.executor import ExecStats, PipelinedExecutor  # noqa: F401
from repro.core.faults import (  # noqa: F401
    DEGRADATION_RUNGS, AllocationFault, DemandTimeout, FaultError,
    FaultPlan, FaultSpec, RecoveryPolicy, TransferFault, WorkerCrash,
    WorkerLost)
from repro.core.graphing import (  # noqa: F401
    ShardDiv, build_graph, expert_weight_bytes, ffn_weight_bytes)
from repro.core.install import run_install  # noqa: F401
from repro.core.planner import (  # noqa: F401
    PINNED_COMPUTE_KINDS, TIERS, Schedule, ScheduleDiff, build_schedule,
    choose_spec_k, estimate_spec_tps, estimate_tps, estimate_ttft,
    plan_draft_carve)
from repro.core.prefetch import PrefetchEngine, PrefetchStats  # noqa: F401
from repro.core.specdec import SpecDecoder  # noqa: F401
from repro.core.profile_db import ProfileDB  # noqa: F401
from repro.core.sublayer import STREAMABLE_KINDS  # noqa: F401
from repro.core.system import (  # noqa: F401
    CLI1, CLI2, CLI3, SYSTEMS, TPU_V5E, InferenceSetting, SystemConfig,
    system_for_device_kind)
