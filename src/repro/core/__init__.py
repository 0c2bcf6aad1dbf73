"""Core contribution of the paper: pull-based (Join-Idle-Queue) scheduling.

The exported surface, grouped by layer (docs/ARCHITECTURE.md is the
end-to-end tour; each symbol's docstring states which contracts bind it):

* schedulers — ``Scheduler`` (callback protocol), ``HikuScheduler``
  (Algorithm 1), ``make_scheduler``/``available_schedulers`` (registry);
* engine — ``Simulator`` + ``SimConfig`` (the bit-exact event loop),
  ``FunctionSpec``/``make_functions``/``make_vu_programs`` (seeded
  Azure-like workloads);
* records/metrics — ``RequestRecord``/``RecordColumns``/
  ``RecordAccumulator`` (columnar store), ``RunMetrics``/``summarize``/
  ``summarize_window``/``summarize_windows``/``latency_cdf``/
  ``load_cv_per_second`` (§V metrics, vectorized);
* scale-out — ``ShardedSimulator``/``ShardSpec``/``ShardResult``/
  ``MergedRun``/``StreamChunk``/``shard_seed`` (static K-shard partition +
  batch/streaming merge), ``AdmissionSimulator``/``AdmissionConfig``/
  ``AdmissionRun`` (global pull-based admission tier),
  ``AdmissionPolicy``/``ShardState``/``register_policy``/
  ``unregister_policy``/``available_policies``/``make_policy`` (pluggable
  admission-policy registry; see docs/POLICIES.md for the author
  contract), ``Scenario``/``make_scenario``/``available_scenarios``
  (bursty workload suite), ``StolenTask``/``Migration``/``steal_tick``
  (cross-shard work stealing over the admission co-run);
* learned state — ``DurationEstimator``/``BanditTuner``
  (``core.estimators``: snapshot-exact online estimation feeding the
  ``sjf``/``bandit`` policies), ``ShardScript``/``scripts_from_run``/
  ``replay_shards`` (``core.replay``: scripted per-shard re-execution of
  a recorded admission run, byte-identical on all three backends);
* event plane / autoscaling — ``EventPlane``/``MetricEvent``
  (``core.eventplane``: deterministic in-process pub/sub over windowed
  metric summaries), ``Autoscaler``/``AutoscaleConfig``/
  ``AutoscaleActuator`` (``core.autoscale``: reactive/predictive pool
  sizing on the bus, scale-down via notice windows, scale-to-zero
  janitor; docs/ARCHITECTURE.md §14 is the contract);
* chaos — ``FaultEvent``/``FaultPlan`` (declarative seeded fault
  schedules) with the ``shard_kill_wave``/``spot_preemption``/
  ``rolling_restart``/``flappy_workers`` generators, plus
  ``SalvagedVU``/``Salvage``/``drain_tick`` (dead-shard drain with
  exactly-once recovery; docs/ARCHITECTURE.md §10 is the contract);
* JAX form — ``JIQState``/``init_state``/``sched_step``/``sched_many``/
  ``sched_many_fused`` + the ``ARRIVAL``/``FINISH``/``EVICT`` event kinds
  (vectorized Algorithm 1, Pallas-fused on TPU).
"""

from . import baselines as _baselines  # noqa: F401  (registers schedulers)
from . import hiku as _hiku  # noqa: F401
from .admission import (
    AdmissionConfig,
    AdmissionRun,
    AdmissionShard,
    AdmissionSimulator,
)
from .autoscale import AutoscaleActuator, AutoscaleConfig, Autoscaler
from .chaos import (
    FaultEvent,
    FaultPlan,
    flappy_workers,
    rolling_restart,
    shard_kill_wave,
    spot_preemption,
)
from .estimators import BanditTuner, DurationEstimator
from .eventplane import EventPlane, MetricEvent
from .hiku import HikuScheduler
from .jax_sched import (
    ARRIVAL,
    EVICT,
    FINISH,
    JIQState,
    init_state,
    sched_many,
    sched_many_fused,
    sched_step,
)
from .metrics import (
    RunMetrics,
    latency_cdf,
    load_cv_per_second,
    summarize,
    summarize_window,
    summarize_windows,
)
from .policies import (
    AdmissionPolicy,
    ShardState,
    available_policies,
    make_policy,
    register_policy,
    unregister_policy,
)
from .records import RecordAccumulator, RecordColumns, RequestRecord
from .replay import ShardScript, replay_shards, scripts_from_run
from .scheduler import Scheduler, available_schedulers, make_scheduler
from .shard import (
    MergedRun,
    ShardedSimulator,
    ShardResult,
    ShardSpec,
    StreamChunk,
    shard_seed,
)
from .simulator import SalvagedVU, SimConfig, Simulator, StolenTask
from .stealing import Migration, Salvage, drain_tick, steal_tick
from .trace import FunctionSpec, default_n_events, make_functions, make_vu_programs
from .workloads import Scenario, available_scenarios, make_scenario

__all__ = [
    "ARRIVAL",
    "AdmissionConfig",
    "AdmissionPolicy",
    "AdmissionRun",
    "AdmissionShard",
    "AdmissionSimulator",
    "AutoscaleActuator",
    "AutoscaleConfig",
    "Autoscaler",
    "BanditTuner",
    "DurationEstimator",
    "EVICT",
    "EventPlane",
    "FINISH",
    "FaultEvent",
    "FaultPlan",
    "FunctionSpec",
    "HikuScheduler",
    "JIQState",
    "MergedRun",
    "MetricEvent",
    "Migration",
    "RecordAccumulator",
    "RecordColumns",
    "RequestRecord",
    "RunMetrics",
    "Salvage",
    "SalvagedVU",
    "Scenario",
    "Scheduler",
    "ShardResult",
    "ShardScript",
    "ShardSpec",
    "ShardState",
    "ShardedSimulator",
    "SimConfig",
    "Simulator",
    "StolenTask",
    "StreamChunk",
    "available_policies",
    "available_scenarios",
    "available_schedulers",
    "init_state",
    "latency_cdf",
    "load_cv_per_second",
    "default_n_events",
    "drain_tick",
    "flappy_workers",
    "make_functions",
    "make_policy",
    "make_scenario",
    "make_scheduler",
    "make_vu_programs",
    "register_policy",
    "replay_shards",
    "rolling_restart",
    "sched_many",
    "sched_many_fused",
    "sched_step",
    "scripts_from_run",
    "shard_kill_wave",
    "shard_seed",
    "spot_preemption",
    "steal_tick",
    "summarize",
    "summarize_window",
    "summarize_windows",
    "unregister_policy",
]
