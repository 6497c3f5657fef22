from repro.runtime.autoscale import (
    AutoscaleConfig,
    Autoscaler,
    ClusterAutoscaleConfig,
    ClusterAutoscaler,
)
from repro.runtime.billing import BillingConfig, BillingMeter, CostBreakdown
from repro.runtime.cluster import (
    Cluster,
    ClusterConfig,
    ClusterReport,
    ClusterResult,
    DagRun,
    DagSpec,
    Job,
    StageResult,
    StageSpec,
)
from repro.runtime.loadgen import (
    LoadSpec,
    TraceJob,
    TraceWorkload,
    generate,
)
from repro.runtime.placement import (
    DRFSorter,
    PlacementConfig,
    ResourceVector,
    choose_class,
    spec_resource_vector,
    spec_worker_demand,
)
from repro.runtime.pool import LambdaPool, PoolConfig, SimWorker
from repro.runtime.provider import (
    DEFAULT_CLASSES,
    ClassedProvider,
    InstanceClass,
    Provider,
    ProviderConfig,
    WarmContainer,
)
from repro.runtime.reduce import TreeConfig, fanin_drain, tree_drain
from repro.runtime.scheduler import (
    RoundMetrics,
    Scheduler,
    SchedulerConfig,
)

__all__ = [
    "LambdaPool", "PoolConfig", "SimWorker",
    "Scheduler", "SchedulerConfig", "RoundMetrics",
    "TreeConfig", "fanin_drain", "tree_drain",
    "Provider", "ProviderConfig", "WarmContainer",
    "InstanceClass", "DEFAULT_CLASSES", "ClassedProvider",
    "ResourceVector", "spec_resource_vector", "spec_worker_demand",
    "DRFSorter", "PlacementConfig", "choose_class",
    "BillingConfig", "BillingMeter", "CostBreakdown",
    "AutoscaleConfig", "Autoscaler",
    "ClusterAutoscaleConfig", "ClusterAutoscaler",
    "Cluster", "ClusterConfig", "ClusterReport", "ClusterResult", "Job",
    "DagRun", "DagSpec", "StageResult", "StageSpec",
    "LoadSpec", "TraceJob", "TraceWorkload", "generate",
]
