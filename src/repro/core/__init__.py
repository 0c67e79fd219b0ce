"""Paper contribution: dynamic, hierarchical graph-based resource model."""
from .graph import CONTAINMENT, ResourceGraph, Vertex, build_cluster, build_tpu_fleet
from .jobspec import Jobspec, ResourceReq
from .match import DictMatcher, Matcher
from .flatgraph import FlatGraph, FlatMatcher, flat_for
from .actor import ActorGroup, QueueActor, check_actor_safe
from .transform import (TransformKind, TransformResult, add_subgraph,
                        remove_subgraph, update_metadata)
from .engine import Allocation, GrowEngine, GrowResult, MGTiming
from .scheduler import (Hierarchy, SchedulerInstance, TreeSpec, build_chain,
                        build_tree)
from .queue import (Clock, Job, JobQueue, JobState, QueueStats, SimClock,
                    WallClock)
from .policy import (POLICIES, ConservativeBackfill, EasyBackfill, FCFS,
                     FirstFit, PreemptivePriority, PriorityFCFS,
                     SchedulingPolicy, make_policy)
from .events import EventLog, EventType, JobEvent
from .metrics import (MetricsAggregator, QuantileSketch, SpanCollector,
                      fragmentation)
from .api import (Instance, JobHandle, RemoteInstance, RemoteJobHandle,
                  RemoteSubscription)
from .tenancy import (FairShareArbiter, Lease, LeaseLedger, MultiTenantTree,
                      TenantSpec)
from .external import (AWS_ZONES, TABLE3_CATALOG, ExternalProvider,
                       InstanceType, ProvisionResult, SimulatedEC2Provider,
                       TPUSliceProvider, fleet_catalog)
from .rpc import (ClientReactor, MethodRegistry, MuxServer, MuxTransport,
                  ProtocolError, RPCError, RPCServer, SocketTransport)

__all__ = [
    "CONTAINMENT", "ResourceGraph", "Vertex", "build_cluster",
    "build_tpu_fleet", "Jobspec", "ResourceReq", "Matcher",
    "DictMatcher", "FlatGraph", "FlatMatcher", "flat_for",
    "ActorGroup", "QueueActor", "check_actor_safe", "TransformKind",
    "TransformResult", "add_subgraph", "remove_subgraph", "update_metadata",
    "Allocation", "GrowEngine", "GrowResult", "Hierarchy", "MGTiming",
    "SchedulerInstance", "TreeSpec", "build_chain", "build_tree",
    "Clock", "Job", "JobQueue", "JobState", "QueueStats", "SimClock",
    "WallClock", "MethodRegistry", "MuxServer", "MuxTransport",
    "ClientReactor", "ProtocolError", "RPCError", "RPCServer",
    "SocketTransport",
    "EventLog", "EventType", "JobEvent",
    "MetricsAggregator", "QuantileSketch", "SpanCollector", "fragmentation",
    "Lease", "LeaseLedger",
    "Instance", "JobHandle", "RemoteInstance", "RemoteJobHandle",
    "RemoteSubscription",
    "POLICIES", "ConservativeBackfill", "EasyBackfill", "FCFS",
    "FirstFit", "PreemptivePriority", "PriorityFCFS", "SchedulingPolicy",
    "make_policy", "FairShareArbiter", "MultiTenantTree", "TenantSpec",
    "AWS_ZONES", "TABLE3_CATALOG", "ExternalProvider", "InstanceType",
    "ProvisionResult", "SimulatedEC2Provider", "TPUSliceProvider",
    "fleet_catalog",
]
