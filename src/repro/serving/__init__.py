from repro.serving.cluster import ClusterFrontend, PrefixDirectory
from repro.serving.engine import (ComputeBackend, EngineConfig, MemoryPlane,
                                  PrefillChunk, ServeEngine, StepPlan,
                                  StepReport, choose_hot_tier,
                                  latency_percentiles)
from repro.serving.directory import DirectoryShard, ShardedDirectory
from repro.serving.events import (Event, EventKind, EventQueue, EventTrace,
                                  NonQuiescentError)
from repro.serving.fabric import Fabric
from repro.serving.fleet_sim import (FleetConfig, FleetRequest, FleetSim,
                                     latency_slo)
from repro.serving.kv_cache import PagedKVManager, PressureStats, RadixStats
from repro.serving.radix import PrefixMatch, RadixKVIndex, RadixNode
from repro.serving.retention_lifecycle import LifecycleStats, RetentionLifecycle
from repro.serving.scheduler import ContinuousBatchScheduler, Request

__all__ = ["EngineConfig", "ServeEngine", "ComputeBackend", "MemoryPlane",
           "StepPlan", "StepReport", "PrefillChunk", "PagedKVManager",
           "PressureStats", "RadixStats", "LifecycleStats",
           "RetentionLifecycle", "ContinuousBatchScheduler",
           "Request", "ClusterFrontend", "PrefixDirectory", "RadixKVIndex",
           "RadixNode", "PrefixMatch", "choose_hot_tier",
           "latency_percentiles", "Event", "EventKind", "EventQueue",
           "EventTrace", "NonQuiescentError", "FleetConfig", "FleetRequest",
           "FleetSim", "latency_slo", "Fabric", "ShardedDirectory",
           "DirectoryShard"]
