"""FISH core: the paper's contribution (Algs. 1-3, CHK, consistent hashing,
baseline groupings, DSPE simulator)."""

from .assignment import WorkerStateEstimator, select_min_wait
from .baselines import (
    DChoices,
    FieldGrouping,
    FishGrouper,
    Grouper,
    PartialKeyGrouping,
    ShuffleGrouping,
    WChoices,
)
from .chash import ConsistentHashRing, hash32
from .fish import EpochFrequencyTracker, FishParams, chk_num_workers
from .stream import (
    CapacityEvent,
    EdgeResult,
    EdgeState,
    MembershipEvent,
    StreamMetrics,
    at_time,
    edge_metrics,
    simulate_edge,
)

__all__ = [
    "WorkerStateEstimator",
    "select_min_wait",
    "DChoices",
    "FieldGrouping",
    "FishGrouper",
    "Grouper",
    "PartialKeyGrouping",
    "ShuffleGrouping",
    "WChoices",
    "ConsistentHashRing",
    "hash32",
    "EpochFrequencyTracker",
    "FishParams",
    "chk_num_workers",
    "CapacityEvent",
    "EdgeResult",
    "EdgeState",
    "MembershipEvent",
    "StreamMetrics",
    "at_time",
    "edge_metrics",
    "simulate_edge",
]
