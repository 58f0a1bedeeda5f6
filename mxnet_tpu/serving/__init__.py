"""Low-latency serving tier: AOT bucketed decode, continuous batching,
KV-cache, hot model reload.  See docs/serving.md."""

from .batcher import (ContinuousBatcher, DeadlineExceeded,
                      ServerOverloaded, max_delay_ms_from_env,
                      max_queue_from_env)
from .engine import (ServingEngine, batch_buckets_from_env, compile_count,
                     dispatch_count, prefill_buckets_for, reset_counters,
                     state_for_serving, trace_count, whole_layer_ops)
from .replica import FleetWatcher, FrontDoor, ReplicaServer

__all__ = [
    "ServingEngine", "ContinuousBatcher", "ReplicaServer", "FrontDoor",
    "FleetWatcher", "ServerOverloaded", "DeadlineExceeded",
    "state_for_serving", "batch_buckets_from_env", "prefill_buckets_for",
    "max_delay_ms_from_env", "max_queue_from_env", "trace_count",
    "compile_count", "dispatch_count", "reset_counters", "whole_layer_ops",
]
