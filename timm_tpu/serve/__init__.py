"""Serving subsystem: continuous batching over AOT-warmed bucketed shapes.

See ``engine.InferenceEngine`` for the engine, ``drill`` for the CPU-runnable
load drill (``canonical_drill`` + ``summary_line``), and README "Serving" for usage.
"""
from .bucketing import (
    DEFAULT_BUCKETS, batch_bucket, pad_rows, select_bucket, strip_rows,
    validate_buckets,
)
from .drill import canonical_drill, run_load_drill, summary_line
from .engine import InferenceEngine, collect_cache_events
from .queueing import RequestQueue, ServeFuture, ServeRequest
from .residency import ModelPool, ResidentModel

__all__ = [
    'DEFAULT_BUCKETS', 'batch_bucket', 'pad_rows', 'select_bucket',
    'strip_rows', 'validate_buckets',
    'canonical_drill', 'run_load_drill', 'summary_line',
    'InferenceEngine', 'collect_cache_events',
    'RequestQueue', 'ServeFuture', 'ServeRequest',
    'ModelPool', 'ResidentModel',
]
