"""Fault-tolerance subsystem: durable checkpoints, non-finite step sentinel,
preemption-aware shutdown, reader retry policy, and a fault-injection harness.

See README "Fault tolerance" for the knobs:
  TIMM_TPU_NONFINITE_TOLERANCE / _GUARD, TIMM_TPU_POISON_BUDGET,
  TIMM_TPU_PREEMPTION_POLL, TIMM_TPU_FAULT_INJECT, train.py --resume auto /
  --fault-inject / --nonfinite-rollback.

The sentinel's host read lags the dispatch by one step (sentinel.py): a
NonFiniteError arrives one `train_step` call after the step that trips it, or
from `TrainingTask.drain()`, which the loop calls before anything is saved or
evaluated.
"""
from .durable import (
    SCHEMA_VERSION, CorruptCheckpointError, atomic_copy, atomic_write_bytes,
    atomic_write_json, atomic_write_npz, checkpoint_progress_key, copy_sharded_checkpoint,
    find_checkpoints, is_sharded_manifest, load_verified, load_with_fallback, manifest_path,
    read_checkpoint_scalar, read_manifest, remove_checkpoint_files, resolve_auto_resume,
    set_durable_write_listener, shard_file_path, snapshot_process_shards, snapshot_to_host,
    sweep_orphan_shards, verify_checkpoint, write_sharded_checkpoint,
)
from .elastic import (
    AsyncCheckpointWriter, ElasticPlan, convert_loader_position,
    plan_elastic_resume, rescale_for_devices,
)
from .faultinject import FaultInjector, fault_selftest, get_fault_injector, set_fault_injector
from .hoststate import RESUME_PREFIX, capture_host_rng, restore_host_rng
from .multihost import cluster_env, free_port, run_kill_drill
from .preemption import GracefulShutdown, TrainingPreempted
from .retry import (
    DEFAULT_POISON_BUDGET, SkipBudget, TooManyBadSamples, backoff_delays, retry_io,
)
from .sentinel import (
    NonFiniteError, NonFiniteSentinel, guard_enabled, new_sentinel_state,
    tree_all_finite, update_sentinel_state,
)
