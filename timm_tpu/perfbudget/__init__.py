"""Hardware-independent perf-regression suite + replay.

Three pieces (see each module's docstring):
  * probe    — lower the real train/serve programs, extract XLA cost
               analysis, jaxpr size, per-device bytes, donation/sharding
               legality for a config matrix;
  * budgets  — checked-in seed budgets + the one tolerance policy (fails on
               regression AND on silent improvement; re-baseline via
               ``python -m timm_tpu.perfbudget --update-budgets``);
  * replay   — the PERF.md on-device checklist as one scripted sequence
               writing BENCH_SELF.json (`bench.py --replay [--dry-run]`).

Top-level imports stay lazy-safe: importing this package does not import
jax (bench.py's abort paths use the replay writers pre-jax-setup).
"""
from .budgets import (
    BUDGETS_PATH, TOLERANCES, assert_within, check_counter, check_counter_min,
    check_ratio_max, check_ratio_min, check_upper, compare_budgets, compare_config,
    format_violations, load_budgets, tolerance_for, update_budgets,
)
from .probe import DEFAULT_MATRIX, ProbeConfig, donation_evidence, probe_config, run_matrix
from .replay import (
    REPLAY_STEPS, SELF_SCHEMA, load_self_doc, record_result,
    run_replay, save_self_doc, validate_self_result,
)

__all__ = [
    'BUDGETS_PATH', 'TOLERANCES', 'assert_within', 'check_counter',
    'check_counter_min', 'check_ratio_max', 'check_ratio_min', 'check_upper',
    'compare_budgets', 'compare_config', 'format_violations', 'load_budgets',
    'tolerance_for', 'update_budgets',
    'DEFAULT_MATRIX', 'ProbeConfig', 'donation_evidence', 'probe_config', 'run_matrix',
    'REPLAY_STEPS', 'SELF_SCHEMA', 'load_self_doc', 'record_result',
    'run_replay', 'save_self_doc', 'validate_self_result',
]
