"""Hardware-independent perf-regression suite.

Two pieces (see each module's docstring):
  * probe    — lower the real train/serve programs, extract XLA cost
               analysis, jaxpr size, per-device bytes, donation/sharding
               legality for a config matrix;
  * budgets  — checked-in seed budgets + the one tolerance policy (fails on
               regression AND on silent improvement; re-baseline via
               ``python -m timm_tpu.perfbudget --update-budgets``).

Importing this package does not import jax, and nothing in it imports
`timm_tpu.autotune` or `timm_tpu.analysis`: both import `probe` from here
(the `layering` rule of `analysis/source_rules.py` holds the direction).
"""
from .budgets import (
    BUDGETS_PATH, TOLERANCES, assert_within, check_counter, check_counter_min,
    check_ratio_max, check_ratio_min, check_upper, compare_budgets, compare_config,
    format_violations, load_budgets, tolerance_for, update_budgets,
)
from .probe import DEFAULT_MATRIX, ProbeConfig, donation_evidence, probe_config, run_matrix

__all__ = [
    'BUDGETS_PATH', 'TOLERANCES', 'assert_within', 'check_counter',
    'check_counter_min', 'check_ratio_max', 'check_ratio_min', 'check_upper',
    'compare_budgets', 'compare_config', 'format_violations', 'load_budgets',
    'tolerance_for', 'update_budgets',
    'DEFAULT_MATRIX', 'ProbeConfig', 'donation_evidence', 'probe_config', 'run_matrix',
]
