"""Hands-free MFU: enumerate legal configs, rank them on a compiled-cost
roofline, apply the winner.

Surfaces: `train.py --autotune` (table + applied flags),
`python -m timm_tpu.autotune` (JSON), `autotune.propose_buckets` (serve
bucket-ladder advisory), and the elastic re-solve
(`resolve_config_for_topology`, called by `plan_elastic_resume`).

NOT imported by `timm_tpu/__init__.py` — importing this package pulls in
probe machinery lazily; all heavy imports happen inside functions.
"""
from .buckets import ladder_cost, ladder_waste, propose_buckets
from .cost import (
    DEVICE_CLASSES, CostEstimate, DeviceClass, analytic_cost,
    default_hbm_budget, detect_device_class, probed_cost,
    roofline_ms,
)
from .solver import (
    AutotuneError, AutotuneResult, RankedPoint, apply_to_args, autotune,
    format_table, resolve_config_for_topology, to_json,
)
from .space import (
    CandidateConfig, LegalPoint, Rejection, batch_splits, enumerate_configs,
    mesh_axis_points,
)

__all__ = [
    'AutotuneError', 'AutotuneResult', 'CandidateConfig', 'CostEstimate',
    'DEVICE_CLASSES', 'DeviceClass', 'LegalPoint', 'RankedPoint', 'Rejection',
    'analytic_cost', 'apply_to_args', 'autotune', 'batch_splits',
    'default_hbm_budget', 'detect_device_class', 'enumerate_configs',
    'format_table', 'ladder_cost', 'ladder_waste',
    'mesh_axis_points', 'probed_cost', 'propose_buckets',
    'resolve_config_for_topology', 'roofline_ms', 'to_json',
]
