"""Test config: an 8-device virtual CPU mesh, chosen before JAX initializes.

The platform is pinned through jax.config as well as JAX_PLATFORMS: an
explicit choice for the whole suite, whatever the environment says.
"""
import os

os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=8')

from timm_tpu.parallel import use_virtual_cpu_devices
from timm_tpu.utils.compile_cache import configure_compile_cache

use_virtual_cpu_devices(8)

# Persistent XLA compilation cache: model sweeps recompile the same tiny
# fixture programs every run. Subprocess tests (resilience drills) that call
# configure_compile_cache resolve the same directory.
configure_compile_cache()

import pytest


def pytest_configure(config):
    # registered in pyproject.toml too; double registration is harmless and
    # keeps `pytest tests/test_serve.py` warning-free outside the repo root
    config.addinivalue_line(
        'markers',
        'serve: continuous-batching inference engine — bucketing, admission '
        'queue, AOT prewarm, LRU residency, load drill (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'perfbudget: hardware-independent perf-regression budgets + profiler '
        'harness (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'deviceaug: on-device batch augmentation + NaFlex packed bucketed '
        'batching — host/device parity, donation, zero-recompile epochs '
        '(runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'quant: int8 post-training weight-only quantization — round-trip '
        'bounds, seeded-weights logits tolerance, scale-spec inheritance, '
        'quantized serve parity, distill smoke (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'kernels: Pallas kernel portfolio — registry lint, auto-generated '
        'parity, fused AdamW/EMA drift, augment-epilogue oracle parity, '
        'win-or-delete verdicts (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'elastic: elastic pod-scale training — resize-the-mesh resume drills '
        '(8↔4 devices, global batch invariant) + async checkpoint writer '
        '(runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'analysis: unified static-analysis suite — source/jaxpr/HLO rules, '
        'pragma waivers, planted-violation fixtures, CLI exit codes, zoo '
        'abstract-trace smoke (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'autotune: config autotuner — legal-space enumeration, roofline '
        'ranking, estimator/probed agreement, elastic re-solve, bucket-'
        'ladder DP (runs in tier-1)')
    config.addinivalue_line(
        'markers',
        'multihost: multi-process pod runtime — KV-store consensus, '
        'process-local sharded checkpoints, host-loss kill drill '
        '(runs in tier-1)')


@pytest.fixture(scope='session')
def mesh8():
    from timm_tpu.parallel import create_mesh, set_global_mesh
    mesh = create_mesh()
    set_global_mesh(mesh)
    return mesh


@pytest.fixture(scope='session')
def analysis_programs():
    """ONE probe run shared by the perf-budget comparisons (test_perfbudget)
    and the analysis suite's Tier B/C passes (test_analysis): run_matrix
    lowers each program exactly once, and capture_programs hands the jaxprs
    + compiled executables to the jaxpr/HLO rules without re-lowering.
    probe_config saves/restores the global mesh, so this composes with
    whatever mesh the consuming test file has active."""
    from timm_tpu.perfbudget import run_matrix
    from timm_tpu.perfbudget.probe import capture_programs

    names = ('base', 'accum4', 'serve_test_vit', 'tp22', 'elastic_resize',
             'stage_scan_convnext', 'stage_scan_swin')
    with capture_programs() as programs:
        measured = run_matrix(names=list(names))
    return {'names': names, 'measured': measured, 'programs': list(programs)}
