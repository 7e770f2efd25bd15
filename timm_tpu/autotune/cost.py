"""Analytic roofline cost model over the legal-config space.

Three tiers, cheapest first; each is a strictly better-informed version of
the one below and all three rank with the SAME roofline:

  * ``analytic``  — closed-form transformer FLOPs (3x-forward rule over the
    attn/MLP matmuls) and a per-device byte-traffic model built from the
    enumerator's `param_bytes_per_device` numbers. Zero lowering; this is
    what the elastic re-solve runs in the restart pre-pass.
  * ``estimator`` — the analytic model rescaled so it passes EXACTLY through
    one probed anchor: `perfbudget.probe` lowers the real TrainingTask step
    once, and ``fit_scales`` divides XLA's compiled flops/bytes by the
    analytic prediction for the same point. Full enumeration then costs one
    compile, not hundreds.
  * ``probed``    — `--probe-top-k`: the shortlist's REAL programs are
    lowered and the roofline runs on their compiled `cost_analysis()`
    directly (trace time recorded as the tiebreak).

The roofline itself (Williams et al.): predicted step time is
``max(flops / peak_flops, bytes / hbm_bandwidth)`` per device class, with
trace/compile cost as a deterministic tiebreak (block_scan=False traces
O(depth) — it can never win a tie). A caller's ``correction`` factor
multiplies the predicted time; rankings are invariant to it, and no run on
the chip has fit one yet (ROADMAP D6), so the printed milliseconds are the
nameplate roofline's.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

from .space import LegalPoint, OPT_SLOTS

__all__ = [
    'DeviceClass', 'DEVICE_CLASSES', 'detect_device_class', 'roofline_ms',
    'CostEstimate', 'analytic_flops', 'analytic_bytes', 'analytic_cost',
    'probed_cost', 'fit_scales', 'REMAT_FLOPS_FACTOR',
]

# Full remat re-runs ~one forward of the fwd+bwd(≈3x fwd) step: 4/3 FLOPs.
REMAT_FLOPS_FACTOR = 4.0 / 3.0
# Train step ≈ forward + 2x backward (the 3x rule PERF.md measured at 3.05).
TRAIN_FLOPS_FACTOR = 3.0


@dataclasses.dataclass(frozen=True)
class DeviceClass:
    """Peak numbers per chip. bf16 matmul peak and HBM bandwidth are the
    roofline's two denominators; `hbm_bytes` is the default enumeration
    budget. The 'cpu' class exists so CPU runs rank deterministically —
    its absolute milliseconds are nominal, not meaningful."""
    name: str
    peak_flops: float   # bf16 FLOP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: int      # capacity


# v5e numbers match PERF.md's ground truth (197e12 peak, 819 GB/s).
DEVICE_CLASSES: Dict[str, DeviceClass] = {
    'v4': DeviceClass('v4', 275e12, 1228e9, 32 << 30),
    'v5e': DeviceClass('v5e', 197e12, 819e9, 16 << 30),
    'v5p': DeviceClass('v5p', 459e12, 2765e9, 96 << 30),
    'v6e': DeviceClass('v6e', 918e12, 1640e9, 32 << 30),
    'cpu': DeviceClass('cpu', 1e12, 100e9, 4 << 30),
}


def detect_device_class(devices=None) -> DeviceClass:
    """Map `device_kind` strings onto the registry; unknown kinds fall back
    to 'cpu' (deterministic ranking with nominal constants)."""
    import jax

    devices = list(devices) if devices is not None else jax.devices()
    kind = (getattr(devices[0], 'device_kind', '') or '').lower() if devices else ''
    for key in ('v6e', 'v5p', 'v5e', 'v4'):
        if key in kind or key.replace('v', 'tpu v') in kind:
            return DEVICE_CLASSES[key]
    if 'v5 lite' in kind or 'v5litepod' in kind:
        return DEVICE_CLASSES['v5e']
    return DEVICE_CLASSES['cpu']


def roofline_ms(flops: float, bytes_accessed: float,
                dc: DeviceClass) -> Tuple[float, float, float, str]:
    """(step_ms, compute_ms, memory_ms, bound): the max of the two service
    times, per device. Monotone in both inputs by construction."""
    compute_ms = 1e3 * float(flops) / dc.peak_flops
    memory_ms = 1e3 * float(bytes_accessed) / dc.hbm_bw
    if compute_ms >= memory_ms:
        return compute_ms, compute_ms, memory_ms, 'compute'
    return memory_ms, compute_ms, memory_ms, 'memory'


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    step_ms: float          # predicted GLOBAL-step time (accum micro-steps)
    compute_ms: float
    memory_ms: float
    bound: str              # 'compute' | 'memory'
    tier: str               # 'analytic' | 'estimator' | 'probed'
    flops: float            # per-device, per global step
    bytes: float            # per-device, per global step
    trace_penalty: float    # deterministic tiebreak (block_scan off, depth)

    def sort_key(self) -> Tuple:
        """Total order: corrected time, then trace cost, then nothing —
        ties beyond that break on the candidate ordering the solver fixes."""
        return (round(self.step_ms, 6), round(self.trace_penalty, 6))


def analytic_flops(dims: Tuple[int, int, int], batch_size: int,
                   mlp_ratio: float = 4.0) -> float:
    """Whole-model train-step FLOPs for a batch (all devices combined).

    Per block and token: qkv (6LW^2 over the block: counted per token as
    6W^2), attention proj 2W^2, scores+apply 4LW, MLP 2*2*r*W^2 — times
    depth, times 3 for fwd+bwd. Patch embed/head are small and omitted;
    the estimator tier's fitted scale absorbs them."""
    seq_len, width, depth = (int(d) for d in dims)
    per_block = (6.0 + 2.0 + 4.0 * float(mlp_ratio)) * width * width \
        + 4.0 * seq_len * width
    fwd = float(batch_size) * seq_len * depth * per_block
    return TRAIN_FLOPS_FACTOR * fwd


def analytic_bytes(point: LegalPoint, n_devices: int) -> float:
    """Per-device HBM traffic for ONE GLOBAL step (accum micro-steps + one
    optimizer update).

    Each micro-step streams the full param bytes twice (fwd + bwd reads;
    under fsdp the all-gather still delivers full params to every device)
    plus ~2x the live activation bytes (written forward, read backward; the
    enumerator already discounted the remat fraction). The once-per-step
    update term reads+writes only the device's own shard: grads
    (reduce-scattered), OPT_SLOTS optimizer slots, and the param write."""
    cfg = point.config
    micro = 2.0 * point.param_bytes_full + 2.0 * point.act_bytes
    update = (3.0 + 2.0 * OPT_SLOTS) * point.param_bytes
    return cfg.grad_accum * micro + update


def analytic_cost(point: LegalPoint, dims: Optional[Tuple[int, int, int]],
                  dc: DeviceClass, n_devices: int, *,
                  mlp_ratio: float = 4.0,
                  flops_scale: float = 1.0, bytes_scale: float = 1.0,
                  correction: float = 1.0, tier: str = 'analytic') -> CostEstimate:
    """Roofline over the analytic model (optionally anchor-rescaled).

    FLOPs split evenly over devices (batch shards over every mesh axis;
    tp shards the matmuls themselves). `trace_penalty` charges
    block_scan=False a depth-proportional trace cost so the tiebreak always
    prefers the scanned program, mirroring the measured O(depth) contract."""
    cfg = point.config
    depth = int(dims[2]) if dims else 1
    if dims is not None:
        flops = analytic_flops(dims, cfg.batch_size, mlp_ratio) / max(n_devices, 1)
    else:
        flops = 0.0
    if cfg.remat:
        flops *= REMAT_FLOPS_FACTOR
    flops *= cfg.grad_accum * flops_scale
    bytes_ = analytic_bytes(point, n_devices) * bytes_scale
    step_ms, compute_ms, memory_ms, bound = roofline_ms(flops, bytes_, dc)
    penalty = float(depth if not cfg.block_scan else 1)
    return CostEstimate(step_ms=step_ms * correction, compute_ms=compute_ms,
                        memory_ms=memory_ms, bound=bound, tier=tier,
                        flops=flops, bytes=bytes_, trace_penalty=penalty)


def fit_scales(anchor_metrics: Dict, anchor_point: LegalPoint,
               dims: Tuple[int, int, int], dc: DeviceClass, n_devices: int,
               mlp_ratio: float = 4.0) -> Tuple[float, float]:
    """(flops_scale, bytes_scale) so the analytic model passes exactly
    through the probed anchor. `anchor_metrics` is a `perfbudget.probe`
    'full'-collect result for the anchor config (flops / bytes_accessed of
    the REAL compiled train step). Missing metrics leave that scale at 1."""
    base = analytic_cost(anchor_point, dims, dc, n_devices, mlp_ratio=mlp_ratio)
    flops_scale = bytes_scale = 1.0
    if anchor_metrics.get('flops') and base.flops > 0:
        flops_scale = float(anchor_metrics['flops']) / base.flops
    if anchor_metrics.get('bytes_accessed') and base.bytes > 0:
        bytes_scale = float(anchor_metrics['bytes_accessed']) / base.bytes
    return flops_scale, bytes_scale


def probed_cost(metrics: Dict, point: LegalPoint, dc: DeviceClass, *,
                correction: float = 1.0) -> Optional[CostEstimate]:
    """Roofline directly on a probed config's compiled cost analysis. The
    lowered program already contains the whole accum loop + update, so no
    scaling applies. Returns None when XLA reported no flops (the probe
    logged why — see `_cost_analysis`)."""
    if 'flops' not in metrics:
        return None
    flops = float(metrics['flops'])
    bytes_ = float(metrics.get('bytes_accessed', 0.0))
    step_ms, compute_ms, memory_ms, bound = roofline_ms(flops, bytes_, dc)
    return CostEstimate(step_ms=step_ms * correction, compute_ms=compute_ms,
                        memory_ms=memory_ms, bound=bound, tier='probed',
                        flops=flops, bytes=bytes_,
                        trace_penalty=float(metrics.get('trace_ms', 0.0)))


def default_hbm_budget(dc: DeviceClass) -> int:
    """Enumeration budget: the device's HBM minus a fixed XLA scratch
    reserve (env TIMM_TPU_AUTOTUNE_HBM_GB overrides end to end)."""
    env = os.environ.get('TIMM_TPU_AUTOTUNE_HBM_GB', '')
    if env:
        return int(float(env) * 2**30)
    return int(dc.hbm_bytes * 0.9)
