"""Budget storage, tolerance policy, and the ONE comparison helper family.

Every hardware-independent perf property this repo has proven (compiled
FLOPs, jaxpr equation counts, trace time, per-device state bytes, donation/
sharding legality) is pinned here against `tests/fixtures/perf_budgets.json`.
The policy is deliberately two-sided for deterministic metrics:

  * **regression** — a measured value worse than budget * (1 + tol) fails;
  * **silent improvement** — a measured value better than budget * (1 - tol)
    ALSO fails. An improvement is real information: it must be re-baselined
    explicitly (``python -m timm_tpu.perfbudget --update-budgets``) so the
    budget keeps teeth. Without this, one accidental improvement (or a probe
    bug measuring the wrong thing) silently widens the band forever.

Timing metrics (trace_ms) are upper-bound only; the probe measures the min
over two fresh traces (load spikes only ever inflate a trace, so the min is
stable) and the tolerance gives 1.3x headroom — wall-clock noise must not
flake tier-1, but a block-scan-off regression (~1.45x trace, ~1.4x eqns)
must still trip. Legality metrics (donation_ok, no_replicated_residual) are
exact booleans.

The ``check_*`` helpers at the bottom are the shared comparison policy for
the ad-hoc ratio/counter assertions that used to be scattered across
test_block_scan.py / test_serve.py / test_sharding.py — one message format,
one failure type, one place to tune.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence

SCHEMA = 'perf_budgets/v1'

# default checked-in budget file (env-overridable for scratch baselines)
_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
BUDGETS_PATH = os.environ.get(
    'TIMM_TPU_PERF_BUDGETS',
    os.path.join(_REPO_ROOT, 'tests', 'fixtures', 'perf_budgets.json'))

# metric -> (kind, tolerance). kinds:
#   band  : fail above budget*(1+tol) [regression] AND below budget*(1-tol)
#           [improvement refused until --update-budgets]
#   upper : fail above budget*(1+tol) only (timing: noise-tolerant)
#   lower : fail below budget*(1-tol) only (counts that may only grow)
#   bool  : must equal the budget exactly (legality flags)
TOLERANCES: Dict[str, tuple] = {
    'jaxpr_eqns': ('band', 0.10),
    'flops': ('band', 0.05),
    'bytes_accessed': ('band', 0.50),          # XLA:CPU pre-fusion estimate
    'param_bytes_replicated': ('band', 0.02),
    'param_bytes_sharded': ('band', 0.02),
    'opt_bytes_per_device': ('band', 0.02),
    'activation_bytes_unconstrained': ('band', 0.02),
    'activation_bytes_constrained': ('band', 0.02),
    'trace_ms': ('upper', 0.30),               # probe takes min-of-2 fresh
                                               # traces (spikes only inflate),
                                               # so 1.3x catches scan-off
                                               # (~1.45x) without flaking
    'donation_aliases': ('lower', 0.10),
    'donation_ok': ('bool', 0.0),
    'naflex_donation_aliases': ('lower', 0.10),
    'naflex_donation_ok': ('bool', 0.0),
    'no_replicated_residual': ('bool', 0.0),
    'serve_programs': ('bool', 0.0),
    'serve_donation_declared': ('bool', 0.0),
    # int8 serve-path quantization (the quant probe): per-device bytes and
    # compiled argument-buffer bytes are deterministic shape/dtype sums
    # (tight band); the cost-model aggregates keep the loose estimate band.
    # `quant_bytes_accessed_ratio` divides the compiled int8 programs'
    # argument bytes by the fp32 twins' — the per-step HBM weight-read
    # traffic — and must sit well under the 0.55x gate (quant_halves_hbm)
    'param_bytes_fp32': ('band', 0.02),
    'param_bytes_int8': ('band', 0.02),
    'quant_param_bytes_ratio': ('band', 0.02),
    'bytes_accessed_fp32': ('band', 0.50),
    'hbm_bytes_accessed_fp32': ('band', 0.02),
    'hbm_bytes_accessed_int8': ('band', 0.02),
    'quant_bytes_accessed_ratio': ('band', 0.02),
    'quant_halves_hbm': ('bool', 0.0),         # both ratios <= 0.55x fp32
    'quant_sharding_ok': ('bool', 0.0),
    'quant_scales_sharded': ('lower', 0.10),
    # elastic resize probe (resilience/elastic.py): pure legality — the
    # re-placed-after-resize state must land sharded on the new mesh and the
    # rescale solver must hold the global batch; device counts pinned exactly
    'elastic_resharding_ok': ('bool', 0.0),
    'elastic_global_batch_ok': ('bool', 0.0),
    'elastic_devices_from': ('band', 0.0),
    'elastic_devices_to': ('band', 0.0),
}
_DEFAULT_TOL = ('band', 0.10)


def tolerance_for(metric: str) -> tuple:
    return TOLERANCES.get(metric, _DEFAULT_TOL)


def load_budgets(path: Optional[str] = None) -> Dict:
    path = path or BUDGETS_PATH
    with open(path) as f:
        doc = json.load(f)
    if doc.get('schema') != SCHEMA:
        raise ValueError(f'{path}: unexpected budget schema {doc.get("schema")!r} '
                         f'(want {SCHEMA!r})')
    return doc


def update_budgets(measured: Dict[str, Dict], path: Optional[str] = None,
                   note: str = '') -> Dict:
    """Re-baseline: write `measured` ({config: {metric: value}}) as the new
    budget file. This is the ONLY sanctioned way to accept an improvement."""
    path = path or BUDGETS_PATH
    doc = {
        'schema': SCHEMA,
        'generated_at': time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()),
        'note': note or 'seed budgets; re-baseline via '
                        'python -m timm_tpu.perfbudget --update-budgets',
        'tolerances': {m: {'kind': k, 'tol': t} for m, (k, t) in TOLERANCES.items()},
        'configs': {name: dict(sorted(metrics.items()))
                    for name, metrics in sorted(measured.items())},
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write('\n')
    os.replace(tmp, path)
    return doc


def compare_config(measured: Dict, budget: Dict, config: str = '',
                   metrics: Optional[Sequence[str]] = None) -> List[Dict]:
    """Compare one config's measured metrics against its budget entry.

    Returns a list of violation dicts. `metrics` restricts the comparison
    (partial probes — e.g. trace-only); default compares every budgeted
    metric and flags budgeted-but-unmeasured metrics as 'missing' so a probe
    that silently stops collecting a metric cannot pass."""
    out: List[Dict] = []
    names = list(metrics) if metrics is not None else sorted(budget)
    for metric in names:
        if metric not in budget:
            continue
        b = budget[metric]
        kind, tol = tolerance_for(metric)

        def viol(direction, detail, measured_v=None):
            out.append({'config': config, 'metric': metric, 'kind': kind,
                        'measured': measured_v, 'budget': b,
                        'direction': direction, 'detail': detail})

        if metric not in measured:
            viol('missing', 'metric budgeted but not measured')
            continue
        v = measured[metric]
        if kind == 'bool':
            if bool(v) != bool(b):
                viol('mismatch', f'expected {b!r}, measured {v!r}', v)
            continue
        hi, lo = float(b) * (1.0 + tol), float(b) * (1.0 - tol)
        if kind in ('band', 'upper') and float(v) > hi:
            viol('regression',
                 f'{v:.6g} > {b:.6g} * (1+{tol:g}) = {hi:.6g}', v)
        if kind in ('band', 'lower') and float(v) < lo:
            direction = 'improvement' if kind == 'band' else 'regression'
            what = ('improved past the tolerance band — re-baseline explicitly '
                    'with --update-budgets' if direction == 'improvement'
                    else 'fell below the budgeted floor')
            viol(direction, f'{v:.6g} < {b:.6g} * (1-{tol:g}) = {lo:.6g} ({what})', v)
    return out


def compare_budgets(measured_all: Dict[str, Dict], budgets: Dict,
                    configs: Optional[Sequence[str]] = None) -> List[Dict]:
    """Compare a {config: metrics} result set against a loaded budget doc."""
    entries = budgets.get('configs', budgets)
    out: List[Dict] = []
    for name in (configs if configs is not None else sorted(entries)):
        if name not in entries:
            continue
        if name not in measured_all:
            out.append({'config': name, 'metric': '*', 'kind': 'config',
                        'measured': None, 'budget': None, 'direction': 'missing',
                        'detail': 'budgeted config not probed'})
            continue
        out.extend(compare_config(measured_all[name], entries[name], config=name))
    return out


def format_violations(violations: Sequence[Dict]) -> str:
    if not violations:
        return 'perfbudget: all metrics within budget'
    lines = [f'perfbudget: {len(violations)} budget violation(s):']
    for v in violations:
        lines.append(
            f"  [{v['direction']}] {v['config']}.{v['metric']} "
            f"({v['kind']}): {v['detail']}")
    return '\n'.join(lines)


def assert_within(measured_all: Dict[str, Dict], budgets: Dict,
                  configs: Optional[Sequence[str]] = None) -> None:
    violations = compare_budgets(measured_all, budgets, configs=configs)
    if violations:
        raise AssertionError(format_violations(violations))


# ---- shared ad-hoc comparison policy (the single tolerance authority for
# ---- the compile-time / cache-count assertions in the test suite) -----------

def check_counter(name: str, actual, expected) -> None:
    """Exact counter equality (cache hits, fresh compiles, program counts)."""
    if int(actual) != int(expected):
        raise AssertionError(
            f'perfbudget counter {name!r}: measured {actual}, expected exactly '
            f'{expected}')


def check_counter_min(name: str, actual, minimum) -> None:
    """Counter floor (e.g. disk-cache hits must at least cover the programs)."""
    if int(actual) < int(minimum):
        raise AssertionError(
            f'perfbudget counter {name!r}: measured {actual}, expected >= {minimum}')


def check_ratio_max(name: str, value, baseline, max_ratio: float) -> None:
    """`value` must stay under `max_ratio` x `baseline` — the O(1)-cost
    contracts (scanned depth-12 jaxpr < 2x depth-2, accum=8 < 2x accum=2)."""
    if float(value) >= float(max_ratio) * float(baseline):
        raise AssertionError(
            f'perfbudget ratio {name!r}: {value} >= {max_ratio:g} x baseline '
            f'{baseline} (ratio {float(value) / max(float(baseline), 1e-12):.2f})')


def check_ratio_min(name: str, value, baseline, min_ratio: float) -> None:
    """`value` must exceed `min_ratio` x `baseline` — sanity direction checks
    (the unrolled/loop jaxpr must dwarf the scanned one, or the scanned
    measurement itself is broken)."""
    if float(value) <= float(min_ratio) * float(baseline):
        raise AssertionError(
            f'perfbudget ratio {name!r}: {value} <= {min_ratio:g} x baseline '
            f'{baseline} (ratio {float(value) / max(float(baseline), 1e-12):.2f})')


def check_upper(name: str, value, limit, *, unit: str = '') -> None:
    """Plain upper bound with the shared message format (timing budgets)."""
    if float(value) > float(limit):
        raise AssertionError(
            f'perfbudget bound {name!r}: measured {value}{unit} > budget '
            f'{limit}{unit}')
