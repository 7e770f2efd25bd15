"""The family coverage matrix — ISSUE-20's checked-in sweep artifact.

The zoo sweep (zoo.py) proves every family *traces*; this module proves how
far each family gets through the repo's actual machinery and pins the answer
in ``tests/fixtures/coverage_matrix.json``:

  * ``abstract_trace``        — the zoo gate: eval_shape ctor + abstract fwd
  * ``stage_or_block_scan``   — a scan entry point exists AND at least one
                                block list plans (plan_stage_stack)
  * ``sharded_donated_step``  — ClassificationTask train step lowers on an
                                fsdp=2 mesh with live input_output_alias
  * ``serve_aot``             — InferenceEngine AOT-compiles every bucket
                                with donation declared at lowering
  * ``device_prefetch``       — DevicePrefetcher double-buffers host batches
                                through shard_batch and the forward is finite

The three deep checks compile real programs, so they run only for families
whose representative is small (native size <= DEEP_MAX_SIZE — the test_*
fixtures plus the <=160px families); big-representative families record
``null`` there, and regenerating on a bigger box flips them to real booleans
without a schema change. A ~5-family smoke re-derives its rows in tier-1;
the full matrix re-derives under ``-m slow`` and via the CLI:

    python -m timm_tpu.analysis.coverage            # regenerate the fixture
    python -m timm_tpu.analysis.coverage --check    # recompute + diff, exit 2
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .zoo import family_representative, sweep

__all__ = ['COVERAGE_CHECKS', 'DEEP_CHECKS', 'SMOKE_COVERAGE_FAMILIES',
           'MATRIX_PATH', 'SCHEMA', 'DEEP_MAX_SIZE', 'deep_eligible',
           'scan_capability', 'family_coverage', 'load_matrix', 'write_matrix',
           'diff_matrix']

SCHEMA = 'coverage_matrix/v1'
COVERAGE_CHECKS: Tuple[str, ...] = (
    'abstract_trace', 'stage_or_block_scan', 'sharded_donated_step',
    'serve_aot', 'device_prefetch')
DEEP_CHECKS: Tuple[str, ...] = (
    'sharded_donated_step', 'serve_aot', 'device_prefetch')

# the tier-1 smoke subset: the flat-trunk baseline plus stage-scan families
# across conv (convnext), windowed attention (swin) and BN-conv (regnet)
SMOKE_COVERAGE_FAMILIES: Tuple[str, ...] = (
    'vision_transformer', 'convnext', 'swin_transformer', 'regnet',
    'mlp_mixer')

# deep checks compile the real train/serve programs — only affordable when
# the family representative is small (every test_* fixture model qualifies)
DEEP_MAX_SIZE = 160

_NUM_CLASSES = 10
_BATCH = 2

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MATRIX_PATH = os.environ.get(
    'TIMM_TPU_COVERAGE_MATRIX',
    os.path.join(_REPO_ROOT, 'tests', 'fixtures', 'coverage_matrix.json'))


def deep_eligible(module: str) -> bool:
    """True when the family's representative is cheap enough to compile the
    deep checks' real programs on the tier-1 CPU topology."""
    _name, size = family_representative(module)
    return size <= DEEP_MAX_SIZE


def _scan_block_lists(model) -> List[list]:
    """Candidate homogeneous-block sequences: each stage's block list for
    hierarchical models (regnet's stages ARE the block lists), else the flat
    trunk ``model.blocks``."""
    lists: List[list] = []
    for attr in ('stages', 'layers'):
        stages = getattr(model, attr, None)
        if stages is None:
            continue
        for st in stages:
            blocks = getattr(st, 'blocks', None)
            if blocks is None:
                try:
                    blocks = list(st)
                except TypeError:
                    continue
            try:
                blocks = list(blocks)
            except TypeError:
                continue
            if blocks:
                lists.append(blocks)
        if lists:
            return lists
    blocks = getattr(model, 'blocks', None)
    if blocks is not None:
        try:
            lists.append(list(blocks))
        except TypeError:
            pass
    return lists


def scan_capability(model) -> bool:
    """True when the model exposes a scan switch AND at least one of its
    block lists actually plans (a switch whose every stage falls back to the
    loop is not coverage)."""
    from ..models._manipulate import BlockStackError, plan_stage_stack

    if not (hasattr(model, 'set_stage_scan') or hasattr(model, 'set_block_scan')):
        return False
    for blocks in _scan_block_lists(model):
        try:
            plan_stage_stack(blocks)
            return True
        except BlockStackError:
            continue
    return False


def _abstract_scan_check(name: str) -> Tuple[bool, Optional[str]]:
    from flax import nnx

    import timm_tpu

    try:
        model = nnx.eval_shape(
            lambda: timm_tpu.create_model(name, num_classes=_NUM_CLASSES))
        return scan_capability(model), None
    except Exception as e:  # noqa: BLE001 - per-family reporting
        return False, f'{type(e).__name__}: {e}'


def _deep_checks(name: str, size: int, log=None) -> Dict[str, object]:
    """The three compile-for-real checks for one family representative.
    Each check is independently try/excepted: one family's missing subsystem
    records `false` + an error note instead of aborting the sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from ..data.loader import DevicePrefetcher
    from ..optim import create_optimizer_v2
    from ..parallel import create_mesh, set_global_mesh, shard_batch
    from ..perfbudget.probe import donation_evidence
    from ..serve import InferenceEngine
    from ..task import ClassificationTask

    out: Dict[str, object] = {}
    rng = np.random.RandomState(0)

    # -- sharded donated step: fsdp=2 over a 2-device sub-mesh --------------
    try:
        mesh = create_mesh(devices=jax.devices()[:2], fsdp=2)
        set_global_mesh(mesh)
        model = timm_tpu.create_model(name, num_classes=_NUM_CLASSES)
        task = ClassificationTask(
            model, optimizer=create_optimizer_v2(model, opt='adamw', lr=0.1),
            mesh=mesh)
        batch = shard_batch(
            {'input': jnp.asarray(rng.rand(_BATCH, size, size, 3), jnp.float32),
             'target': jnp.asarray(rng.randint(0, _NUM_CLASSES, _BATCH))}, mesh)
        compiled = task.lower_train_step(batch, lr=0.1)
        out['sharded_donated_step'] = donation_evidence(compiled)['aliases'] > 0
    except Exception as e:  # noqa: BLE001
        out['sharded_donated_step'] = False
        out['sharded_donated_step_error'] = f'{type(e).__name__}: {e}'

    # -- serve AOT bucket + device prefetch: single-device mesh -------------
    set_global_mesh(create_mesh(devices=jax.devices()[:1]))
    try:
        eng = InferenceEngine(buckets=(_BATCH,))
        eng.add_model(name, num_classes=_NUM_CLASSES)
        exes = eng.aot_executables(name)
        report = eng.donation_report(name)
        out['serve_aot'] = (set(exes) == {_BATCH}
                            and all(r.get('declared') for r in report.values()))
    except Exception as e:  # noqa: BLE001
        out['serve_aot'] = False
        out['serve_aot_error'] = f'{type(e).__name__}: {e}'

    try:
        model = timm_tpu.create_model(name, num_classes=_NUM_CLASSES)
        model.eval()
        graphdef, state = nnx.split(model)
        fwd = jax.jit(lambda s, x: nnx.merge(graphdef, s)(x))
        host = [{'input': np.asarray(rng.rand(_BATCH, size, size, 3), np.float32)}
                for _ in range(2)]
        seen, finite = 0, True
        for dev_batch in DevicePrefetcher(host):
            seen += 1
            finite = finite and bool(jnp.isfinite(fwd(state, dev_batch['input'])).all())
        out['device_prefetch'] = finite and seen == len(host)
    except Exception as e:  # noqa: BLE001
        out['device_prefetch'] = False
        out['device_prefetch_error'] = f'{type(e).__name__}: {e}'

    if log is not None:
        log(f'coverage deep {name}@{size}: ' + ' '.join(
            f'{c}={out.get(c)}' for c in DEEP_CHECKS))
    return out


def family_coverage(families: Optional[Sequence[str]] = None,
                    deep: Optional[bool] = None,
                    log=None) -> Dict[str, Dict]:
    """{module: row} for the requested families (default: every registered
    family). `deep=None` auto-selects (representative <= DEEP_MAX_SIZE);
    True/False force the deep checks on/off. Shallow rows carry ``null`` for
    the deep checks — distinct from a measured `false`."""
    import jax

    import timm_tpu
    from ..parallel import mesh as mesh_mod

    modules = list(families or timm_tpu.list_modules())
    zoo = {r['module']: r for r in sweep(families=modules)}

    rows: Dict[str, Dict] = {}
    saved_mesh = mesh_mod.peek_global_mesh()
    try:
        for module in modules:
            name, size = family_representative(module)
            z = zoo[module]
            run_deep = (size <= DEEP_MAX_SIZE) if deep is None else bool(deep)
            if run_deep and jax.device_count() < 2:
                raise RuntimeError(
                    'deep coverage checks need >=2 devices (fsdp=2 mesh): run '
                    'under XLA_FLAGS=--xla_force_host_platform_device_count=8 '
                    'or pass deep=False')
            row: Dict[str, object] = {
                'model': name, 'img_size': z['img_size'], 'deep': run_deep,
                'abstract_trace': bool(z['ok']),
            }
            if 'seq_len' in z:      # a token model: the sweep fed it ids, not an image
                row['seq_len'] = z['seq_len']
            if not z['ok']:
                row['abstract_trace_error'] = z.get('error', 'failed')
            ok, err = _abstract_scan_check(name)
            row['stage_or_block_scan'] = ok
            if err:
                row['stage_or_block_scan_error'] = err
            if run_deep:
                row.update(_deep_checks(name, size, log=log))
            else:
                row.update({c: None for c in DEEP_CHECKS})
            rows[module] = row
            if log is not None:
                log(f'coverage {module}: {name}@{size} ' + ' '.join(
                    f'{c}={row[c]}' for c in COVERAGE_CHECKS))
    finally:
        mesh_mod._GLOBAL_MESH = saved_mesh
    return rows


# ---- the checked-in artifact ------------------------------------------------

def write_matrix(rows: Dict[str, Dict], path: Optional[str] = None) -> Dict:
    path = path or MATRIX_PATH
    doc = {
        'schema': SCHEMA,
        'note': 'per-family machinery coverage; regenerate via '
                'python -m timm_tpu.analysis.coverage',
        'checks': list(COVERAGE_CHECKS),
        'families': {m: rows[m] for m in sorted(rows)},
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(doc, f, indent=1)
        f.write('\n')
    os.replace(tmp, path)
    return doc


def load_matrix(path: Optional[str] = None) -> Dict:
    path = path or MATRIX_PATH
    with open(path) as f:
        doc = json.load(f)
    if doc.get('schema') != SCHEMA:
        raise ValueError(f'{path}: unexpected coverage schema '
                         f'{doc.get("schema")!r} (want {SCHEMA!r})')
    return doc


def diff_matrix(fixture_rows: Dict[str, Dict], live_rows: Dict[str, Dict],
                checks: Sequence[str] = COVERAGE_CHECKS) -> List[str]:
    """Compare live per-check booleans against the checked-in rows (only the
    check keys — error notes and sizes don't gate). Returns human-readable
    mismatch lines; empty = the matrix still matches reality."""
    problems: List[str] = []
    for module, live in sorted(live_rows.items()):
        pinned = fixture_rows.get(module)
        if pinned is None:
            problems.append(f'{module}: missing from the checked-in matrix')
            continue
        for check in checks:
            if pinned.get(check) != live.get(check):
                problems.append(
                    f'{module}.{check}: checked-in {pinned.get(check)} '
                    f'!= live {live.get(check)} '
                    f'({live.get(check + "_error", "no error recorded")})')
    return problems


def main(argv=None) -> int:
    import argparse
    import sys

    parser = argparse.ArgumentParser(prog='python -m timm_tpu.analysis.coverage')
    parser.add_argument('--out', default=None,
                        help=f'matrix path (default {MATRIX_PATH})')
    parser.add_argument('--families', default='',
                        help='comma-separated family subset (default: all)')
    parser.add_argument('--no-deep', action='store_true',
                        help='skip the compile-for-real checks everywhere')
    parser.add_argument('--check', action='store_true',
                        help='recompute and diff against the checked-in matrix '
                             'instead of writing; exit 2 on mismatch')
    args = parser.parse_args(argv)

    # a CPU analysis on 8 virtual devices: pinned before the first JAX call
    from ..parallel import use_virtual_cpu_devices
    use_virtual_cpu_devices(8)

    families = [f.strip() for f in args.families.split(',') if f.strip()] or None
    rows = family_coverage(families=families,
                           deep=False if args.no_deep else None,
                           log=lambda m: print(m, file=sys.stderr, flush=True))
    if args.check:
        doc = load_matrix(args.out)
        problems = diff_matrix(doc['families'], rows)
        if problems:
            print('\n'.join(problems))
            return 2
        print(f'coverage matrix matches reality ({len(rows)} families)')
        return 0
    path = args.out or MATRIX_PATH
    write_matrix(rows, path)
    deep_rows = [m for m, r in rows.items() if r['deep']]
    green = [m for m in deep_rows
             if all(rows[m][c] for c in COVERAGE_CHECKS)]
    print(f'coverage: {len(rows)} families -> {path} '
          f'({len(deep_rows)} deep, {len(green)} fully green)')
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
