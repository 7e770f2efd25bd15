"""Perf-budget suite: hardware-independent regression gates.

1. Budget semantics: the tolerance policy fails on regression AND on silent
   improvement (re-baseline only via --update-budgets), and a metric that
   silently stops being measured fails as 'missing'.
2. Seed budgets: probing the live code against tests/fixtures/
   perf_budgets.json stays clean; an injected block_scan=False regression
   trips the jaxpr-eqn AND trace-time budgets for the scanned config.
3. `python -m timm_tpu.perfbudget`'s `main`: exit codes and the printed
   violation, over the session's capture.
"""
import json

import pytest

from timm_tpu.perfbudget import (
    DEFAULT_MATRIX, ProbeConfig, check_counter, check_counter_min, check_ratio_max,
    check_ratio_min, check_upper, compare_budgets, compare_config, format_violations,
    load_budgets, probe_config, run_matrix, tolerance_for, update_budgets,
)

pytestmark = pytest.mark.perfbudget


# ---- 1. tolerance policy (pure, no jax) -------------------------------------

def test_tolerance_policy_directions():
    budget = {'jaxpr_eqns': 1000, 'trace_ms': 400.0, 'donation_aliases': 100,
              'donation_ok': True}

    # within band: clean
    ok = {'jaxpr_eqns': 1040, 'trace_ms': 380.0, 'donation_aliases': 99,
          'donation_ok': True}
    assert compare_config(ok, budget, 'cfg') == []

    # regression: band exceeded upward
    worse = dict(ok, jaxpr_eqns=1200)
    v = compare_config(worse, budget, 'cfg')
    assert [x['direction'] for x in v] == ['regression'] and v[0]['metric'] == 'jaxpr_eqns'

    # silent improvement: band exceeded downward must ALSO fail
    better = dict(ok, jaxpr_eqns=500)
    v = compare_config(better, budget, 'cfg')
    assert [x['direction'] for x in v] == ['improvement']
    assert 'update-budgets' in v[0]['detail']

    # upper-only metric: improvement is free, regression is not
    assert compare_config(dict(ok, trace_ms=10.0), budget, 'cfg') == []
    v = compare_config(dict(ok, trace_ms=900.0), budget, 'cfg')
    assert [x['direction'] for x in v] == ['regression']

    # lower-only metric: losing aliases is a regression, gaining is free
    v = compare_config(dict(ok, donation_aliases=50), budget, 'cfg')
    assert [x['direction'] for x in v] == ['regression']
    assert compare_config(dict(ok, donation_aliases=150), budget, 'cfg') == []

    # bool mismatch + silently-dropped metric
    v = compare_config(dict(ok, donation_ok=False), budget, 'cfg')
    assert [x['direction'] for x in v] == ['mismatch']
    dropped = {k: v for k, v in ok.items() if k != 'donation_ok'}
    v = compare_config(dropped, budget, 'cfg')
    assert [x['direction'] for x in v] == ['missing']

    # un-probed budgeted config
    v = compare_budgets({}, {'configs': {'cfg': budget}})
    assert [x['direction'] for x in v] == ['missing'] and v[0]['metric'] == '*'
    assert 'violation' in format_violations(v)

    assert tolerance_for('flops') == ('band', 0.05)
    assert tolerance_for('never_seen_metric') == ('band', 0.10)


def test_shared_check_helpers():
    check_counter('c', 2, 2)
    with pytest.raises(AssertionError, match='expected exactly'):
        check_counter('c', 3, 2)
    check_counter_min('c', 5, 5)
    with pytest.raises(AssertionError, match='>='):
        check_counter_min('c', 4, 5)
    check_ratio_max('r', 199, 100, 2.0)
    with pytest.raises(AssertionError, match='>= 2'):
        check_ratio_max('r', 200, 100, 2.0)
    check_ratio_min('r', 201, 100, 2.0)
    with pytest.raises(AssertionError, match='<= 2'):
        check_ratio_min('r', 200, 100, 2.0)
    check_upper('u', 1.0, 1.0)
    with pytest.raises(AssertionError, match='> budget'):
        check_upper('u', 1.1, 1.0, unit='ms')


def test_improvement_requires_explicit_rebaseline(tmp_path):
    """The --update-budgets workflow: a genuine win fails comparison until
    the budgets file is regenerated, after which it passes."""
    budgets = load_budgets()
    base = dict(budgets['configs']['base'])
    improved = dict(base, jaxpr_eqns=base['jaxpr_eqns'] // 2)

    v = compare_config(improved, base, 'base')
    assert [x['direction'] for x in v] == ['improvement']

    path = str(tmp_path / 'budgets.json')
    doc = update_budgets({'base': improved}, path=path, note='test rebaseline')
    assert doc['schema'] == 'perf_budgets/v1'
    reloaded = load_budgets(path)
    assert compare_budgets({'base': improved}, reloaded) == []


# ---- 2. live probe vs seed budgets ------------------------------------------

@pytest.fixture(scope='module')
def seed_budgets():
    return load_budgets()


def test_seed_budgets_pass_on_live_code(seed_budgets, analysis_programs):
    """The session-scoped capture (tests/conftest.py `analysis_programs`,
    shared with the analysis suite's Tier B/C passes in test_analysis.py)
    probes base/accum4/serve_test_vit/tp22/elastic_resize exactly ONCE per
    tier-1 run; this test compares those measurements against the checked-in
    budgets. tp22 rides along as new comparison coverage (it previously only
    ran via the CLI). The full matrix is still the CLI
    (`python -m timm_tpu.perfbudget`); scan_depth12's budget is exercised by
    the injected-regression test below.

    trace_ms is excluded HERE only: for the small configs it is sensitive to
    how much tracing already warmed the process (the seed CLI probes the full
    matrix in order; this subset doesn't), and the 1.3x tolerance is sized
    for the consistent-context CLI run. The trace-time budget still has
    tier-1 teeth via the scan_depth12 injection test below, where the signal
    (~1.45x) dwarfs warmth effects."""
    names = list(analysis_programs['names'])
    measured = analysis_programs['measured']
    violations = [v for v in compare_budgets(measured, seed_budgets, configs=names)
                  if v['metric'] != 'trace_ms']
    assert not violations, format_violations(violations)


def test_injected_blockscan_regression_trips_budgets(seed_budgets):
    """Acceptance: turning block_scan OFF for the depth-12 config must trip
    BOTH the jaxpr-equation and the trace-time budgets (the O(1)-in-depth
    contract), proving the suite catches the regression it was built for.

    jaxpr_eqns is deterministic, so it compares against the checked-in seed.
    The trace_ms baseline is re-probed in THIS process instead: trace wall
    time shifts with how warm the interpreter is, so the only apples-to-apples
    comparison is scan-on vs scan-off under identical warmth — exactly what a
    regression lands as. The budget machinery (kind/tolerance) is unchanged.

    The injected regression is probed at depth 24 (the O(depth) loop cost
    doubles, the scanned side barely moves): at depth 12 the scan/loop trace
    ratio sits right AT the 30% band tolerance on slower hosts (~1.2-1.3x),
    so the acceptance check would flake on exactly the machinery it is meant
    to prove out."""
    scan_cfg = next(c for c in DEFAULT_MATRIX if c.name == 'scan_depth12')

    def probe(block_scan):
        return probe_config(ProbeConfig(
            name='scan_depth12', model=scan_cfg.model,
            model_kwargs=scan_cfg.model_kwargs + (('depth', 24),),
            batch_size=scan_cfg.batch_size,
            block_scan=block_scan, collect='trace'))

    probe(True)  # discard: the first probe pays one-time warm-up costs
    baseline, measured = None, None
    for _ in range(2):  # interleaved so drift hits both sides equally
        b, m = probe(True), probe(False)
        if baseline is None or b['trace_ms'] < baseline['trace_ms']:
            baseline = b
        if measured is None or m['trace_ms'] < measured['trace_ms']:
            measured = m
    print(f'scan trace_ms={baseline["trace_ms"]} '
          f'loop trace_ms={measured["trace_ms"]}')  # shown iff the test fails
    budget = dict(seed_budgets['configs']['scan_depth12'])
    budget['trace_ms'] = baseline['trace_ms']
    violations = compare_config(measured, budget,
                                'scan_depth12', metrics=('jaxpr_eqns', 'trace_ms'))
    tripped = {v['metric'] for v in violations if v['direction'] == 'regression'}
    assert tripped == {'jaxpr_eqns', 'trace_ms'}, format_violations(violations)


def test_elastic_resize_probe_within_budgets(analysis_programs):
    """PR-13 acceptance: the re-placed-after-resize train step stays legal —
    state saved on the 8-device (2,4) mesh re-places sharded on the 4-device
    mesh, the rescale solver holds the global batch, and donation survives
    the resize. The exact bools/counts pinned in perf_budgets.json are
    compared by the test above (same shared capture, probed once); the two
    elastic invariants are additionally asserted here directly."""
    measured = analysis_programs['measured']
    assert measured['elastic_resize']['elastic_resharding_ok'] is True
    assert measured['elastic_resize']['donation_ok'] is True


def test_run_matrix_rejects_unknown_config():
    with pytest.raises(ValueError, match='unknown'):
        run_matrix(names=['no_such_config'])


# ---- 3. the CLI the README names --------------------------------------------

def test_cli_compares_against_budgets_and_exits_nonzero_on_a_tampered_band(
        tmp_path, monkeypatch, capsys, seed_budgets, analysis_programs):
    """`python -m timm_tpu.perfbudget`: exit 0 against the checked-in budgets,
    exit 1 with the violation printed against a `--budgets` file with one band
    tampered. `run_matrix` is the session's capture, so nothing is lowered
    again; `trace_ms` is taken from the budget for the reason
    `test_seed_budgets_pass_on_live_code` gives."""
    from timm_tpu.perfbudget import __main__ as cli

    names = list(analysis_programs['names'])
    measured = {n: dict(analysis_programs['measured'][n]) for n in names}
    for n, metrics in measured.items():
        if 'trace_ms' in metrics:
            metrics['trace_ms'] = seed_budgets['configs'][n]['trace_ms']
    asked = []

    def captured_matrix(names=None, log=None):
        asked.append(list(names))
        return {n: measured[n] for n in names}

    monkeypatch.setattr('timm_tpu.perfbudget.probe.run_matrix', captured_matrix)
    argv = ['--configs', ','.join(names)]
    assert cli.main(argv) == 0, capsys.readouterr().out
    assert 'all metrics within budget' in capsys.readouterr().out
    assert asked == [names]

    tampered = json.loads(json.dumps(seed_budgets))
    tampered['configs']['base']['jaxpr_eqns'] *= 2
    path = tmp_path / 'budgets.json'
    path.write_text(json.dumps(tampered))
    assert cli.main(argv + ['--budgets', str(path)]) == 1
    out = capsys.readouterr().out
    assert '1 budget violation(s)' in out and '[improvement] base.jaxpr_eqns' in out
