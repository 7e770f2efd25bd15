"""Unified static-analysis suite (timm_tpu/analysis).

1. Pragma semantics: trailing / standalone / module scope, mandatory reason,
   legacy shims, pragma-spellings inside strings are not pragmas.
2. Registry: every migrated in-test lint exists as a registered rule.
3. Tier A at HEAD: the source rules pass on the live repo (this replaces the
   five in-test lint copies deleted from test_sharding/test_kernels/
   test_layers/test_data).
4. Planted violations (tests/fixtures/lint_violations/): each fixture fails
   its rule, each waived twin is suppressed, the waiver stays in the report.
5. Tier B/C on the session capture: the jaxpr/HLO rules pass over the
   programs the perfbudget probes lowered ONCE for the whole session
   (tests/conftest.py `analysis_programs`) — nothing is lowered twice.
6. CLI exit codes pinned: 0 clean / 2 violations / 3 internal error, plus
   the JSON report schema.
7. Zoo abstract-trace smoke: the cheap family subset traces clean (the full
   51-family sweep runs under -m slow and via the CLI).
"""
import json
import os

import pytest

from timm_tpu.analysis import (
    EXIT_CLEAN, EXIT_ERROR, EXIT_VIOLATIONS, AnalysisContext, FilePragmas,
    Finding, Report, all_rules, ensure_registered, run_analysis, select,
)
from timm_tpu.analysis.__main__ import main as analysis_main
from timm_tpu.analysis.jaxpr_rules import audit_softmax_policy, scan_module_program
from timm_tpu.analysis.zoo import SMOKE_FAMILIES, sweep

pytestmark = pytest.mark.analysis

FIXTURES = os.path.join(os.path.dirname(__file__), 'fixtures', 'lint_violations')

# the five lints this PR migrated out of tests/, plus the new passes
MIGRATED = {'donation-declared', 'partition-rules', 'kernel-registered',
            'fp32-softmax', 'silent-except'}
NEW = {'host-sync', 'traced-branch', 'pragma-syntax', 'large-literal',
       'dtype-promotion', 'donation-alias', 'replicated-residual',
       'baked-constant', 'zoo-abstract-trace', 'process-zero-io'}


# ---- 1. pragma semantics ----------------------------------------------------

def test_trailing_pragma_waives_its_own_line():
    text = 'x = 1\n' * 9 + 'y = 2  # timm-tpu-lint: disable=my-rule because reasons\n'
    fp = FilePragmas(text)
    assert fp.waiver_for('my-rule', 10) == 'because reasons'
    assert fp.waiver_for('my-rule', 9) is None
    assert fp.waiver_for('other-rule', 10) is None
    assert not fp.malformed


def test_standalone_pragma_waives_next_line():
    lines = ['x = 1'] * 8 + ['# timm-tpu-lint: disable=my-rule planted', 'y = 2']
    fp = FilePragmas('\n'.join(lines) + '\n')
    assert fp.waiver_for('my-rule', 10) == 'planted'
    assert fp.waiver_for('my-rule', 9) is None


def test_first_five_lines_waive_file_wide():
    text = ('# timm-tpu-lint: disable=my-rule module-wide reason\n'
            + 'x = 1\n' * 40)
    fp = FilePragmas(text)
    assert fp.waiver_for('my-rule', 37) == 'module-wide reason'
    assert fp.waiver_for('my-rule') == 'module-wide reason'
    assert fp.waiver_for('other-rule') is None


def test_comma_list_waives_each_listed_rule():
    text = 'x = 1\n' * 9 + 'y = 2  # timm-tpu-lint: disable=rule-a,rule-b shared reason\n'
    fp = FilePragmas(text)
    assert fp.waiver_for('rule-a', 10) == 'shared reason'
    assert fp.waiver_for('rule-b', 10) == 'shared reason'


def test_reasonless_pragma_waives_nothing_and_is_malformed():
    text = 'x = 1\n' * 9 + 'y = 2  # timm-tpu-lint: disable=my-rule\n'
    fp = FilePragmas(text)
    assert fp.waiver_for('my-rule', 10) is None
    assert any('reason' in msg for _, msg in fp.malformed)

    garbled = 'x = 1\n' * 9 + 'y = 2  # timm-tpu-lint: sdisable my-rule\n'
    assert FilePragmas(garbled).malformed


def test_shims_keep_their_historical_rules_and_scopes():
    # standalone no-donate shim waives the next line for donation-declared
    lines = ['import jax'] * 6 + ['# no-donate: eval keeps its inputs',
                                  'step = jax.jit(f)']
    fp = FilePragmas('\n'.join(lines) + '\n')
    assert fp.waiver_for('donation-declared', 8) == 'eval keeps its inputs'
    assert fp.waiver_for('kernel-registered', 8) is None

    # first-5-lines no-kernel-registry shim waives file-wide
    fp = FilePragmas('# no-kernel-registry: host-side helper\nx = 1\n')
    assert fp.waiver_for('kernel-registered') == 'host-side helper'

    # a reasonless shim is malformed and waives nothing
    fp = FilePragmas('# no-kernel-registry:\nx = 1\n')
    assert fp.waiver_for('kernel-registered') is None
    assert fp.malformed


def test_pragma_spelling_inside_string_is_not_a_pragma():
    text = ('x = 1\n' * 6
            + 's = "# timm-tpu-lint: disable=my-rule not a real pragma"\n')
    fp = FilePragmas(text)
    assert fp.waiver_for('my-rule', 7) is None
    assert fp.waiver_for('my-rule') is None
    assert not fp.malformed


# ---- 2. registry ------------------------------------------------------------

def test_registry_covers_every_migrated_lint_and_all_tiers():
    rules = all_rules()
    names = {r.name for r in rules}
    assert MIGRATED <= names, MIGRATED - names
    assert NEW <= names, NEW - names
    tiers = {r.tier for r in rules}
    assert tiers == {'A', 'B', 'C'}
    # Tier B/C rules that walk programs declare it, so the CLI knows when
    # the probe lowering (and the 8-device re-exec) is actually needed
    for r in rules:
        if r.name in ('large-literal', 'donation-alias',
                      'replicated-residual', 'baked-constant'):
            assert r.needs_programs, r.name


def test_select_rejects_unknown_names_and_tiers():
    with pytest.raises(KeyError, match='no-such-rule'):
        select(names=['no-such-rule'])
    with pytest.raises(KeyError, match='unknown tier'):
        select(tiers=['Z'])


def test_report_exit_codes_error_outranks_violations():
    rep = Report()
    rep.add('clean', [], 0.0)
    assert rep.exit_code == EXIT_CLEAN
    rep.add('dirty', [Finding('dirty', 'p.py', 1, 'm')], 0.0)
    assert rep.exit_code == EXIT_VIOLATIONS
    rep.add('crashed', [], 0.0, error='ValueError: boom')
    assert rep.exit_code == EXIT_ERROR
    assert rep.to_dict()['rules']['crashed']['status'] == 'error'
    # waived findings stay in the report but don't drive the exit code
    rep2 = Report()
    rep2.add('waivy', [Finding('waivy', 'p.py', 1, 'm', waived=True,
                               waive_reason='r')], 0.0)
    assert rep2.exit_code == EXIT_CLEAN and len(rep2.waived) == 1


# ---- 3. Tier A at HEAD ------------------------------------------------------

def test_tier_a_clean_at_head():
    """The consolidated source rules pass on the live repo — this single run
    replaces the five in-test lint copies this PR deleted. partition-rules
    sweeps the zoo smoke families here; the all-family sweep is the slow
    test below."""
    ensure_registered()
    report = run_analysis(AnalysisContext(zoo_families=SMOKE_FAMILIES),
                          select(tiers=['A']))
    assert report.exit_code == EXIT_CLEAN, report.format_text()
    assert set(report.rules) >= (MIGRATED | {'host-sync', 'traced-branch',
                                             'pragma-syntax', 'process-zero-io', 'layering'})


@pytest.mark.slow
def test_partition_rules_disjoint_over_every_registered_family():
    """The acceptance gate at full width: every param path of every
    registered family matches exactly one non-catch-all partition rule, with
    the conv rules active (same sweep as `python -m timm_tpu.analysis`)."""
    ensure_registered()
    report = run_analysis(AnalysisContext(), select(names=['partition-rules']))
    assert report.exit_code == EXIT_CLEAN, report.format_text()


# ---- 4. planted violations --------------------------------------------------

def _run_rule(rule_name, subdir):
    ctx = AnalysisContext(root=os.path.join(FIXTURES, subdir))
    return run_analysis(ctx, select(names=[rule_name]))


@pytest.mark.parametrize('rule_name,filename', [
    ('silent-except', 'bare_except.py'),
    ('donation-declared', 'missing_donation.py'),
    ('host-sync', 'host_sync.py'),
    ('traced-branch', 'traced_branch.py'),
    ('fp32-softmax', 'fp32_softmax.py'),
    ('process-zero-io', 'process_zero_io.py'),
    ('layering', 'layering.py'),
])
def test_planted_source_violation_fails_and_waiver_suppresses(rule_name, filename):
    report = _run_rule(rule_name, 'source')
    assert report.exit_code == EXIT_VIOLATIONS, report.format_text()
    paths = [f.path for f in report.violations]
    assert any(p.endswith(filename) for p in paths), (filename, paths)
    assert not any(p.endswith('_waived.py') for p in paths), paths


def test_waived_finding_stays_in_the_report():
    """A waiver suppresses the violation but not the audit trail."""
    report = _run_rule('silent-except', 'source')
    waived = [f for f in report.waived if f.path.endswith('bare_except_waived.py')]
    assert waived and waived[0].waive_reason


def test_planted_unregistered_kernel_fails_and_waives():
    report = _run_rule('kernel-registered', 'kernels')
    assert report.exit_code == EXIT_VIOLATIONS, report.format_text()
    paths = [f.path for f in report.violations]
    assert any(p.endswith('unregistered_kernel.py') for p in paths), paths
    assert not any(p.endswith('unregistered_kernel_waived.py') for p in paths)


def test_planted_baked_constant_detected_and_module_waiver_honored():
    findings = scan_module_program(
        os.path.join(FIXTURES, 'jaxpr', 'baked_constant.py'))
    assert findings, 'the planted 2 MB baked constant must be detected'
    assert not any(f.waived for f in findings)

    waived = scan_module_program(
        os.path.join(FIXTURES, 'jaxpr', 'baked_constant_waived.py'))
    assert waived and all(f.waived for f in waived)


def test_dtype_promotion_clean_on_policy_softmax_and_flags_planted_upcast():
    import jax
    import jax.numpy as jnp

    assert audit_softmax_policy() == []

    def bad_softmax(x):
        return jax.nn.softmax(x.astype(jnp.float32), axis=-1)

    findings = audit_softmax_policy(
        bad_softmax, (jnp.zeros((2, 4, 8, 8), jnp.bfloat16),))
    assert findings, 'planted fp32 upcast under a declared-bf16 policy'
    assert all('exp' in f.message or 'div' in f.message for f in findings)


# ---- 5. Tier B/C on the session capture -------------------------------------

def test_capture_covers_the_expected_programs(analysis_programs):
    names = {rec['name'] for rec in analysis_programs['programs']}
    assert 'base/train_step' in names, names
    assert 'tp22/fwd' in names, names
    assert any(n.startswith('serve_test_vit/bucket') for n in names), names
    assert 'elastic_resize/train_step_postresize' in names, names
    assert 'stage_scan_convnext/train_step' in names, names
    assert 'stage_scan_swin/train_step' in names, names


def test_tier_bc_rules_clean_on_captured_programs(analysis_programs):
    """The jaxpr + compiled-HLO passes run over the programs the perfbudget
    comparisons already lowered (same session fixture): donation survived
    compilation, the tp residual stays sharded, nothing baked a >1 MB
    constant."""
    ctx = AnalysisContext(programs=analysis_programs['programs'])
    rules = [r for r in all_rules() if r.needs_programs]
    report = run_analysis(ctx, rules)
    assert report.exit_code == EXIT_CLEAN, report.format_text()
    assert {'large-literal', 'donation-alias', 'replicated-residual',
            'baked-constant'} <= set(report.rules)


# ---- 6. CLI exit codes ------------------------------------------------------

def test_cli_exit_0_on_clean_rules():
    assert analysis_main(['--rules', 'fp32-softmax,pragma-syntax', '-q']) == EXIT_CLEAN


def test_cli_exit_2_on_planted_violations():
    rc = analysis_main(['--rules', 'silent-except', '-q',
                        '--source-root', os.path.join(FIXTURES, 'source')])
    assert rc == EXIT_VIOLATIONS


def test_cli_exit_3_on_unknown_rule():
    assert analysis_main(['--rules', 'no-such-rule', '-q']) == EXIT_ERROR


def test_cli_exit_3_on_internal_rule_error():
    """A crashed rule must never read as a clean repo: an unknown probe
    config makes large-literal's lowering raise before any probing, and the
    run reports exit 3 (error), not 0/2."""
    rc = analysis_main(['--rules', 'large-literal', '-q',
                        '--probe-configs', 'bogus-config'])
    assert rc == EXIT_ERROR


def test_cli_json_report_schema(tmp_path):
    out = tmp_path / 'report.json'
    rc = analysis_main(['--rules', 'fp32-softmax', '--json', str(out), '-q'])
    assert rc == EXIT_CLEAN
    doc = json.loads(out.read_text())
    assert doc['schema'] == 'timm-tpu-analysis/v1'
    assert doc['exit_code'] == EXIT_CLEAN
    assert set(doc['rules']) == {'fp32-softmax'}
    for rec in doc['rules'].values():
        assert {'status', 'wall_s', 'error', 'findings'} <= set(rec)


def test_cli_list_prints_rule_table(capsys):
    assert analysis_main(['--list']) == 0
    out = capsys.readouterr().out
    for name in MIGRATED | NEW:
        assert name in out, name


# ---- 7. zoo abstract-trace --------------------------------------------------

def test_zoo_smoke_families_trace_clean():
    records = sweep(families=SMOKE_FAMILIES)
    assert len(records) == len(SMOKE_FAMILIES)
    bad = [r for r in records if not r['ok']]
    assert not bad, bad


@pytest.mark.slow
def test_zoo_full_sweep_every_registered_family():
    """ROADMAP item 5 gate at full width: every registered family constructs
    and abstract-forwards at its native input size — this is the sweep that
    caught the res2net/resnest/sknet aa_layer constructor bug."""
    records = sweep()
    bad = [r for r in records if not r['ok']]
    assert not bad, bad
