"""Tier A — AST/source rules.

The five lints that used to live inline in tests/ (donation-declared,
partition-rules, kernel-registered, fp32-softmax, silent-except) plus the
sweeps added since (host-sync, traced-branch, pragma-syntax, process-zero-io,
layering). All of
them honor the unified pragma (see pragmas.py); the first four keep their
historical waiver spellings via the shims.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Set

import numpy as np

from .registry import AnalysisContext, rule
from .report import Finding

# directories of the timm_tpu package swept by the repo-wide source rules
_PACKAGE = 'timm_tpu'


def _lineno(text: str, pos: int) -> int:
    return text.count('\n', 0, pos) + 1


# ---- silent-except (repo-wide; was tests/test_data.py, data/ only) ----------

_SILENT_EXCEPT_RE = re.compile(
    r'except\s+(Exception|BaseException)?\s*(as\s+\w+)?\s*:\s*\n\s*pass\b')


@rule('silent-except', 'A',
      'no `except [Exception]: pass` anywhere in timm_tpu/ or the top-level '
      'scripts — transient faults go through the resilience retry policy, '
      'permanent ones through the poison-skip budget; both log')
def silent_except(ctx: AnalysisContext) -> List[Finding]:
    files = list(ctx.walk_files(_PACKAGE))
    pkg_dir = ctx.source_dir(_PACKAGE)
    if pkg_dir != ctx.root:
        # top-level driver scripts ride along (train.py, validate.py, ...)
        files += [os.path.join(ctx.root, f) for f in sorted(os.listdir(ctx.root))
                  if f.endswith('.py')]
    findings = []
    for path in files:
        text = ctx.read(path)
        for m in _SILENT_EXCEPT_RE.finditer(text):
            line = _lineno(text, m.start())
            findings.append(ctx.finding(
                'silent-except', path, line,
                'silent exception swallow — log it, retry it, or waive '
                'with a reason'))
    return findings


# ---- fp32-softmax (was tests/test_layers.py) --------------------------------

@rule('fp32-softmax', 'A',
      'layers must route softmax dtype through config.softmax_with_policy; '
      'a hard-coded fp32 upcast next to a softmax bypasses '
      'set_softmax_dtype (config.py is the one allowed location)')
def fp32_softmax(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for path in ctx.source_files(_PACKAGE, 'layers'):
        if os.path.basename(path) == 'config.py':
            continue
        for lineno, line in enumerate(ctx.read(path).splitlines(), 1):
            if 'softmax(' in line and 'float32' in line:
                findings.append(ctx.finding(
                    'fp32-softmax', path, lineno,
                    'hard-coded fp32 softmax outside the policy module '
                    '(use timm_tpu.layers.softmax_with_policy)'))
    return findings


# ---- donation-declared (was tests/test_sharding.py) -------------------------

_JIT_RE = re.compile(r'(?:jax|nnx)\.jit\s*\(')
_DONATION_WAIVERS = ('no-donate:', 'timm-tpu-lint: disable=donation-declared')


@rule('donation-declared', 'A',
      'every jax.jit/nnx.jit call in timm_tpu/task/ declares donate_argnums '
      'or carries an explicit `# no-donate: <reason>` — the PERF.md item-3a '
      'regression (donation landed in bench only) cannot silently return')
def donation_declared(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for path in ctx.source_files(_PACKAGE, 'task'):
        lines = ctx.read(path).splitlines()
        for i, line in enumerate(lines):
            if not _JIT_RE.search(line.split('#')[0]):
                continue
            window = '\n'.join(lines[max(0, i - 3):i + 12])
            if ('donate_argnums' in window
                    or any(w in window for w in _DONATION_WAIVERS)):
                continue
            findings.append(ctx.finding(
                'donation-declared', path, i + 1,
                f'jit call without donate_argnums or a `# no-donate: '
                f'<reason>` comment: {line.strip()}'))
    return findings


# ---- kernel-registered (was tests/test_kernels.py) --------------------------

@rule('kernel-registered', 'A',
      'each .py in timm_tpu/kernels/ registers a KernelSpec whose `module` '
      'names it, or opens with `# no-kernel-registry: <reason>` in its '
      'first 5 lines')
def kernel_registered(ctx: AnalysisContext) -> List[Finding]:
    from ..kernels import registry as kreg
    kreg.ensure_registered()
    registered = {spec.module for spec in kreg.all_specs()}
    findings = []
    for path in ctx.source_files(_PACKAGE, 'kernels'):
        stem = os.path.splitext(os.path.basename(path))[0]
        if f'{_PACKAGE}.kernels.{stem}' in registered:
            continue
        pragmas = ctx.pragmas(path)
        reason = pragmas.waiver_for('kernel-registered')
        if reason:
            continue
        findings.append(ctx.finding(
            'kernel-registered', path, 1,
            f'{stem}.py defines no registered kernel and carries no '
            f'`# no-kernel-registry: <reason>` waiver '
            f'(registered modules: {sorted(registered)})'))
    return findings


# ---- partition-rules (was tests/test_sharding.py, 2 tests) ------------------

@rule('partition-rules', 'A',
      'the default rule table stays disjoint + exhaustive over every swept '
      'family (each param path matches exactly one non-catch-all rule; the '
      'tier-1 smoke covers the zoo smoke set, the CLI run all ~51), and '
      'under tp>1 every model-axis rule shards at least one real param and '
      'the conv rules place real hierarchical kernels',
      needs_devices=4)
def partition_rules(ctx: AnalysisContext) -> List[Finding]:
    from flax import nnx

    import timm_tpu
    from ..parallel import (
        create_mesh, default_partition_rules, match_rule, path_specs,
    )
    from ..utils.serialization import flatten_pytree
    from .zoo import family_representative

    findings: List[Finding] = []
    rules = default_partition_rules()
    specific, catchall = rules[:-1], rules[-1]
    if catchall.pattern != '.*':
        findings.append(Finding('partition-rules', 'parallel/rules', 0,
                                'last rule is not the catch-all'))
        return findings

    def paths_for(model_name, **kwargs):
        model = timm_tpu.create_model(model_name, **kwargs)
        return flatten_pytree(nnx.state(model, nnx.Param))

    def abstract_paths_for(model_name):
        # nnx.eval_shape constructs without allocating arrays, so sweeping
        # every family stays milliseconds per family
        model = nnx.eval_shape(
            lambda: timm_tpu.create_model(model_name, num_classes=10))
        return flatten_pytree(nnx.state(model, nnx.Param))

    # disjoint + exhaustive over the swept families: first-match-wins never
    # has to disambiguate. zoo_families=None (the CLI path) sweeps all
    # registered families; the tier-1 fixture injects the smoke subset.
    for module in (ctx.zoo_families or timm_tpu.list_modules()):
        try:
            name, _ = family_representative(module)
            paths = abstract_paths_for(name)
        except Exception:
            continue  # a family that cannot construct is zoo-abstract-trace's finding
        for path in paths:
            n = sum(1 for r in specific if r.matches(path))
            if n != 1:
                findings.append(Finding(
                    'partition-rules', f'{name}:{path}', 0,
                    f'matched {n} non-catch-all rules (expected exactly 1)'))

    # sized-model exhaustiveness spot check on a real (non-test-size) config
    for path in abstract_paths_for('vit_tiny_patch16_224'):
        n = sum(1 for r in specific if r.matches(path))
        if n != 1:
            findings.append(Finding(
                'partition-rules', f'vit_tiny_patch16_224:{path}', 0,
                f'matched {n} non-catch-all rules (expected exactly 1)'))

    # tp exercise: each of the four model-axis rules shards >=1 real param,
    # and the tp kernels also carry fsdp on the other dim (2-D sharding)
    mesh = create_mesh(fsdp=2, tp=2)
    paths = paths_for('test_vit', num_classes=10, img_size=32)
    specs = path_specs(paths, mesh)
    by_rule: Dict[str, List[str]] = {}
    for path in paths:
        _, r = match_rule(path, rules)
        by_rule.setdefault(r.name, []).append(path)
    for rname in ('attn-qkv', 'attn-out', 'mlp-fc1', 'mlp-fc2'):
        hit = [p for p in by_rule.get(rname, ())
               if any(ax == 'model' for ax in specs[p])]
        if not hit:
            findings.append(Finding(
                'partition-rules', f'rule:{rname}', 0,
                'tp rule not exercised by any test_vit param '
                '(dead weight that would silently rot)'))
    qkv = tuple(specs.get('blocks.0.attn.qkv.kernel', ()))
    if 'model' not in qkv or 'fsdp' not in qkv:
        findings.append(Finding(
            'partition-rules', 'blocks.0.attn.qkv.kernel', 0,
            f'tp kernel not 2-D sharded (got spec {qkv})'))

    # conv exercise on the same real 2x2 mesh: a hierarchical family's large
    # conv kernels shard their OUT-CHANNEL dim over fsdp, depthwise kernels
    # replicate, and its NHWC MLP Linears (1x1 convs) still pick up tp
    cpaths = paths_for('test_convnext', num_classes=10)
    cspecs = path_specs(cpaths, mesh)
    large_conv = [p for p in cpaths
                  if p.endswith('.kernel') and len(cpaths[p].shape) == 4
                  and cpaths[p].shape[-2] > 1
                  and int(np.prod(cpaths[p].shape)) >= 1024]
    if not any(tuple(cspecs[p])[-1:] == ('fsdp',) for p in large_conv):
        findings.append(Finding(
            'partition-rules', 'test_convnext:conv-out', 0,
            f'no large conv kernel sharded fsdp on its out-channel dim '
            f'(candidates: {large_conv[:4]})'))
    dw = [p for p in cpaths
          if p.endswith('.kernel') and len(cpaths[p].shape) == 4
          and cpaths[p].shape[-2] == 1]
    bad_dw = [p for p in dw if tuple(cspecs[p]) != ()]
    if not dw or bad_dw:
        findings.append(Finding(
            'partition-rules', 'test_convnext:depthwise', 0,
            f'depthwise conv kernels must replicate (violations: {bad_dw[:4]}, '
            f'found {len(dw)} dw kernels)'))
    if not any('model' in tuple(cspecs[p]) for p in cpaths
               if '.mlp.' in p and p.endswith('.kernel')):
        findings.append(Finding(
            'partition-rules', 'test_convnext:mlp-tp', 0,
            'no convnext MLP kernel carries the model axis — the NHWC '
            '1x1-conv Linears should reuse the attention-era tp rules'))
    return findings


# ---- host-sync + traced-branch (new AST sweeps) -----------------------------

def _is_jit_attr(node) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == 'jit'


def _has_jit_decorator(fn) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _is_jit_attr(target):
            return True
        if (isinstance(dec, ast.Call) and dec.args
                and _is_jit_attr(dec.args[0])):
            return True  # @partial(jax.jit, ...)
    return False


def _scoped_children(node):
    """(defs, other_nodes) whose nearest enclosing scope is `node` — the
    walk stops at nested function/class boundaries."""
    defs, others = [], []
    stack = list(ast.iter_child_nodes(node))
    while stack:
        ch = stack.pop()
        if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            defs.append(ch)
        else:
            others.append(ch)
            stack.extend(ast.iter_child_nodes(ch))
    return defs, others


def _jitted_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    """Function defs that are jit boundaries: decorated with *.jit (possibly
    through functools.partial), or passed by name to a jax.jit/nnx.jit call.
    Names resolve lexically — `jax.jit(step)` binds to the `step` visible
    from the call site, so a jitted inner function never implicates an
    outer method that happens to share its name."""
    out: List[ast.FunctionDef] = []
    seen: Set[int] = set()

    def flag(fn) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append(fn)

    def visit(node, env: Dict[str, ast.FunctionDef]) -> None:
        defs, others = _scoped_children(node)
        env = dict(env)
        env.update({d.name: d for d in defs
                    if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))})
        for o in others:
            if (isinstance(o, ast.Call) and _is_jit_attr(o.func)
                    and o.args and isinstance(o.args[0], ast.Name)
                    and o.args[0].id in env):
                flag(env[o.args[0].id])
        for d in defs:
            if (isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and _has_jit_decorator(d)):
                flag(d)
            visit(d, env)

    visit(tree, {})
    return sorted(out, key=lambda f: f.lineno)


def _param_names(fn: ast.FunctionDef) -> Set[str]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    return {n for n in names if n not in ('self', 'cls')}


_HOST_SYNC_NP_CALLS = {'asarray', 'array'}


def _host_sync_hits(fn: ast.FunctionDef) -> Iterable[ast.Call]:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == 'item':
            yield node
        elif (isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name)
              and f.value.id in ('np', 'numpy')
              and f.attr in _HOST_SYNC_NP_CALLS):
            yield node
        elif (isinstance(f, ast.Name) and f.id in ('float', 'int')
              and node.args
              and not isinstance(node.args[0], ast.Constant)):
            yield node


@rule('host-sync', 'A',
      'no host-synchronizing call (`.item()`, `np.asarray`/`np.array`, '
      '`float()`/`int()` on a non-literal) inside a jitted function body — '
      'under jit these either fail on tracers or force a device sync')
def host_sync(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for path in ctx.walk_files(_PACKAGE):
        tree = ctx.ast_of(path)
        if tree is None:
            continue
        for fn in _jitted_functions(tree):
            for call in _host_sync_hits(fn):
                findings.append(ctx.finding(
                    'host-sync', path, call.lineno,
                    f'host-sync call inside jitted `{fn.name}` '
                    f'(traced values cannot leave the device here)'))
    return findings


_STATIC_ATTRS = ('shape', 'ndim', 'dtype', 'size')
_STATIC_CALLS = ('len', 'isinstance', 'getattr', 'hasattr', 'callable')


def _hazardous_params(test: ast.expr, params: Set[str]) -> Set[str]:
    """Param names whose runtime VALUE the test consults. Static uses branch
    at trace time and are skipped: `x is None`, `x.shape`/`.ndim`/`.dtype`/
    `.size`, `len(x)`, `isinstance(x, ...)`."""
    hazards: Set[str] = set()

    class _V(ast.NodeVisitor):
        def visit_Attribute(self, node):
            if (isinstance(node.value, ast.Name)
                    and node.attr in _STATIC_ATTRS):
                return
            self.generic_visit(node)

        def visit_Call(self, node):
            if isinstance(node.func, ast.Name) and node.func.id in _STATIC_CALLS:
                return
            self.generic_visit(node)

        def visit_Compare(self, node):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return
            self.generic_visit(node)

        def visit_Name(self, node):
            if node.id in params:
                hazards.add(node.id)

    _V().visit(test)
    return hazards


def _branch_hits(fn: ast.FunctionDef) -> Iterable[ast.stmt]:
    params = _param_names(fn)
    for node in ast.walk(fn):
        if (isinstance(node, (ast.If, ast.While))
                and _hazardous_params(node.test, params)):
            yield node


@rule('traced-branch', 'A',
      'no Python `if`/`while` on a traced argument value inside a jitted '
      'function — the branch freezes at trace time (or raises '
      'TracerBoolConversionError); use lax.cond/jnp.where')
def traced_branch(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for path in ctx.walk_files(_PACKAGE):
        tree = ctx.ast_of(path)
        if tree is None:
            continue
        for fn in _jitted_functions(tree):
            for stmt in _branch_hits(fn):
                findings.append(ctx.finding(
                    'traced-branch', path, stmt.lineno,
                    f'Python branch on a traced argument inside jitted '
                    f'`{fn.name}` — this freezes at trace time; use '
                    f'lax.cond / jnp.where'))
    return findings


# ---- process-zero-io --------------------------------------------------------

_RANK_NAMES = ('rank', 'local_rank', 'process_index', 'process_id')


def _mentions_rank(node: ast.expr) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _RANK_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _RANK_NAMES:
            return True
    return False


def _is_primary_guard(test: ast.expr) -> bool:
    """True when an `if` test gates on the primary process: a call to
    `is_primary(...)`, or a comparison of a rank/process_index value
    against 0 (`rank == 0`, `jax.process_index() == 0`, ...)."""
    for n in ast.walk(test):
        if isinstance(n, ast.Call):
            f = n.func
            if ((isinstance(f, ast.Name) and f.id == 'is_primary')
                    or (isinstance(f, ast.Attribute) and f.attr == 'is_primary')):
                return True
        if isinstance(n, ast.Compare):
            sides = [n.left] + list(n.comparators)
            if (any(isinstance(s, ast.Constant) and s.value == 0 for s in sides)
                    and any(_mentions_rank(s) for s in sides)):
                return True
    return False


def _open_for_write(node: ast.Call) -> bool:
    if not (isinstance(node.func, ast.Name) and node.func.id == 'open'):
        return False
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == 'mode':
            mode = kw.value
    return (isinstance(mode, ast.Constant) and isinstance(mode.value, str)
            and any(c in mode.value for c in 'wax'))


def _unguarded_writes(tree: ast.Module) -> Iterable[ast.Call]:
    def visit(node, guarded: bool):
        if isinstance(node, ast.If) and _is_primary_guard(node.test):
            # the else-branch of a primary guard is explicitly NOT primary
            for ch in node.body:
                visit(ch, True)
            for ch in node.orelse:
                visit(ch, guarded)
            return
        if isinstance(node, ast.Call) and _open_for_write(node) and not guarded:
            yield_list.append(node)
        for ch in ast.iter_child_nodes(node):
            visit(ch, guarded)

    yield_list: List[ast.Call] = []
    visit(tree, False)
    return yield_list


@rule('process-zero-io', 'A',
      'top-level driver scripts write non-shard files only on the primary '
      'process: every open-for-write sits under an `is_primary()` / '
      '`rank == 0` guard or carries a waiver — on a pod, N hosts racing one '
      'summary/args/results file corrupt it (per-process shard writes live '
      'in the durable library, not in drivers)')
def process_zero_io(ctx: AnalysisContext) -> List[Finding]:
    pkg_dir = ctx.source_dir(_PACKAGE)
    if pkg_dir != ctx.root:
        files = [os.path.join(ctx.root, f) for f in sorted(os.listdir(ctx.root))
                 if f.endswith('.py')]
    else:
        # fixture layout: the flat planted-violation directory IS the root
        files = ctx.walk_files()
    findings = []
    for path in files:
        tree = ctx.ast_of(path)
        if tree is None:
            continue
        for call in _unguarded_writes(tree):
            findings.append(ctx.finding(
                'process-zero-io', path, call.lineno,
                'file write outside an `is_primary()` / `rank == 0` guard — '
                'every pod host would race this write; guard it or waive '
                'with `# timm-tpu-lint: disable=process-zero-io <reason>`'))
    return findings


# ---- layering ---------------------------------------------------------------

_TOOL_PACKAGES = ('timm_tpu.perfbudget', 'timm_tpu.analysis', 'timm_tpu.autotune')
_PROGRAM_DIRS = ('task', 'models', 'layers', 'data', 'optim', 'loss', 'scheduler',
                 'parallel', 'kernels', 'utils')
_PROGRAM_SCRIPTS = ('train.py', 'validate.py', 'inference.py')


def _import_nodes(tree: ast.Module, module_level: bool) -> Iterable[ast.stmt]:
    """The tree's import statements; with `module_level`, only those that run
    when the module is imported (not under a `def` or a lambda)."""
    stack: List[ast.AST] = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            yield node
        elif not (module_level and isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))):
            stack.extend(ast.iter_child_nodes(node))


def _imported_names(ctx: AnalysisContext, path: str, node: ast.stmt) -> List[str]:
    """Dotted names the statement may import, a relative `from` resolved
    against the file's package."""
    if isinstance(node, ast.Import):
        return [a.name for a in node.names]
    base = node.module or ''
    if node.level:
        package = os.path.dirname(ctx.rel(path)).split(os.sep)
        package = package[:len(package) - (node.level - 1)]
        base = '.'.join(package + ([node.module] if node.module else []))
    return [base] + [f'{base}.{a.name}' for a in node.names]


@rule('layering', 'A',
      'the program does not import its CPU tools: no module under timm_tpu/'
      '{task,models,layers,data,optim,loss,scheduler,parallel,kernels,utils} '
      'and none of train.py / validate.py / inference.py imports '
      'timm_tpu.perfbudget, timm_tpu.analysis or timm_tpu.autotune at module '
      'level (what a benchmark cell imports is what the program is), and '
      'timm_tpu/perfbudget imports no timm_tpu.autotune at any level (the '
      'arrows read autotune -> perfbudget.probe, analysis -> perfbudget.probe)')
def layering(ctx: AnalysisContext) -> List[Finding]:
    if ctx.source_dir(_PACKAGE) != ctx.root:
        program = [f for d in _PROGRAM_DIRS if os.path.isdir(os.path.join(ctx.root, _PACKAGE, d))
                   for f in ctx.walk_files(_PACKAGE, d)]
        program += [os.path.join(ctx.root, f) for f in _PROGRAM_SCRIPTS]
        perfbudget = ctx.walk_files(_PACKAGE, 'perfbudget')
    else:
        # fixture layout: the flat planted-violation directory is program code
        program, perfbudget = ctx.walk_files(), []
    findings = []
    for files, module_level, banned, why in (
            (program, True, _TOOL_PACKAGES, 'the program imports a CPU tool at module level'),
            (perfbudget, False, ('timm_tpu.autotune',), 'perfbudget imports autotune, which imports perfbudget.probe')):
        for path in files:
            tree = ctx.ast_of(path)
            if tree is None:
                continue
            for node in _import_nodes(tree, module_level):
                hit = next((n for n in _imported_names(ctx, path, node) for b in banned
                            if n == b or n.startswith(b + '.')), None)
                if hit is not None:
                    findings.append(ctx.finding('layering', path, node.lineno, f'`{hit}`: {why}'))
    return findings


# ---- pragma-syntax ----------------------------------------------------------

@rule('pragma-syntax', 'A',
      'every `# timm-tpu-lint:` pragma and waiver shim parses and carries a '
      'reason — reasonless waivers waive nothing')
def pragma_syntax(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for path in ctx.walk_files(_PACKAGE):
        for lineno, msg in ctx.pragmas(path).malformed:
            findings.append(Finding('pragma-syntax', ctx.rel(path),
                                    lineno, msg))
    return findings
