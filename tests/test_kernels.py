"""Pallas kernel tests (interpret mode on CPU; native on TPU).

1. Flash-attention numerics (the original hand-written checks).
2. Registry behaviour (the every-module-registered-or-waived lint moved to
   timm_tpu/analysis, rule `kernel-registered`).
3. Auto-generated parity: one test per (kernel, declared regime case) pair,
   jitted kernel vs jitted XLA reference at the case's dry shapes.
4. Fused AdamW+EMA: 5 donated TrainingTask steps with fused_update=True must
   track the optax path leaf-for-leaf (params, EMA, full opt_state) within
   1e-6, for fp32 and bfloat16 first-moment state; a non-adamw optimizer is
   rejected at task construction.
5. Augment epilogue vs the PR-9 numpy oracle (the source of truth — the XLA
   program is only the A/B reference arm).
6. Win-or-delete harness: a parity-exact but deliberately slow toy kernel on
   its claimed backend gets `delete`, its fast twin gets `keep`, and a
   parity-broken kernel is deleted without being timed.
7. The perfbudget `kernels` probe stays within the checked-in budgets,
   including the fused-update one-pass bytes reduction.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from timm_tpu.kernels import harness, registry
from timm_tpu.kernels.flash_attention import _flash, flash_attention
from timm_tpu.layers.attention import _sdpa

pytestmark = pytest.mark.kernels


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def test_flash_matches_sdpa():
    B, H, N, D = 2, 2, 256, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    ref = _sdpa(q, k, v)
    out = _flash(q, k, v, None, D ** -0.5)
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_key_mask():
    B, H, N, D = 2, 2, 256, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    mask = jnp.asarray(np.random.RandomState(3).rand(B, N) > 0.3)
    ref = _sdpa(q, k, v, attn_mask=mask[:, None, None, :])
    out = flash_attention(q, k, v, mask=mask)
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_unaligned_seq():
    # N=197 exercises the pad-and-mask path
    B, H, N, D = 1, 2, 197, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    ref = _sdpa(q, k, v)
    out = _flash(q, k, v, None, D ** -0.5)
    assert out.shape == ref.shape
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_grads_match():
    B, H, N, D = 1, 2, 128, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    g1 = jax.grad(lambda q, k, v: (_flash(q, k, v, None, D ** -0.5) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (_sdpa(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 5e-2


# ---- 2. registry ------------------------------------------------------------
# The every-module-registered-or-waived lint is now the analysis rule
# `kernel-registered` (timm_tpu/analysis/source_rules.py); the
# `# no-kernel-registry: <reason>` waiver spelling is unchanged.


def test_registry_portfolio_and_dup_rejection():
    assert registry.kernel_names() == (
        'augment_epilogue', 'causal_flash_attention', 'flash_attention', 'fused_adamw')
    with pytest.raises(ValueError, match='already registered'):
        registry.register(registry.get('fused_adamw'))
    with pytest.raises(ValueError, match='regime is empty'):
        dataclasses.replace(registry.get('fused_adamw'), name='empty', cases=())


# ---- 3. auto-generated parity (one test per declared regime case) -----------

_PARITY_GRID = harness.parity_cases()


@pytest.mark.parametrize(
    'spec,case', _PARITY_GRID,
    ids=[f'{s.name}-{c.name}' for s, c in _PARITY_GRID])
def test_kernel_parity(spec, case):
    rec = harness.parity_check(spec, case)
    assert rec['ok'], (
        f"{rec['kernel']}/{rec['case']}: max abs err {rec['max_abs_err']:.3g} "
        f"> tol {rec['tol']:.3g}")


# ---- 4. fused AdamW+EMA through the donated TrainingTask step ---------------


class _TinyNet(nnx.Module):
    def __init__(self, rngs):
        self.fc1 = nnx.Linear(24, 48, rngs=rngs)
        self.fc2 = nnx.Linear(48, 10, rngs=rngs)
        self.num_classes = 10

    def __call__(self, x):
        return self.fc2(nnx.relu(self.fc1(x.reshape(x.shape[0], -1))))


def _run_adamw_arm(fused, mu_dtype, steps=5):
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask

    model = _TinyNet(nnx.Rngs(0))
    opt_kwargs = {'mu_dtype': mu_dtype} if mu_dtype else {}
    opt = create_optimizer_v2(model, opt='adamw', lr=0.01, weight_decay=0.05,
                              **opt_kwargs)
    task = ClassificationTask(model, optimizer=opt, fused_update=fused)
    task.setup_ema(decay=0.99)
    rng = np.random.RandomState(0)
    losses = []
    for i in range(steps):
        batch = {'input': jnp.asarray(rng.rand(8, 2, 2, 6), jnp.float32),
                 'target': jnp.asarray(rng.randint(0, 10, 8))}
        metrics = task.train_step(batch, lr=0.01, step=i)
        losses.append(float(metrics['loss']))
    return (losses, nnx.state(task.model, nnx.Param), task.ema_params,
            task.opt_state)


def _max_leaf_diff(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(leaves_a) == len(leaves_b)
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(leaves_a, leaves_b))


@pytest.mark.parametrize('mu_dtype', [None, 'bfloat16'],
                         ids=['fp32', 'mu_bf16'])
def test_fused_adamw_five_step_drift_vs_optax(mesh8, mu_dtype):
    """Acceptance: 5 donated train steps with fused_update=True track the
    optax path within 1e-6 — params, EMA tree, AND the full opt_state
    (mu/nu/counters), for fp32 and bfloat16 first-moment state."""
    l_ref, p_ref, e_ref, o_ref = _run_adamw_arm(False, mu_dtype)
    l_fus, p_fus, e_fus, o_fus = _run_adamw_arm(True, mu_dtype)
    assert np.allclose(l_ref, l_fus, atol=1e-6), (l_ref, l_fus)
    assert _max_leaf_diff(p_ref, p_fus) <= 1e-6
    assert _max_leaf_diff(e_ref, e_fus) <= 1e-6
    assert _max_leaf_diff(o_ref, o_fus) <= 1e-6


def test_fused_update_rejects_non_adamw(mesh8):
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask

    model = _TinyNet(nnx.Rngs(0))
    opt = create_optimizer_v2(model, opt='sgd', lr=0.01)
    with pytest.raises(ValueError, match='fused_adamw_args'):
        ClassificationTask(model, optimizer=opt, fused_update=True)


# ---- 5. augment epilogue vs the PR-9 numpy oracle ---------------------------

@pytest.mark.parametrize('case_kwargs', [
    dict(),                                  # mixup/cutmix + erase
    dict(with_mix=False),                    # eval-style erase + normalize
    dict(erase_k=2, batch=6, size=24),       # multiple erase boxes
], ids=['mix_erase', 'no_mix', 'two_boxes'])
def test_augment_epilogue_matches_numpy_oracle(case_kwargs):
    from timm_tpu.data.device_augment import augment_image_batch_np
    from timm_tpu.kernels.augment_epilogue import (
        _STATICS, _make_inputs, augment_image_batch_fused,
    )

    batch = _make_inputs(seed=3, **case_kwargs)['batch']
    x, y = jax.jit(
        functools.partial(augment_image_batch_fused, **_STATICS))(batch)
    xn, yn = augment_image_batch_np({k: np.asarray(v) for k, v in batch.items()},
                                    **_STATICS)
    assert float(np.abs(np.asarray(x) - xn).max()) <= 1e-6
    assert float(np.abs(np.asarray(y, np.float32)
                        - np.asarray(yn, np.float32)).max()) <= 1e-6


def test_augment_epilogue_out_of_regime_falls_back():
    """'pixel' erase mode is outside the declared regime: the fused twin must
    route through the XLA program bit-for-bit, not the kernel."""
    from timm_tpu.data.device_augment import augment_image_batch
    from timm_tpu.kernels.augment_epilogue import (
        _STATICS, _make_inputs, augment_epilogue_supported,
        augment_image_batch_fused,
    )

    batch = _make_inputs(seed=5)['batch']
    assert augment_epilogue_supported(batch, 'const')
    assert not augment_epilogue_supported(batch, 'pixel')
    batch = dict(batch, noise_epoch=jnp.asarray(0, jnp.int32),
                 noise_step=jnp.asarray(0, jnp.int32))
    kwargs = dict(_STATICS, re_mode='pixel', re_std=(0.2, 0.2, 0.2))
    x_f, y_f = jax.jit(functools.partial(augment_image_batch_fused, **kwargs))(batch)
    x_r, y_r = jax.jit(functools.partial(augment_image_batch, **kwargs))(batch)
    assert float(jnp.abs(x_f - x_r).max()) == 0.0
    assert float(jnp.abs(y_f - y_r).max()) == 0.0


# ---- 6. win-or-delete verdicts ----------------------------------------------


def _toy_specs():
    """Toy kernel/reference pair that claims the CURRENT backend, so the
    timed arm of `ab_verdict` actually runs in tier-1. The slow arm is
    parity-exact but drags a chain of 256x256 matmuls whose contribution is
    scaled to zero magnitude yet cannot be eliminated."""
    def make_inputs(seed=0, n=256):
        rng = np.random.default_rng(seed)
        return {'x': jnp.asarray(rng.standard_normal((n, n)), jnp.float32)}

    def fast(x):
        return x * 2.0 + 1.0

    def slow(x):
        acc = x
        eye = jnp.eye(x.shape[0], dtype=x.dtype)
        for _ in range(60):
            acc = acc @ eye
        return x * 2.0 + 1.0 + acc * 1e-30

    backend = jax.default_backend()
    losing = registry.KernelSpec(
        name='toy_losing', module=__name__,
        regime='nowhere (test fixture)', gate='win or delete',
        parity_tol=1e-6, kernel_fn=slow, reference_fn=fast,
        make_inputs=make_inputs,
        cases=(registry.KernelCase(name='only', dry=dict(n=256),
                                   live=dict(n=256)),),
        backends=(backend,))
    winning = dataclasses.replace(losing, name='toy_winning',
                                  kernel_fn=fast, reference_fn=slow)
    return losing, winning


def test_losing_kernel_gets_delete_winning_twin_keep():
    """The win-or-delete gate is executable: a parity-clean kernel that loses
    the timed A/B on its claimed backend is deleted; the fast twin (same
    regime, arms swapped) is kept. Neither spec is registered — the verdict
    machinery is exercised directly."""
    losing, winning = _toy_specs()
    rec = harness.ab_verdict(losing, steps=3)
    assert rec['parity_ok']
    assert rec['verdict'] == 'delete', rec
    assert 'loses to the XLA reference' in rec['reason']
    assert 'DELETE' in harness.format_verdict_line(rec)

    rec = harness.ab_verdict(winning, steps=3)
    assert rec['parity_ok'] and rec['verdict'] == 'keep', rec


def test_parity_broken_kernel_deleted_without_timing():
    losing, _ = _toy_specs()
    broken = dataclasses.replace(losing, name='toy_broken',
                                 kernel_fn=lambda x: x * 2.0 + 1.001)
    rec = harness.ab_verdict(broken, steps=1)
    assert rec['verdict'] == 'delete' and not rec['parity_ok']
    assert 'wrong beats slow' in rec['reason']
    assert 'cases' not in rec  # never timed


def test_portfolio_verdicts_pending_off_claimed_hardware():
    """The shipped portfolio claims TPU; in tier-1 (CPU) every verdict must
    be `pending` with parity measured — `run_kernel_ab`'s parity-and-gates arm
    (`live=False`)."""
    recs = harness.run_kernel_ab(live=False, steps=1)
    assert [r['kernel'] for r in recs] == sorted(r['kernel'] for r in recs)
    assert {r['kernel'] for r in recs} == set(registry.kernel_names())
    backend = jax.default_backend()
    for rec in recs:
        assert rec['parity_ok'], rec
        if backend in rec['backends_claimed']:
            assert rec['verdict'] in ('keep', 'delete')
        else:
            assert rec['verdict'] == 'pending'
            assert 'settles the gate' in rec['reason']
        line = harness.format_verdict_line(rec)
        assert rec['kernel'] in line and rec['verdict'].upper() in line


# ---- 7. perfbudget `kernels` probe ------------------------------------------


def test_kernels_probe_within_budgets():
    """The `kernels` probe metrics stay inside the checked-in bands, and the
    fused-update acceptance evidence holds: the kernel's analytic one-pass
    io bytes sit measurably below the compiled unfused chain's bytes
    accessed (refused silent improvement included — band policy)."""
    from timm_tpu.perfbudget import budgets as B
    from timm_tpu.perfbudget.probe import run_matrix

    measured = run_matrix(names=['kernels'])
    violations = B.compare_budgets(measured, B.load_budgets(),
                                   configs=['kernels'])
    assert not violations, B.format_violations(violations)
    m = measured['kernels']
    assert m['kernels_registered'] == len(registry.kernel_names())
    for name in registry.kernel_names():
        assert m[f'{name}_wins_bytes'], (
            f'{name}: io bytes {m[f"{name}_io_bytes"]} do not beat the '
            f'reference bytes accessed {m[f"{name}_ref_bytes_accessed"]}')
    assert m['fused_adamw_io_bytes'] < m['fused_adamw_ref_bytes_accessed']
