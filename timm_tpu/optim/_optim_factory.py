"""Optimizer registry + factory (reference: timm/optim/_optim_factory.py:58-1339).

Optimizers are optax gradient transformations wrapped in an `Optimizer` object
that (a) injects the per-step LR computed by the host-side scheduler,
(b) applies timm's param-group semantics as pytree masks (WD exclusion,
layer-decay lr scales), and (c) optionally applies 'cautious' update masking.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Set, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from flax import nnx

from ._param_groups import param_groups_layer_decay, param_groups_weight_decay

_logger = logging.getLogger(__name__)

__all__ = ['OptimInfo', 'OptimizerRegistry', 'Optimizer', 'create_optimizer_v2',
           'optimizer_kwargs', 'list_optimizers', 'get_optimizer_info']


@dataclass
class OptimInfo:
    """Optimizer metadata (reference _optim_factory.py:58)."""
    name: str
    opt_class: Callable  # factory(learning_rate=..., **opt_args) -> GradientTransformation
    description: str = ''
    has_eps: bool = True
    has_momentum: bool = False
    has_betas: bool = False
    num_betas: int = 2
    second_order: bool = False
    defaults: Optional[Dict[str, Any]] = None


def _cautious(tx: optax.GradientTransformation) -> optax.GradientTransformation:
    """'Cautious optimizer' wrapper: zero update components whose sign
    disagrees with the raw gradient (reference: caution flag in
    timm/optim/adamw.py etc., arXiv:2411.16085)."""

    def init(params):
        return tx.init(params)

    def update(grads, state, params=None, **extra):
        updates, state = tx.update(grads, state, params, **extra)

        def mask(u, g):
            if u is None or g is None:
                return u
            m = (u * g < 0).astype(u.dtype)  # optax updates are negative-gradient sense
            scale = m.size / jnp.maximum(m.sum(), 1.0)
            return u * m * scale
        updates = jax.tree.map(mask, updates, grads)
        return updates, state

    return optax.GradientTransformationExtraArgs(init, update)


def _lookahead(inner: optax.GradientTransformation, sync_period: int = 6,
               slow_step_size: float = 0.5) -> optax.GradientTransformation:
    """Lookahead (reference: timm/optim/lookahead.py:1-66) as a plain transform:
    slow weights live in optimizer state, so params keep their normal pytree
    shape (unlike optax.lookahead's paired params)."""

    def init(params):
        return (inner.init(params), jax.tree.map(jnp.asarray, params), jnp.zeros((), jnp.int32))

    def update(grads, state, params=None, **extra):
        inner_state, slow, count = state
        updates, inner_state = inner.update(grads, inner_state, params, **extra)
        count = count + 1
        is_sync = (count % sync_period) == 0

        def sync(u, p, s):
            fast_new = p + u
            target = s + slow_step_size * (fast_new - s)
            new_u = jnp.where(is_sync, target - p, u)
            new_s = jnp.where(is_sync, target, s)
            return new_u, new_s

        pairs = jax.tree.map(sync, updates, params, slow)
        updates = jax.tree.map(lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        slow = jax.tree.map(lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return updates, (inner_state, slow, count)

    return optax.GradientTransformationExtraArgs(init, update)


def _scale_by_tree(scales) -> optax.GradientTransformation:
    """Per-param lr scaling for layer decay."""

    def init(params):
        return optax.EmptyState()

    def update(updates, state, params=None, **extra):
        updates = jax.tree.map(lambda u, s: u * s, updates, scales)
        return updates, state

    return optax.GradientTransformationExtraArgs(init, update)


class Optimizer:
    """Bundles an optax tx with timm-style group semantics + LR injection.

    Usage inside a jitted step:
        updates, opt_state = optimizer.update(grads, opt_state, params, lr=lr)
        params = optax.apply_updates(params, updates)
    """

    def __init__(
            self,
            tx_factory: Callable[..., optax.GradientTransformation],
            lr: float,
            opt_args: Dict[str, Any],
            lr_scales=None,
            caution: bool = False,
            defaults: Optional[Dict[str, Any]] = None,
    ):
        self.defaults = dict(defaults or {}, lr=lr, **{k: v for k, v in opt_args.items() if isinstance(v, (int, float, str, bool, type(None)))})
        # only learning_rate is a dynamic (per-step injected) hyperparam
        import inspect
        sig_names, has_var_kw = [], False
        try:
            sig = inspect.signature(tx_factory)
            for pname, p in sig.parameters.items():
                if p.kind == inspect.Parameter.VAR_KEYWORD:
                    has_var_kw = True
                elif pname != 'learning_rate':
                    sig_names.append(pname)
        except (TypeError, ValueError):
            pass
        static = set(sig_names)
        if has_var_kw:
            static |= {k for k in opt_args if k != 'learning_rate'}
        static = sorted(static)
        inner = optax.inject_hyperparams(tx_factory, static_args=static)(learning_rate=lr, **opt_args)
        if caution:
            inner = _cautious(inner)
        if lr_scales is not None:
            inner = optax.chain(inner, _scale_by_tree(lr_scales))
        self.tx = inner
        self._has_lr_scales = lr_scales is not None
        self._caution = caution

    def init(self, params):
        return self.tx.init(params)

    def _find_hyperparams(self, state):
        # inject_hyperparams state may be nested under chain/caution wrappers
        if hasattr(state, 'hyperparams'):
            return state
        if isinstance(state, tuple) and not hasattr(state, '_fields'):
            for s in state:
                found = self._find_hyperparams(s)
                if found is not None:
                    return found
        return None

    def update(self, grads, state, params=None, lr=None):
        if lr is not None:
            hp_state = self._find_hyperparams(state)
            if hp_state is not None:
                hp_state.hyperparams['learning_rate'] = jnp.asarray(
                    lr, dtype=hp_state.hyperparams['learning_rate'].dtype)
        return self.tx.update(grads, state, params)


class OptimizerRegistry:
    """(reference _optim_factory.py:82)."""

    def __init__(self):
        self._optimizers: Dict[str, OptimInfo] = {}

    def register(self, info: OptimInfo):
        self._optimizers[info.name.lower()] = info

    def list_optimizers(self, filter: str = '', with_description: bool = False):
        import fnmatch
        names = sorted(self._optimizers)
        if filter:
            names = fnmatch.filter(names, filter)
        if with_description:
            return [(n, self._optimizers[n].description) for n in names]
        return names

    def get_optimizer_info(self, name: str) -> OptimInfo:
        name = name.lower()
        if name not in self._optimizers:
            raise ValueError(f'Optimizer {name} not found in registry')
        return self._optimizers[name]


def _sgdw(learning_rate, momentum=0.9, weight_decay=0.0, nesterov=False, mask=None):
    """SGD w/ decoupled weight decay (reference sgdw.py)."""
    steps = [optax.trace(decay=momentum, nesterov=nesterov)] if momentum else []
    if weight_decay:
        steps.append(optax.add_decayed_weights(weight_decay, mask=mask))
    steps.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*steps)


def _scale_by_rms_tf(decay: float, eps: float) -> optax.GradientTransformation:
    """eps-inside-sqrt RMS scaling for optax versions whose scale_by_rms has
    no eps_in_sqrt flag: nu ← decay·nu + (1-decay)·g²; u = g/√(nu+eps)."""

    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    def update(updates, nu, params=None, **extra):
        nu = jax.tree.map(lambda n, g: decay * n + (1 - decay) * (g * g), nu, updates)
        updates = jax.tree.map(lambda g, n: g * jax.lax.rsqrt(n + eps), updates, nu)
        return updates, nu

    return optax.GradientTransformationExtraArgs(init, update)


def _rmsprop_tf(learning_rate, alpha=0.9, eps=1e-10, momentum=0.9, weight_decay=0.0, mask=None):
    """TF1-behaviour RMSprop (reference rmsprop_tf.py: eps inside sqrt)."""
    import inspect
    if 'eps_in_sqrt' in inspect.signature(optax.scale_by_rms).parameters:
        steps = [optax.scale_by_rms(decay=alpha, eps=eps, eps_in_sqrt=True, bias_correction=False)]
    else:
        steps = [_scale_by_rms_tf(decay=alpha, eps=eps)]
    if weight_decay:
        steps.append(optax.add_decayed_weights(weight_decay, mask=mask))
    if momentum:
        steps.append(optax.trace(decay=momentum))
    steps.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*steps)


def _muon(learning_rate, weight_decay=0.0, momentum=0.95, beta1=0.9, beta2=0.95, eps=1e-8, mask=None):
    """Muon (Newton-Schulz orthogonalized momentum) for 2D params w/ AdamW
    fallback for others (reference muon.py:1-1056)."""
    return optax.contrib.muon(
        learning_rate=learning_rate,
        beta=momentum,
        weight_decay=weight_decay,
        weight_decay_mask=mask if mask is not None else True,
        adam_b1=beta1,
        adam_b2=beta2,
        adam_eps_root=0.0,
    )


def _lamb(learning_rate, b1=0.9, b2=0.999, eps=1e-6, weight_decay=0.0, mask=None, mu_dtype=None):
    if mu_dtype is None:
        return optax.lamb(learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay, mask=mask)
    # optax.lamb doesn't expose mu_dtype; rebuild its exact chain with the
    # first moment stored reduced (m reads/writes halve; v stays fp32)
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps, eps_root=0.0, mu_dtype=mu_dtype),
        optax.add_decayed_weights(weight_decay, mask),
        optax.scale_by_trust_ratio(),
        optax.scale_by_learning_rate(learning_rate),
    )


def _lars(learning_rate, momentum=0.9, weight_decay=0.0, trust_coefficient=0.001, mask=None):
    return optax.lars(
        learning_rate, weight_decay=weight_decay, weight_decay_mask=mask if mask is not None else True,
        trust_coefficient=trust_coefficient, momentum=momentum)


def _adafactor(learning_rate, eps=None, clipping_threshold=1.0, decay_rate=0.8, weight_decay=0.0, mask=None, min_dim_size_to_factor=32):
    return optax.adafactor(
        learning_rate=learning_rate,
        min_dim_size_to_factor=min_dim_size_to_factor,
        decay_rate=decay_rate,
        clipping_threshold=clipping_threshold,
        weight_decay_rate=weight_decay or None,
        weight_decay_mask=mask if mask is not None else True,
    )


def _default_registry() -> OptimizerRegistry:
    r = OptimizerRegistry()

    def wd_first(fn):
        return fn

    r.register(OptimInfo('sgd', partial(optax.sgd), 'SGD w/ Nesterov momentum', has_eps=False, has_momentum=True,
                         defaults={'nesterov': True}))
    r.register(OptimInfo('momentum', partial(optax.sgd), 'SGD w/ classical momentum', has_eps=False, has_momentum=True,
                         defaults={'nesterov': False}))
    r.register(OptimInfo('sgdw', _sgdw, 'SGD w/ decoupled weight decay', has_eps=False, has_momentum=True))
    r.register(OptimInfo('sgdp', _sgdw, 'SGDP (approx. via decoupled-WD SGD)', has_eps=False, has_momentum=True))
    r.register(OptimInfo('adam', optax.adam, 'Adam', has_betas=True))
    r.register(OptimInfo('adamw', optax.adamw, 'Adam w/ decoupled weight decay', has_betas=True))
    r.register(OptimInfo('adamp', optax.adamw, 'AdamP (approx. via AdamW)', has_betas=True))
    r.register(OptimInfo('nadam', optax.nadam, 'Adam w/ Nesterov momentum', has_betas=True))
    r.register(OptimInfo('nadamw', optax.nadamw, 'NAdamW (MLCommons algorithmic-efficiency)', has_betas=True))
    r.register(OptimInfo('radam', optax.radam, 'Rectified Adam', has_betas=True))
    r.register(OptimInfo('adamax', optax.adamax, 'Adamax (inf-norm Adam)', has_betas=True))
    r.register(OptimInfo('adabelief', optax.adabelief, 'AdaBelief', has_betas=True))
    r.register(OptimInfo('adadelta', optax.adadelta, 'Adadelta'))
    r.register(OptimInfo('adagrad', optax.adagrad, 'Adagrad'))
    r.register(OptimInfo('adafactor', _adafactor, 'Adafactor (memory-factored)', has_eps=False))
    r.register(OptimInfo('adafactorbv', _adafactor, 'Big-Vision Adafactor variant', has_eps=False,
                         defaults={'min_dim_size_to_factor': 32}))
    # not present in every optax release the container may ship; register
    # only what exists so one missing contrib optimizer can't break imports
    if hasattr(optax.contrib, 'adopt'):
        r.register(OptimInfo('adopt', optax.contrib.adopt, 'ADOPT - modified Adam', has_betas=True))
    if hasattr(optax, 'adan'):
        r.register(OptimInfo('adan', optax.adan, 'Adaptive Nesterov momentum', has_betas=True, num_betas=3))
    r.register(OptimInfo('lamb', _lamb, 'LAMB (layer-wise adaptation)', has_betas=True))
    r.register(OptimInfo('lars', _lars, 'LARS', has_eps=False, has_momentum=True))
    r.register(OptimInfo('lion', optax.lion, 'Lion (evolved sign momentum)', has_eps=False, has_betas=True))
    r.register(OptimInfo('lookahead', optax.sgd, 'placeholder; use lookahead_* prefix', has_eps=False))
    if hasattr(optax.contrib, 'muon'):
        r.register(OptimInfo('muon', _muon, 'Muon (Newton-Schulz orthogonalization, AdamW fallback)', has_momentum=True))
        r.register(OptimInfo('adamuon', _muon, 'AdaMuon alias (optax muon w/ adam fallback)', has_momentum=True))
        r.register(OptimInfo('nadamuon', _muon, 'NadaMuon alias (optax muon w/ adam fallback)', has_momentum=True))
    r.register(OptimInfo('novograd', optax.novograd, 'NovoGrad', has_betas=True))
    r.register(OptimInfo('nvnovograd', optax.novograd, 'NVIDIA NovoGrad alias', has_betas=True))
    r.register(OptimInfo('rmsprop', partial(optax.rmsprop, decay=0.9, momentum=0.9), 'RMSprop', has_momentum=True))
    r.register(OptimInfo('rmsproptf', _rmsprop_tf, 'TF1-behaviour RMSprop', has_momentum=True))
    r.register(OptimInfo('yogi', optax.yogi, 'Yogi', has_betas=True))
    r.register(OptimInfo('sm3', optax.sm3, 'SM3 (memory-efficient)', has_eps=False))
    from ._extra import laprop, madgrad, mars
    r.register(OptimInfo('madgrad', madgrad, 'MADGRAD (momentumized dual averaging)', has_momentum=True))
    r.register(OptimInfo('madgradw', partial(madgrad, decoupled_decay=True),
                         'MADGRAD w/ decoupled weight decay', has_momentum=True))
    r.register(OptimInfo('laprop', laprop, 'LaProp (decoupled momentum/adaptivity)', has_betas=True))
    r.register(OptimInfo('mars', mars, 'MARS (variance-reduced adaptive momentum)', has_betas=True))
    return r


default_registry = _default_registry()


def list_optimizers(filter: str = '', with_description: bool = False):
    return default_registry.list_optimizers(filter, with_description)


def get_optimizer_info(name: str) -> OptimInfo:
    return default_registry.get_optimizer_info(name)


def optimizer_kwargs(cfg) -> Dict[str, Any]:
    """argparse bridge (reference _optim_factory.py:1300)."""
    kwargs = dict(
        opt=cfg.opt,
        lr=cfg.lr,
        weight_decay=cfg.weight_decay,
        momentum=cfg.momentum,
    )
    if getattr(cfg, 'opt_eps', None) is not None:
        kwargs['eps'] = cfg.opt_eps
    if getattr(cfg, 'opt_betas', None) is not None:
        kwargs['betas'] = cfg.opt_betas
    if getattr(cfg, 'layer_decay', None) is not None:
        kwargs['layer_decay'] = cfg.layer_decay
    if getattr(cfg, 'layer_decay_min_scale', None) is not None:
        kwargs['layer_decay_min_scale'] = cfg.layer_decay_min_scale
    if getattr(cfg, 'opt_kwargs', None):
        kwargs.update(cfg.opt_kwargs)
    if getattr(cfg, 'opt_caution', False):
        kwargs['caution'] = True
    return kwargs


def create_optimizer_v2(
        model_or_params,
        opt: str = 'sgd',
        lr: Optional[float] = None,
        weight_decay: float = 0.0,
        momentum: float = 0.9,
        foreach: Optional[bool] = None,  # torch-ism, accepted and ignored
        filter_bias_and_bn: bool = True,
        layer_decay: Optional[float] = None,
        layer_decay_min_scale: float = 0.0,
        param_group_fn: Optional[Callable] = None,  # accepted for parity; masks built internally
        caution: bool = False,
        mu_dtype=None,
        **kwargs,
) -> Optimizer:
    """Create an Optimizer from a model (reference _optim_factory.py:1199-1298).

    Precedence mirrors the reference: layer_decay > plain weight-decay
    filtering. Returns an `Optimizer` whose state aligns with
    `nnx.state(model, nnx.Param)`.

    `mu_dtype` ('bfloat16' / dtype) stores the first moment (m) of the
    Adam-family optimizers (adam/adamw/nadamw/lamb/...) reduced, halving its
    HBM read+write traffic per step (~0.7 GB/step of ViT-B's 2.08 GB
    optimizer traffic, PERF.md §2 item 3); v stays fp32. Default None keeps
    fp32 state bit-for-bit.
    """
    is_model = isinstance(model_or_params, nnx.Module)
    lr_scales = None
    wd_mask = None
    if is_model:
        model = model_or_params
        if layer_decay is not None:
            lr_scales, wd_mask = param_groups_layer_decay(
                model, weight_decay=weight_decay, layer_decay=layer_decay,
                min_scale=layer_decay_min_scale)
        elif weight_decay and filter_bias_and_bn:
            wd_mask = param_groups_weight_decay(model, weight_decay=weight_decay)

    # split opt string: 'lookahead_adamw' etc.
    opt_split = opt.lower().split('_')
    opt_name = opt_split[-1]
    use_lookahead = len(opt_split) > 1 and opt_split[0] == 'lookahead'
    info = default_registry.get_optimizer_info(opt_name.replace('_', ''))

    opt_args: Dict[str, Any] = dict(info.defaults or {})
    if lr is None:
        lr = 1e-3
    betas = kwargs.pop('betas', None)
    eps = kwargs.pop('eps', None)
    if info.has_betas and betas is not None:
        opt_args.update(b1=betas[0], b2=betas[1])
        if info.num_betas == 3 and len(betas) > 2:
            opt_args['b3'] = betas[2]
    if info.has_eps and eps is not None:
        opt_args['eps'] = eps
    if info.has_momentum:
        opt_args['momentum'] = momentum
    if mu_dtype is not None:
        from ..layers.config import resolve_dtype_arg
        opt_args['mu_dtype'] = resolve_dtype_arg(mu_dtype)

    # weight decay plumbing: pass decay + mask where the factory supports it
    import inspect
    sig_params = None
    try:
        sig_params = set(inspect.signature(info.opt_class).parameters)
    except (TypeError, ValueError):
        pass
    if sig_params is not None:
        if 'weight_decay' in sig_params:
            opt_args['weight_decay'] = weight_decay
        elif 'weight_decay_rate' in sig_params:
            opt_args['weight_decay_rate'] = weight_decay or None
        if wd_mask is not None:
            if 'mask' in sig_params:
                opt_args['mask'] = wd_mask
            elif 'weight_decay_mask' in sig_params:
                opt_args['weight_decay_mask'] = wd_mask
        if 'nesterov' in sig_params and 'nesterov' in opt_args:
            pass
        if 'mu_dtype' in opt_args and 'mu_dtype' not in sig_params:
            _logger.warning(f'optimizer {opt_name!r} has no mu_dtype support; ignoring mu_dtype={mu_dtype}')
        # drop unsupported kwargs
        opt_args = {k: v for k, v in opt_args.items() if k in sig_params or k == 'learning_rate'}
    # user opt_kwargs passthrough
    for k, v in kwargs.items():
        if sig_params is None or k in sig_params:
            opt_args[k] = v

    tx_factory = info.opt_class
    # Coupled L2 for optimizers whose optax factory has no weight-decay param
    # (sgd/momentum/adam/nadam/radam/rmsprop/adabelief/...): torch applies WD by
    # adding wd*p to the gradient before the transform (reference
    # _optim_factory.py param-group defaults); without this the default
    # `train.py --weight-decay` silently trains unregularized.
    supports_wd = sig_params is not None and (
        'weight_decay' in sig_params or 'weight_decay_rate' in sig_params)
    if weight_decay and not supports_wd:
        base_l2 = tx_factory
        bound_l2 = dict(opt_args)
        opt_args = {}

        def tx_factory(learning_rate, _base=base_l2, _bound=bound_l2,
                       _wd=weight_decay, _mask=wd_mask):
            return optax.chain(
                optax.add_decayed_weights(_wd, mask=_mask),
                _base(learning_rate, **_bound),
            )

    if use_lookahead:
        base_factory = tx_factory
        bound_args = dict(opt_args)
        opt_args = {}

        def tx_factory(learning_rate, _base=base_factory, _bound=bound_args):
            return _lookahead(_base(learning_rate, **_bound), sync_period=6, slow_step_size=0.5)

    optimizer = Optimizer(
        tx_factory,
        lr=lr,
        opt_args=opt_args,
        lr_scales=lr_scales,
        caution=caution,
        defaults={'opt': opt, 'weight_decay': weight_decay},
    )
    return optimizer
