"""Training task abstraction (reference: timm/task/task.py:17-231).

The task owns the model, optimizer, EMA and — unlike the torch reference —
the **jitted train/eval step functions**. Design:

  * the train step is a FUNCTIONAL `jax.jit` over explicit state (params,
    non-param model state, optimizer state, EMA, sentinel), carried between
    steps as FLAT tuples of arrays: `_build_train_step` binds the model's own
    `Variable`s once, a step reads their arrays, calls, and writes the
    returned arrays back; no module graph is split, rebuilt or merged per
    step. The structure is fixed from the build on (as `graphdef` is). With
    **explicit `in_shardings`/`out_shardings` and `donate_argnums` for every
    state argument**: XLA aliases the donated input buffers to the matching
    outputs (params/AdamW m,v/EMA update in place — ~2 GB/step less HBM copy
    traffic for ViT-B, PERF.md §2 item 3a), and the sharding annotations are
    what make the aliasing legal (donation requires input and output
    placement to agree leaf-for-leaf).
  * placement comes from `parallel/sharding.py`: on a 1-axis data mesh every
    sharding is replicated (exact pre-FSDP behaviour); on a
    ``('data', 'fsdp')`` mesh large weights and their optimizer slots shard
    over 'fsdp' and GSPMD emits the gather/scatter collectives; on a
    ``('data', 'fsdp', 'model')`` mesh the attention/MLP kernels additionally
    shard heads/hidden over 'model' (Megatron split) and the models'
    activation constraints (parallel/constraints.py) keep the residual
    stream and block internals sharded inside the scanned step. The jit
    wiring below is axis-agnostic — the same in/out sharding trees carry
    1-, 2-, and 3-axis placements, and donation stays legal because the
    optimizer/EMA state inherits each param's spec leaf-for-leaf.
  * optimizer/EMA state is created ON-MESH via `jax.eval_shape` + jitted
    init with `out_shardings` — a replicated host copy of m/v never exists.
  * the reference's AMP scaler (utils/cuda.py:46) is unnecessary — bf16
    compute is native on TPU and fp32 master params are the default.
  * DDP wrap / no_sync (task.py:222, classification.py:64) have no analogue:
    the batch is sharded over the mesh batch axes and XLA emits the gradient
    all-reduce over ICI.
  * grad accumulation is ONE `jax.lax.scan` over stacked microbatches, so
    trace/compile cost is O(1) in `grad_accum_steps` (composing with the
    models' `block_scan`); `grad_accum_scan=False` keeps the legacy Python
    unroll for parity testing.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import nnx

from ..optim import Optimizer
from ..parallel import (
    build_opt_shardings, build_param_shardings, get_global_mesh, replicate_sharding,
)
from ..resilience import (
    NonFiniteSentinel, guard_enabled, new_sentinel_state, tree_all_finite,
    update_sentinel_state,
)
from ..utils import tracing
from ..utils.clip_grad import dispatch_clip_grad, global_grad_norm
from ..utils.model_ema import ModelEmaV3, ema_update
from ..utils.serialization import flatten_pytree, unflatten_into

_logger = logging.getLogger(__name__)

__all__ = ['TrainingTask']


class TrainingTask:
    def __init__(
            self,
            model: nnx.Module,
            optimizer: Optional[Optimizer] = None,
            mesh=None,
            grad_accum_steps: int = 1,
            grad_accum_scan: bool = True,
            clip_grad: Optional[float] = None,
            clip_mode: str = 'norm',
            mean=None,
            std=None,
            nonfinite_guard: Optional[bool] = None,
            nonfinite_tolerance: Optional[int] = None,
            partition_rules=None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh or get_global_mesh()
        self.grad_accum_steps = max(1, grad_accum_steps)
        self.grad_accum_scan = grad_accum_scan
        self.clip_grad = clip_grad
        self.clip_mode = clip_mode
        self.partition_rules = partition_rules
        # non-finite sentinel (resilience/sentinel.py): an all-finite reduction
        # over loss+grads fused into the jitted step; bad steps commit nothing
        # and K consecutive bad steps abort via NonFiniteError. Default on
        # (disable with nonfinite_guard=False or TIMM_TPU_NONFINITE_GUARD=0).
        self._nonfinite_guard = guard_enabled(nonfinite_guard)
        self.sentinel = NonFiniteSentinel(nonfinite_tolerance) if self._nonfinite_guard else None
        self._sentinel_state = self._new_sentinel_state() if self._nonfinite_guard else None
        # (step, nonfinite_count, nonfinite_total) of the newest step, until the
        # next train_step or drain() reads them: the host's read lags the dispatch
        self._unread = None
        # on-device input normalization, fused into the jitted step (the
        # reference normalizes on-GPU in PrefetchLoader, loader.py:124-159)
        if mean is not None:
            self._norm_mean = jnp.asarray(mean, jnp.float32).reshape(1, 1, 1, -1)
            self._norm_std = jnp.asarray(std if std is not None else 1.0, jnp.float32).reshape(1, 1, 1, -1)
        else:
            self._norm_mean = self._norm_std = None

        # placement: params by partition rule (all-replicated on a plain data
        # mesh, fsdp-sharded on a ('data','fsdp') mesh), everything else
        # (BN stats, RNG counters) replicated
        rep = replicate_sharding(self.mesh)
        params = nnx.state(model, nnx.Param)
        self._param_shardings = build_param_shardings(params, self.mesh, self.partition_rules)
        nnx.update(model, jax.device_put(params, self._param_shardings))
        other = nnx.state(model, nnx.Not(nnx.Param))
        if jax.tree.leaves(other):
            nnx.update(model, jax.device_put(other, rep))
        if self.optimizer is not None:
            params = nnx.state(model, nnx.Param)
            self._opt_shardings, _ = build_opt_shardings(
                self.optimizer, params, self.mesh, self.partition_rules)
            try:
                # abstract init: m/v materialize directly on their owning
                # devices; no replicated copy of the optimizer state exists
                # (no-donate: init consumes fresh params, there is no prior
                # state whose buffers an output could alias)
                self.opt_state = jax.jit(
                    self.optimizer.init, out_shardings=self._opt_shardings)(params)
            except Exception as e:
                _logger.warning(f'sharded optimizer init failed ({e!r}); '
                                'falling back to eager init + device_put')
                self.opt_state = jax.device_put(self.optimizer.init(params), self._opt_shardings)
        else:
            self.opt_state = None
            self._opt_shardings = None

        self.ema: Optional[ModelEmaV3] = None
        self.ema_params = None
        self._train_step = None  # the jitted step; its binding to the model's Variables goes with it
        self._step_vars = None
        self._eval_step = None
        self.compiled = False  # jit is always on; flag kept for API parity

    # -- overridables --------------------------------------------------------
    def loss_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        """Return (loss, output). Subclasses implement the objective."""
        raise NotImplementedError

    def eval_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        return model(batch['input'])

    def step_counters(self, output) -> Dict[str, Any]:
        """Counters of `loss_forward`'s output that ride in the step's metrics
        (`tracing.device_counter` names -> scalars); over accumulated
        microbatches they add, a `*_max` takes the largest."""
        return {}

    def normalize_input(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        if self._norm_mean is None or 'input' not in batch:
            return batch
        x = batch['input']
        x = (x.astype(jnp.float32) - self._norm_mean) / self._norm_std
        return dict(batch, input=x.astype(batch['input'].dtype)
                    if batch['input'].dtype != jnp.float32 else x)

    # -- optimizer / EMA state -----------------------------------------------
    # Between steps both live as the flat tuples of arrays the jitted step takes
    # and returns; the structured tree is built on read and flattened on
    # assignment (None flattens to no leaves and comes back as None).
    @property
    def opt_state(self):
        return jax.tree.unflatten(self._opt_treedef, self._opt_leaves)

    @opt_state.setter
    def opt_state(self, tree):
        leaves, self._opt_treedef = jax.tree.flatten(tree)
        self._opt_leaves = tuple(leaves)

    @property
    def ema_params(self):
        return jax.tree.unflatten(self._ema_treedef, self._ema_leaves)

    @ema_params.setter
    def ema_params(self, tree):
        leaves, self._ema_treedef = jax.tree.flatten(tree)
        self._ema_leaves = tuple(leaves)

    # -- setup ---------------------------------------------------------------
    def setup_ema(self, decay: float = 0.9999, warmup: bool = False, **kwargs):
        """(reference task.py:110). The EMA tree is a deep COPY placed like the
        params (donation aliases param and EMA buffers independently; sharing
        storage with the live params would alias one buffer twice)."""
        self.drain()
        self.ema = ModelEmaV3(decay=decay, use_warmup=warmup, **kwargs)
        self.ema_params = jax.device_put(
            jax.tree.map(lambda p: jnp.array(p, copy=True), nnx.state(self.model, nnx.Param)),
            self._param_shardings)
        self._train_step = self._step_vars = None  # EMA presence is baked into the jitted step; rebuild

    def set_block_scan(self, enable: bool = True) -> bool:
        """Toggle scan-over-layers execution on the owned model (and its
        sync'd EMA clone, which inherits the flag at sync time). The jitted
        steps are invalidated explicitly: block_scan is a static model attr,
        so a stale traced step would silently keep the old execution mode on
        flax versions whose jit cache ignores attr-only graphdef changes."""
        if not hasattr(self.model, 'set_block_scan'):
            return False
        self.drain()
        self.model.set_block_scan(enable)
        self._train_step = self._step_vars = None
        self._eval_step = None
        return True

    def set_grad_accum(self, steps: int) -> bool:
        """Rescale gradient accumulation (elastic resume holds
        global_batch = loader_batch x accum invariant across topology
        changes). `accum` is captured inside the jitted train step's
        accumulation scan, so the step is invalidated exactly like
        set_block_scan; returns True when the value actually changed."""
        steps = max(1, int(steps))
        if steps == self.grad_accum_steps:
            return False
        self.drain()
        self.grad_accum_steps = steps
        self._train_step = self._step_vars = None
        return True

    def compile(self, backend: str = ''):
        self.compiled = True  # parity no-op; the steps are always jitted

    def prepare_distributed(self):
        return self  # sharded-batch DP needs no wrapping; parity (classification.py:64)

    # -- jitted steps ----------------------------------------------------------
    def _split_model(self) -> Tuple[Any, Any, Any]:
        return nnx.split(self.model, nnx.Param, ...)

    def _build_train_step(self):
        if self.optimizer is None:
            raise RuntimeError('TrainingTask.train_step requires an optimizer')
        optimizer = self.optimizer
        accum = self.grad_accum_steps
        accum_scan = self.grad_accum_scan
        clip_grad, clip_mode = self.clip_grad, self.clip_mode
        has_ema = self.ema_params is not None
        guard = self._nonfinite_guard
        loss_forward = self.loss_forward
        step_counters = self.step_counters
        normalize_input = self.normalize_input

        # bind once: the model's own Variables in flatten order, and the
        # treedefs that turn the step's flat tuples back into the trees the
        # mathematics is written on (inside the trace only)
        self.model.train()
        graphdef, params, rest = self._split_model()
        is_var = lambda x: isinstance(x, nnx.Variable)  # noqa: E731
        self._step_vars = (jax.tree.leaves(params, is_leaf=is_var), jax.tree.leaves(rest, is_leaf=is_var))
        tracing.count('task.state_binds')
        defs = (jax.tree.structure(params), jax.tree.structure(rest), self._opt_treedef, self._ema_treedef)

        rep = replicate_sharding(self.mesh)
        # params / optimizer / EMA: one sharding a leaf, in the state's flatten
        # order. Elsewhere a single sharding broadcasts over a whole subtree
        # (non-param state, metrics). The batch position is None =
        # inherit from the argument: parallel.shard_batch is the explicit
        # placement mechanism, and eval/debug batches smaller than the mesh
        # batch-shard count stay legal (they run replicated).
        param_sh = tuple(jax.tree.leaves(self._param_shardings))
        opt_sh = tuple(jax.tree.leaves(self._opt_shardings))
        ema_sh = param_sh if has_ema else rep
        if (len(self._step_vars[0]), len(self._step_vars[1]), len(param_sh), len(opt_sh)) != tuple(
                d.num_leaves for d in (defs[0], defs[1], defs[0], defs[2])):
            raise ValueError('the step carries one array a Variable and one sharding a leaf, in flatten order')

        def loss_and_state(params, rest, mb):
            """Merge → loss_forward → re-split, so grads flow w.r.t. params
            while BN-stat / RNG-counter mutations are carried functionally."""
            # copy=True: merge otherwise re-uses the Variables of `rest`, which
            # were created at the enclosing jit/scan trace level and may not be
            # mutated (RNG counters, BN stats) under value_and_grad
            m = nnx.merge(graphdef, params, rest, copy=True)
            loss, output = loss_forward(m, mb)
            _, _, new_rest = nnx.split(m, nnx.Param, ...)
            return loss.astype(jnp.float32), (new_rest, step_counters(output))

        def merge_counters(stacked):
            return {k: v.max(0) if k.endswith('_max') else v.sum(0) for k, v in stacked.items()}

        grad_fn = jax.value_and_grad(loss_and_state, has_aux=True)

        def microbatch_split(batch):
            """[accum*mb, ...] → [accum, mb, ...]; scalar leaves (e.g. NaFlex
            seq_len metadata) broadcast to every microbatch instead."""
            return jax.tree.map(
                lambda x: x.reshape(accum, -1, *x.shape[1:]) if getattr(x, 'ndim', 0) >= 1 else x,
                batch)

        def train_step(param_leaves, rest_leaves, opt_leaves, ema_leaves, sentinel_state, batch, lr, ema_decay):
            params, rest, opt_state, ema_params = map(
                jax.tree.unflatten, defs, (param_leaves, rest_leaves, opt_leaves, ema_leaves))
            with tracing.scope('step.input'):
                batch = normalize_input(batch)

            if accum > 1 and accum_scan:
                # ONE lax.scan over stacked microbatches: trace/compile cost
                # no longer scales with grad_accum_steps. Array leaves ride
                # the scan xs; scalar leaves stay in the carry-free closure.
                flat, treedef = jax.tree_util.tree_flatten(microbatch_split(batch))
                scan_idx = [i for i, leaf in enumerate(flat) if getattr(leaf, 'ndim', 0) >= 1]
                xs = [flat[i] for i in scan_idx]

                def rebuild(scanned):
                    leaves = list(flat)
                    for i, leaf in zip(scan_idx, scanned):
                        leaves[i] = leaf
                    return jax.tree_util.tree_unflatten(treedef, leaves)

                def body(carry, scanned):
                    grads_acc, loss_acc, r = carry
                    (l_i, (new_r, c_i)), g_i = grad_fn(params, r, rebuild(scanned))
                    return (jax.tree.map(jnp.add, grads_acc, g_i), loss_acc + l_i, new_r), c_i

                init = (jax.tree.map(jnp.zeros_like, params), jnp.zeros((), jnp.float32), rest)
                (grads, loss, new_rest), counters = jax.lax.scan(body, init, xs)
                counters = merge_counters(counters)
                loss = loss / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
            elif accum > 1:
                # legacy unrolled accumulation (grad_accum_scan=False): kept
                # for trace-cost A/B and scan-vs-unroll parity tests
                microbatches = microbatch_split(batch)
                loss = jnp.zeros((), jnp.float32)
                grads, r, each = None, rest, []
                for i in range(accum):
                    mb = jax.tree.map(
                        lambda x: x[i] if getattr(x, 'ndim', 0) >= 2 else x, microbatches)
                    (l_i, (r, c_i)), g_i = grad_fn(params, r, mb)
                    each.append(c_i)
                    loss = loss + l_i
                    grads = g_i if grads is None else jax.tree.map(jnp.add, grads, g_i)
                new_rest = r
                counters = merge_counters(jax.tree.map(lambda *xs: jnp.stack(xs), *each))
                loss = loss / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
            else:
                (loss, (new_rest, counters)), grads = grad_fn(params, rest, batch)

            with tracing.scope('step.clip'):
                grad_norm = global_grad_norm(grads)
                if clip_grad is not None:
                    params_for_clip = params if clip_mode == 'agc' else None
                    grads, _ = dispatch_clip_grad(grads, clip_grad, mode=clip_mode, params=params_for_clip)

            with tracing.scope('step.update'):
                updates, new_opt_state = optimizer.update(grads, opt_state, params, lr=lr)
                new_params = optax.apply_updates(params, updates)
            if guard:
                # all-finite reduction over loss + raw grads; a bad step keeps
                # params/opt_state/EMA bit-identical to the previous step
                with tracing.scope('step.guard'):
                    ok = tree_all_finite(loss, grads)
                    select = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
                    new_params = jax.tree.map(select, new_params, params)
                    new_opt_state = jax.tree.map(select, new_opt_state, opt_state)
                    sentinel_state = update_sentinel_state(sentinel_state, ok)

            if has_ema:
                # decay==0 naturally syncs EMA to model (reference ModelEmaV3
                # lerp weight 1.0 during the update_after_step window).
                with tracing.scope('step.ema'):
                    new_ema = ema_update(ema_params, new_params, ema_decay)
                if guard:
                    with tracing.scope('step.guard'):
                        new_ema = jax.tree.map(select, new_ema, ema_params)
                ema_params = new_ema
            metrics = {'loss': loss, 'grad_norm': grad_norm, **counters}
            if guard:
                # the counters as outputs of their own (the state itself is donated to the
                # next step): no eager slice on the main thread after the call
                metrics['nonfinite'] = sentinel_state[0] > 0
                metrics['nonfinite_count'] = sentinel_state[0]
                metrics['nonfinite_total'] = sentinel_state[1]
            new_state = (new_params, new_rest, new_opt_state, ema_params)
            if tuple(map(jax.tree.structure, new_state)) != defs:
                # the leaves go back into the Variables they came from by position
                raise ValueError('the train step changed the structure of its state')
            return (*(tuple(jax.tree.leaves(t)) for t in new_state), sentinel_state, metrics)

        # donation + matching in/out shardings let XLA alias every state
        # buffer in place (params, m/v, EMA, RNG counters, sentinel); the
        # sharding annotations are REQUIRED for the aliasing to be legal
        return jax.jit(
            train_step,
            donate_argnums=(0, 1, 2, 3, 4),
            in_shardings=(param_sh, rep, opt_sh, ema_sh, rep, None, rep, rep),
            out_shardings=(param_sh, rep, opt_sh, ema_sh, rep, rep),
        )

    def _build_eval_step(self):
        eval_forward = self.eval_forward
        normalize_input = self.normalize_input
        self.model.eval()
        graphdef, _, _ = self._split_model()
        rep = replicate_sharding(self.mesh)

        def eval_step(params, rest, batch):
            m = nnx.merge(graphdef, params, rest)
            return eval_forward(m, normalize_input(batch))

        # no-donate: eval reuses params/rest across calls (and for EMA eval the
        # live train params are passed straight back in on the next call).
        # Batch placement is inherited (shard_batch), outputs follow it.
        return jax.jit(
            eval_step,
            in_shardings=(self._param_shardings, rep, None),
            out_shardings=None,
        )

    # -- public step API -------------------------------------------------------
    def train_step(self, batch: Dict[str, Any], lr: float, step: int = 0):
        """One optimization step; `batch['input']` is NHWC, batch dim sharded
        over the mesh (use parallel.shard_batch). The spans split the call's
        host time by part (utils/tracing.py; PERF.md section 3).

        The non-finite counters the host reads here are those of the call
        BEFORE this one: this step is enqueued behind the one still running, and
        a `NonFiniteError` is raised one call after the step that trips it (or by
        `drain()`, which whoever saves or evaluates the state calls first)."""
        with tracing.span('task.train_step'):
            if self._train_step is None:
                self._train_step = self._build_train_step()
            with tracing.span('task.state_split'):
                param_vars, rest_vars = self._step_vars
                params = tuple(v.get_raw_value() for v in param_vars)
                rest = tuple(v.get_raw_value() for v in rest_vars)
            ema_decay = self.ema.get_decay(step) if self.ema is not None else 0.0
            sent_in = self._sentinel_state if self._sentinel_state is not None else ()
            with tracing.span('task.scalars_put'):
                lr_in, ema_decay_in = jnp.asarray(lr, jnp.float32), jnp.asarray(ema_decay, jnp.float32)
            with tracing.span('task.step_call'):
                params, rest, self._opt_leaves, ema_out, sent_out, metrics = self._train_step(
                    params, rest, self._opt_leaves, self._ema_leaves, sent_in, batch, lr_in, ema_decay_in)
            with tracing.span('task.state_update'):
                for var, value in zip(param_vars, params):
                    var.set_raw_value(value)
                for var, value in zip(rest_vars, rest):
                    var.set_raw_value(value)
                self._ema_leaves = ema_out
                if self._sentinel_state is not None:
                    self._sentinel_state = sent_out
            if self.sentinel is not None:
                # the step's own counters: outputs of the program, not donated, a snapshot of
                # this step (`sent_out` goes into the next call and cannot be read after it)
                unread, self._unread = self._unread, (step, metrics['nonfinite_count'], metrics['nonfinite_total'])
                self._observe(unread)
        return metrics

    def _observe(self, unread):
        """Read one step's counters (none before a run's first step: the span is
        there all the same); raises NonFiniteError after K consecutive bad steps."""
        with tracing.span('task.sentinel_poll'):
            if unread is None:
                return
            step, *counts = unread
            if not counts[0].is_ready():  # both are outputs of one execution
                tracing.count('task.polls_host_ahead')  # the device is still busy: it did not wait for the host
            self.sentinel.observe(counts, step=step)

    def drain(self):
        """Read the counters of the step still unread, if any: the old ordering,
        for whoever is about to save, evaluate or rebuild the step. A second call
        reads nothing."""
        if self._unread is not None:
            unread, self._unread = self._unread, None
            self._observe(unread)

    def _train_step_args(self, batch: Dict[str, Any], lr: float, step: int):
        """The jitted step, built if need be, and the arguments `train_step`
        would call it with, for the two AOT entry points below."""
        if self._train_step is None:
            self._train_step = self._build_train_step()
        ema_decay = self.ema.get_decay(step) if self.ema is not None else 0.0
        sent_in = self._sentinel_state if self._sentinel_state is not None else ()
        return self._train_step, (
            *(tuple(v.get_raw_value() for v in vs) for vs in self._step_vars), self._opt_leaves, self._ema_leaves,
            sent_in, batch, jnp.asarray(lr, jnp.float32), jnp.asarray(ema_decay, jnp.float32))

    def trace_train_step(self, batch: Dict[str, Any], lr: float = 0.1, step: int = 0):
        """AOT-trace the jitted train step on `batch` WITHOUT executing it;
        returns the ClosedJaxpr (trace-cost regression tests count its
        equations to pin the O(1)-in-grad_accum_steps property)."""
        step_fn, args = self._train_step_args(batch, lr, step)
        return step_fn.trace(*args).jaxpr

    def lower_train_step(self, batch: Dict[str, Any], lr: float = 0.1, step: int = 0):
        """AOT-lower-and-compile the jitted train step on `batch` WITHOUT
        executing it; returns the jax.stages.Compiled. The perfbudget probe
        reads `cost_analysis()` (FLOPs / bytes accessed) and the HLO
        `input_output_alias` header (donation legality) off it, and the
        compile goes through the persistent cache so repeated probes are
        disk-bound. Its text is kept for whoever reads the step's device
        scopes out of a trace (`tracing.program_text('task.step_call')`):
        on this path only, never by `train_step`."""
        step_fn, args = self._train_step_args(batch, lr, step)
        compiled = step_fn.lower(*args).compile()
        tracing.keep_program('task.step_call', compiled)
        text = tracing.program_text('task.step_call')
        gathers, fast = tracing.scope_gathers(text, 'glm.moe.route')
        if gathers:     # a model with expert layers: how many of the route's gathers read a source in fast memory
            tracing.gauge('moe.route_gathers', gathers)
            tracing.gauge('moe.route_gathers_fast', fast)
        scans = tracing.scope_loops(text, 'kda.core')
        if scans:       # a model with gated delta-rule layers: two a layer once the chunk-boundary states are kept
            tracing.gauge('kda.core_scans', scans)
        products = tracing.scope_products(text, 'evabyte.ffn')
        if products:    # a model of dense SwiGLU layers: nine a layer once the block keeps its two up-products
            tracing.gauge('ffn.products', products)
        return compiled

    def _new_sentinel_state(self):
        """Fresh counters placed like the step's output: an unplaced array
        has another type than the mesh-replicated one the step hands back, and
        the second train step would trace and compile a second program."""
        return jax.device_put(new_sentinel_state(), replicate_sharding(self.mesh))

    def reset_nonfinite(self):
        """Clear the consecutive-bad-step counters (after a rollback); the step
        still unread is forgotten with the state it belonged to."""
        self._unread = None
        if self._sentinel_state is not None:
            self._sentinel_state = self._new_sentinel_state()
        if self.sentinel is not None:
            self.sentinel.reset()

    def update_ema(self, step: int):
        pass  # fused into train_step; parity no-op (task.py update_ema)

    def eval_step(self, batch: Dict[str, Any], use_ema: bool = False):
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        self.model.eval()
        _, params, rest = self._split_model()
        ema = self.ema_params if use_ema else None
        out = self._eval_step(params if ema is None else ema, rest, batch)
        self.model.train()  # train_step does not set the mode: the model stays in train mode between calls
        return out

    # -- module sync / checkpoint ------------------------------------------------
    def sync_model(self, use_ema: bool = False) -> nnx.Module:
        if use_ema and self.ema_params is not None:
            nnx.update(self.model, self.ema_params)
        return self.model

    def get_checkpoint_state(self) -> Dict[str, np.ndarray]:
        """Flat checkpoint dict (schema mirrors reference checkpoint_saver.py:89).
        fsdp-sharded leaves are gathered to full host arrays by np.asarray, so
        the checkpoint bytes are identical for every mesh shape."""
        state = flatten_pytree(nnx.state(self.model, nnx.Param), 'state_dict')
        if self.ema_params is not None:
            state.update(flatten_pytree(self.ema_params, 'state_dict_ema'))
        if self.opt_state is not None:
            state.update(flatten_pytree(self.opt_state, 'optimizer'))
        # non-param model variables (e.g. BN stats) minus rng bookkeeping
        other = nnx.state(self.model, nnx.Not(nnx.Param))
        flat_other = {k: v for k, v in flatten_pytree(other, 'model_state').items() if 'rngs' not in k}
        state.update(flat_other)
        return state

    @staticmethod
    def _place(tree, shardings):
        """device_put a host pytree under `shardings` (a matching tree or one
        sharding for every leaf). Multi-process meshes route through
        `place_global`, which builds non-fully-addressable global arrays from
        each host's local pieces; single-process this IS jax.device_put."""
        from ..parallel.mesh import place_global
        if isinstance(shardings, jax.sharding.Sharding):
            return jax.tree.map(lambda x: place_global(x, shardings), tree)
        return jax.tree.map(place_global, tree, shardings)

    def load_checkpoint_state(self, state: Dict[str, np.ndarray], strict: bool = True, load_opt: bool = True):
        """Restore from a flat checkpoint dict; loaded leaves are re-placed
        under THIS task's shardings, so a checkpoint saved on any mesh shape
        (single-device, data-only, data×fsdp, multi-process sharded) loads on
        any other. A step still unread (`drain`) is forgotten: its state goes."""
        self._unread = None
        params = unflatten_into(nnx.state(self.model, nnx.Param), state, 'state_dict', strict=strict)
        nnx.update(self.model, self._place(params, self._param_shardings))
        if self.ema_params is not None and any(k.startswith('state_dict_ema.') for k in state):
            ema = unflatten_into(self.ema_params, state, 'state_dict_ema', strict=strict)
            self.ema_params = self._place(ema, self._param_shardings)
        if load_opt and self.opt_state is not None and any(k.startswith('optimizer.') for k in state):
            opt = unflatten_into(self.opt_state, state, 'optimizer', strict=strict)
            self.opt_state = self._place(opt, self._opt_shardings)
        if any(k.startswith('model_state.') for k in state):
            other = nnx.state(self.model, nnx.Not(nnx.Param))
            other = unflatten_into(other, state, 'model_state', strict=False)
            nnx.update(self.model, self._place(other, replicate_sharding(self.mesh)))
