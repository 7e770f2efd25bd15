"""Parameter grouping / model manipulation
(reference: timm/models/_manipulate.py:29-346).

Parameter "names" are the dotted flat-state paths produced by
`model_state_dict`; `group_matcher` specs are the same regex-tuple structures
the reference uses, matched against those names.
"""
from __future__ import annotations

import collections.abc
import functools
import re
import types
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

MATCH_PREV_GROUP = (99999,)

__all__ = [
    'group_parameters', 'group_with_matcher', 'named_parameters', 'checkpoint_seq',
    'BlockStackError', 'iter_submodules', 'build_block_stack', 'scan_block_stack',
    'drop_path_scan_inputs', 'resolve_block_scan', 'warn_scan_fallback',
    'build_stage_stack', 'scan_stage_stack', 'plan_stage_stack', 'resolve_stage_scan',
]


def named_parameters(model) -> Dict[str, Any]:
    """Flat {dotted.name: array} of trainable params only."""
    from flax import nnx
    out = {}
    state = nnx.state(model, nnx.Param)
    for path, leaf in nnx.to_flat_state(state):
        key = '.'.join(str(getattr(p, 'key', p)) for p in path)
        if 'rngs' in key:
            continue
        out[key] = leaf[...]
    return out


def group_with_matcher(
        named_objects,
        group_matcher: Union[Dict, Callable],
        return_values: bool = False,
        reverse: bool = False,
):
    """(reference _manipulate.py:80-140)."""
    if isinstance(group_matcher, dict):
        compiled = []
        for group_ordinal, (group_name, mspec) in enumerate(group_matcher.items()):
            if mspec is None:
                continue
            if isinstance(mspec, (tuple, list)):
                for sspec in mspec:
                    compiled += [(group_ordinal, group_name, re.compile(sspec[0]), sspec[1])]
            else:
                compiled += [(group_ordinal, group_name, re.compile(mspec), None)]
        group_matcher = compiled

    def _get_grouping(name):
        if isinstance(group_matcher, (list, tuple)):
            for grp_ordinal, _, pattern, suffix in group_matcher:
                r = pattern.match(name)
                if r:
                    parts = (grp_ordinal,) + r.groups()
                    if suffix is not None:
                        parts = parts + (tuple(suffix) if isinstance(suffix, (tuple, list)) else (suffix,))
                    flat = []
                    for p in parts:
                        if p is None:
                            continue
                        if isinstance(p, (tuple, list)):
                            flat.extend(float(q) for q in p if q is not None)
                        else:
                            flat.append(float(p))
                    return tuple(flat)
            return (float('inf'),)
        ord_ = group_matcher(name)
        if not isinstance(ord_, collections_abc_iterable()):
            return (ord_,)
        return tuple(ord_)

    grouping = defaultdict(list)
    for name, obj in named_objects:
        grouping[_get_grouping(name)].append(obj if return_values else name)

    # remap to integers, ordered
    layer_id_to_param = defaultdict(list)
    lid = -1
    for k in sorted(filter(lambda x: x is not None, grouping.keys())):
        if lid < 0 or k[-1] != MATCH_PREV_GROUP[0]:
            lid += 1
        layer_id_to_param[lid].extend(grouping[k])

    if reverse:
        assert not return_values, 'reverse mapping only supported for name output'
        param_to_layer_id = {}
        for lid_, names in layer_id_to_param.items():
            for n in names:
                param_to_layer_id[n] = lid_
        return param_to_layer_id
    return layer_id_to_param


def collections_abc_iterable():
    import collections.abc
    return collections.abc.Iterable


def group_parameters(model, group_matcher, return_values: bool = False, reverse: bool = False):
    return group_with_matcher(
        named_parameters(model).items(), group_matcher, return_values=return_values, reverse=reverse)


def _run_modules(modules, x):
    for m in modules:
        x = m(x)
    return x


# ---- scan-over-layers block stacking ----------------------------------------
#
# A depth-L transformer traced as a Python loop costs O(L) trace time and O(L)
# XLA subgraphs to compile. For homogeneous block stacks the params can instead
# be stacked into leading-axis pytrees and the stack run as ONE lax.scan whose
# body is traced/compiled once — O(1) in depth (the MaxText/Flax big-model
# recipe). The helpers below implement that generically for any nnx block list
# so every ViT-family model (vision_transformer, deit, beit, eva) shares one
# code path.


class BlockStackError(RuntimeError):
    """Raised when a block list cannot be stacked for lax.scan execution
    (heterogeneous types/statics/shapes, live inner dropout RNG, <2 blocks).
    Callers fall back to the Python loop."""


def resolve_block_scan(flag) -> bool:
    """Resolve a model's ``block_scan`` constructor arg: an explicit bool wins;
    None reads the ``TIMM_TPU_BLOCK_SCAN`` env toggle (default off)."""
    if flag is not None:
        return bool(flag)
    import os
    return os.environ.get('TIMM_TPU_BLOCK_SCAN', '').lower() in ('1', 'true', 'yes', 'on')


_SCAN_FALLBACK_WARNED = set()


def warn_scan_fallback(model_name: str, err, what: str = 'block_scan'):
    """Log (once per model-class/reason) that block/stage scan fell back to
    the loop."""
    key = (model_name, str(err))
    if key not in _SCAN_FALLBACK_WARNED:
        _SCAN_FALLBACK_WARNED.add(key)
        import logging
        logging.getLogger(__name__).warning(
            f'{model_name}: {what} fell back to the Python block loop: {err}')


def iter_submodules(module):
    """Yield `module` and every nnx.Module reachable through its attributes
    (including list/tuple containers), in deterministic attribute order."""
    from flax import nnx
    seen = set()

    def _walk(m):
        if id(m) in seen:
            return
        seen.add(id(m))
        yield m
        for v in vars(m).values():
            if isinstance(v, nnx.Module):
                yield from _walk(v)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    if isinstance(item, nnx.Module):
                        yield from _walk(item)

    yield from _walk(module)


def _static_equal(a, b) -> bool:
    """Whether two static graphdef values describe the same computation.
    Per-block init-fn closures (`trunc_normal_.<locals>.init`) and plain
    config objects (`Pool2d`) are distinct objects in every block, so identity
    `==` calls homogeneous blocks different; compare them by code + captured
    values and by fields instead. A depth-indexed float or a different
    submodule layout still compares unequal."""
    if a is b:
        return True
    if type(a) is not type(b):
        return False
    if isinstance(a, types.FunctionType):
        return (a.__code__ is b.__code__
                and _static_equal(a.__defaults__, b.__defaults__)
                and _static_equal(a.__kwdefaults__, b.__kwdefaults__)
                and _static_equal([c.cell_contents for c in a.__closure__ or ()],
                                  [c.cell_contents for c in b.__closure__ or ()]))
    if isinstance(a, functools.partial):
        return _static_equal((a.func, a.args, a.keywords), (b.func, b.args, b.keywords))
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_static_equal(x, y) for x, y in zip(a, b))
    if isinstance(a, collections.abc.Mapping):
        return a.keys() == b.keys() and all(_static_equal(a[k], b[k]) for k in a)
    if isinstance(a, np.ndarray):
        return np.array_equal(a, b)
    if type(a).__eq__ is object.__eq__ and hasattr(a, '__dict__'):
        return _static_equal(vars(a), vars(b))
    return bool(a == b)


def graphdefs_equivalent(a, b) -> bool:
    """Structural equality of two `nnx.GraphDef`s: same node layout, same
    attribute names and kinds, static values equal under `_static_equal`."""
    from flax.nnx.graph import Static
    if a.nodes != b.nodes or len(a.attributes) != len(b.attributes):
        return False
    for (ka, va), (kb, vb) in zip(a.attributes, b.attributes):
        if ka != kb or type(va) is not type(vb):
            return False
        if isinstance(va, Static):
            if not _static_equal(va.value, vb.value):
                return False
        elif va != vb:
            return False
    return True


def _split_blocks(blocks):
    """``nnx.split(b, nnx.RngState, ...)`` for each block with its DropPath
    statics (per-layer rate float + forked stream) neutralized, so a
    linearly-ramped stochastic-depth schedule doesn't make the graphdefs
    heterogeneous: in scan mode the per-layer rates ride a scanned rate vector
    and the keys are drawn eagerly outside the scan (see
    `drop_path_scan_inputs`), so the merged blocks' DropPath modules must be
    structural no-ops."""
    from flax import nnx

    from ..layers.drop import DropPath

    dp_saved = []
    for b in blocks:
        for sm in iter_submodules(b):
            if isinstance(sm, DropPath):
                dp_saved.append((sm, sm.drop_prob, sm.rngs))
                sm.drop_prob = 0.0
                sm.rngs = None
    try:
        return [nnx.split(b, nnx.RngState, ...) for b in blocks]
    finally:
        for sm, p, r in dp_saved:
            sm.drop_prob = p
            sm.rngs = r


def build_block_stack(blocks, validate: bool = True):
    """Split a homogeneous block list into ``(graphdef, rng_state, stacked)``
    where ``stacked`` is the blocks' non-RNG state with a leading depth axis.

    DropPath statics are neutralized before splitting (`_split_blocks`).

    Raises BlockStackError when stacking is impossible or would silently
    change semantics (different block types, depth-dependent statics, live
    inner-dropout RNG that the scan body could not advance).
    """
    import jax
    import jax.numpy as jnp
    from flax import nnx

    blocks = list(blocks)
    if len(blocks) < 2:
        raise BlockStackError('need at least 2 blocks to scan')
    if any(type(b) is not type(blocks[0]) for b in blocks[1:]):
        raise BlockStackError(
            f'heterogeneous block types: {sorted({type(b).__name__ for b in blocks})}')

    if validate:
        # an inner Dropout with a live stream would consume RNG state inside
        # the scan body with no way to write the advanced counts back — every
        # step would reuse the same mask. DropPath is exempt (handled via the
        # scanned rate vector + eagerly drawn keys).
        for b in blocks:
            for sm in iter_submodules(b):
                if isinstance(sm, nnx.Dropout) and sm.rngs is not None \
                        and not sm.deterministic and sm.rate > 0:
                    raise BlockStackError(
                        'active inner dropout (train mode, rate>0) cannot run under scan')

    splits = _split_blocks(blocks)
    graphdef, rng_state, _ = splits[0]
    if validate:
        for i, (gd, _, _) in enumerate(splits[1:], start=1):
            if not graphdefs_equivalent(gd, graphdef):
                raise BlockStackError(
                    f'block 0 and block {i} differ in static structure '
                    '(depth-dependent statics or layout)')
    try:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[s[2] for s in splits])
    except (ValueError, TypeError) as e:
        raise BlockStackError(f'block states are not stackable: {e}') from e
    return graphdef, rng_state, stacked


def drop_path_scan_inputs(blocks):
    """Per-layer DropPath inputs for scan mode: ``(rates[L, S], keys[L, S])``
    over the S DropPath sites of each of the L blocks, or None when no site is
    active (eval mode, or every rate 0). Keys are drawn from each block's own
    forked stream — the stream counts advance exactly as in loop mode."""
    import jax.numpy as jnp

    from ..layers.drop import DropPath

    rows = [[sm for sm in iter_submodules(b) if isinstance(sm, DropPath)] for b in blocks]
    n_sites = len(rows[0])
    if n_sites == 0 or any(len(r) != n_sites for r in rows):
        return None
    if not any(m.drop_prob > 0 and m.rngs is not None and not m.deterministic
               for row in rows for m in row):
        return None
    rates, keys, ref_key = [], [], None
    for row in rows:
        rrow, krow = [], []
        for m in row:
            live = m.rngs is not None and not m.deterministic and m.drop_prob > 0
            rrow.append(m.drop_prob if live else 0.0)
            k = m.rngs.dropout() if live else None
            if k is not None:
                ref_key = k
            krow.append(k)
        rates.append(rrow)
        keys.append(krow)
    # rate-0 sites keep everything regardless of key; reuse a drawn key there
    keys = [[k if k is not None else ref_key for k in row] for row in keys]
    return (jnp.asarray(rates, jnp.float32),
            jnp.stack([jnp.stack(row) for row in keys]))


def scan_block_stack(blocks, x, call_block=None, *, per_layer=None, remat: bool = False,
                     remat_policy=None, collect: bool = False, validate: bool = True):
    """Run a homogeneous block list as one ``jax.lax.scan`` over stacked
    per-layer state: trace/compile cost is O(1) in depth.

    ``call_block(block, x, extra)`` runs one merged block; ``extra`` is the
    per-layer slice of the ``per_layer`` pytree (or None). ``remat=True``
    wraps the body in `jax.checkpoint` (remat-inside-scan replaces
    `checkpoint_seq` for scanned stacks). ``collect=True`` additionally
    returns the stacked per-layer outputs ``[L, ...]`` (forward_intermediates).

    On a mesh with a 'model' axis the scan CARRY is pinned to the residual
    sharding (batch over data/fsdp, channels over 'model') — both the initial
    carry and the per-step output. Without the in-body constraint GSPMD must
    pick one layout for the whole while-loop and picks replicated, which is
    the involuntary-remat pattern PERF.md documents; with it, activations
    stay model-sharded across all L layers. No-op on tp=1 meshes.
    """
    import jax

    from ..parallel import shard_activation

    graphdef, rng_state, stacked = build_block_stack(blocks, validate=validate)
    if call_block is None:
        call_block = lambda blk, xx, extra: blk(xx)

    from flax import nnx

    x = shard_activation(x, 'residual')

    def body(carry, xs):
        layer_state, extra = xs
        blk = nnx.merge(graphdef, rng_state, layer_state)
        y = call_block(blk, carry, extra)
        y = shard_activation(y, 'residual')
        return y, (y if collect else None)

    if remat:
        body = jax.checkpoint(body, policy=remat_policy)
    out, ys = jax.lax.scan(body, x, (stacked, per_layer))
    return (out, ys) if collect else out


# ---- stage-level scan (hierarchical models) ---------------------------------
#
# Hierarchical models (convnext, swin, metaformer, pvt_v2, regnet, mambaout)
# run N stages of homogeneous blocks separated by downsample boundaries.
# Within one stage the block_scan recipe applies unchanged — stack per-layer
# state, run ONE lax.scan — but two structural wrinkles need planning that
# ViT stacks never see:
#
#   * an EAGER PREFIX: the first block of a stage often differs from the rest
#     (regnet's stride-2/downsample block, convnext's in_chs != out_chs
#     shortcut block). Those k blocks run as a Python loop and the
#     homogeneous suffix scans.
#   * a PERIOD: swin alternates shifted/unshifted blocks (period 2), so the
#     graphdefs repeat with period p rather than being all-equal. Blocks are
#     stacked per offset-column (blocks [j, j+p, j+2p, ...]) and the scan
#     body runs p merged blocks per step.
#
# `plan_stage_stack` searches (eager_prefix, period) in a fixed cheap order;
# a stage with no valid plan raises BlockStackError and the caller falls back
# to the loop (logged once per model class — never silently slow).


def resolve_stage_scan(flag) -> bool:
    """Resolve a hierarchical model's ``stage_scan`` constructor arg: an
    explicit bool wins; None reads the ``TIMM_TPU_STAGE_SCAN`` env toggle
    (default off, mirroring ``resolve_block_scan``)."""
    if flag is not None:
        return bool(flag)
    import os
    return os.environ.get('TIMM_TPU_STAGE_SCAN', '').lower() in ('1', 'true', 'yes', 'on')


def plan_stage_stack(blocks) -> Tuple[int, int]:
    """Find ``(eager_prefix, period)`` for a stage's block list: the first
    `eager_prefix` blocks run eagerly, the rest scan with period `period`
    (each offset-column homogeneous, >=2 scan steps). Searched smallest-first
    so a fully homogeneous stage plans as (0, 1). Raises BlockStackError when
    no candidate fits."""
    blocks = list(blocks)
    if len(blocks) < 2:
        raise BlockStackError('need at least 2 blocks to scan')
    types = [type(b) for b in blocks]
    graphdefs = [gd for gd, _, _ in _split_blocks(blocks)]
    for prefix in (0, 1):
        for period in (1, 2):
            rest = len(blocks) - prefix
            if rest < 2 * period or rest % period:
                continue
            cols_ok = all(
                all(types[prefix + j + i * period] is types[prefix + j]
                    and graphdefs_equivalent(graphdefs[prefix + j + i * period], graphdefs[prefix + j])
                    for i in range(rest // period))
                for j in range(period))
            if cols_ok:
                return prefix, period
    raise BlockStackError(
        'no (eager_prefix, period) plan makes the stage scannable: block '
        'statics vary beyond a length-1 prefix and period-2 alternation')


def build_stage_stack(blocks, period: int = 1, validate: bool = True):
    """Stack a stage's scannable blocks per offset-column: returns
    ``(graphdefs, rng_states, stackeds)``, each a length-`period` list, where
    ``stackeds[j]`` is the stacked state of blocks ``[j, j+period, ...]``.
    Period 1 is exactly one `build_block_stack`."""
    blocks = list(blocks)
    if len(blocks) % period:
        raise BlockStackError(
            f'{len(blocks)} blocks do not divide into period-{period} columns')
    graphdefs, rng_states, stackeds = [], [], []
    for j in range(period):
        graphdef, rng_state, stacked = build_block_stack(blocks[j::period], validate=validate)
        graphdefs.append(graphdef)
        rng_states.append(rng_state)
        stackeds.append(stacked)
    return graphdefs, rng_states, stackeds


def _check_no_train_batch_stats(blocks):
    """Batch-stat modules (BatchNorm & friends expose `use_running_average`)
    update running mean/var as a side effect of a train-mode call; a scan
    body cannot write those updates back to the real modules, so scanning
    would silently freeze the stats. Raise and let the loop handle it."""
    for b in blocks:
        for sm in iter_submodules(b):
            if getattr(sm, 'use_running_average', None) is False:
                raise BlockStackError(
                    f'{type(sm).__name__} in training mode: running-stat '
                    'updates inside a scan body would be silently discarded')


def _set_drop_path_overrides(block, rates, keys):
    """Pin the scanned per-layer (rate, key) onto the merged block's DropPath
    sites, in the same deterministic `iter_submodules` order
    `drop_path_scan_inputs` drew them in."""
    from ..layers.drop import DropPath
    site = 0
    for sm in iter_submodules(block):
        if isinstance(sm, DropPath):
            sm._scan_override = (rates[site], keys[site])
            site += 1


def scan_stage_stack(blocks, x, call_block=None, *, remat: bool = False,
                     remat_policy=None, validate: bool = True):
    """Run one stage's block list as ONE ``jax.lax.scan``: trace/compile cost
    O(1) in stage depth, with an eager prefix for a heterogeneous first block
    and period-p column stacking for alternating statics (swin's shift).

    ``call_block(block, x)`` runs one merged block (default ``block(x)``;
    pvt_v2 passes its static feat_size through a closure). Per-layer DropPath
    rates/keys ride the scanned inputs exactly as in `scan_block_stack`,
    except they are pinned onto the merged blocks' DropPath modules (stage
    blocks take no override argument). ``remat=True`` wraps the body in
    `jax.checkpoint` — remat-inside-scan replaces `checkpoint_seq`.

    The carry is pinned to the NHWC 'channels' layout on 'model' meshes
    (rank-3 stages like pvt get 'residual'); without the in-body constraint
    GSPMD picks one (replicated) layout for the whole while-loop — the
    involuntary-remat regime PERF.md documents.

    Raises BlockStackError (train-mode batch stats, no valid plan,
    unstackable states); callers fall back to the bit-identical Python loop.
    """
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from ..parallel import shard_activation

    blocks = list(blocks)
    if call_block is None:
        call_block = lambda blk, xx: blk(xx)
    if validate:
        _check_no_train_batch_stats(blocks)
    prefix, period = plan_stage_stack(blocks)
    kind = 'channels' if getattr(x, 'ndim', 0) == 4 else 'residual'

    for blk in blocks[:prefix]:
        x = call_block(blk, x)
    scanned = blocks[prefix:]
    graphdefs, rng_states, stackeds = build_stage_stack(scanned, period, validate=validate)
    n_steps = len(scanned) // period

    dp = drop_path_scan_inputs(scanned)
    if dp is not None:
        # [L, S] -> [n_steps, period, S]: lax.scan slices the step axis,
        # the body indexes the period offset
        rates, keys = dp
        dp = (rates.reshape(n_steps, period, -1),
              keys.reshape((n_steps, period) + keys.shape[1:]))

    x = shard_activation(x, kind)

    def body(carry, xs):
        layer_states, extra = xs
        y = carry
        for j in range(period):
            blk = nnx.merge(graphdefs[j], rng_states[j], layer_states[j])
            if extra is not None:
                _set_drop_path_overrides(blk, extra[0][j], extra[1][j])
            y = call_block(blk, y)
            y = shard_activation(y, kind)
        return y, None

    if remat:
        body = jax.checkpoint(body, policy=remat_policy)
    out, _ = jax.lax.scan(body, x, (tuple(stackeds), dp))
    return out


def checkpoint_seq(functions, x, every: int = 1, flatten: bool = False, skip_last: bool = False,
                   policy=None):
    """Apply a sequence of nnx modules with rematerialisation every `every`
    modules (reference _manipulate.py:213 checkpoint_seq). Trades recompute
    for HBM — the TPU equivalent of torch activation checkpointing.

    `policy` is a `jax.checkpoint_policies` predicate (e.g. ``dots_saveable``)
    selecting which intermediates are saved vs recomputed in the backward pass;
    None = save nothing (maximum memory saving, maximum recompute).
    """
    from flax import nnx
    functions = list(functions)
    end = len(functions) - 1 if skip_last else len(functions)
    remat_run = nnx.remat(_run_modules, policy=policy)
    idx = 0
    while idx < end:
        chunk = tuple(functions[idx:min(idx + every, end)])
        x = remat_run(chunk, x)
        idx += every
    if skip_last:
        x = functions[-1](x)
    return x
