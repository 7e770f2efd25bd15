"""FSDP-style parameter/optimizer sharding (ZeRO over the mesh 'fsdp' axis).

The reference framework replicates every parameter and optimizer slot on
every chip (DDP); model size is then capped by one chip's HBM and AdamW pays
full replicated m/v traffic (PERF.md §2 item 3). Here the 1-axis data mesh
grows an optional second axis, ``('data', 'fsdp')``:

  * the BATCH is sharded over the product of both axes (every device computes
    different samples — plain data parallelism from the loss's view);
  * large matmul WEIGHTS are sharded over 'fsdp' along one dimension, small
    params (biases, norm scales, cls/pos embeddings) stay replicated;
  * OPTIMIZER state inherits each param's spec leaf-for-leaf (ZeRO-1/2:
    m/v shards live only on the devices that own the param shard).

Everything is expressed as `NamedSharding` annotations consumed by GSPMD
(Xu et al.): XLA inserts the all-gathers before use and reduce-scatters after
the backward pass; no hand-written collectives. The partition decision is a
small ordered list of REGEX RULES over the '.'-joined param path — the t5x /
big_vision logical-axis-rules idiom — so models can override placement
without touching module code.

Specs are shape-validated: a rule only shards a dimension when the dim is
divisible by the fsdp axis size; otherwise the param is replicated (logged
once per path). This keeps every model loadable on any mesh shape.
"""
from __future__ import annotations

import logging
import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_logger = logging.getLogger(__name__)

__all__ = [
    'PartitionRule', 'default_partition_rules', 'match_rule',
    'spec_for_param', 'build_param_shardings', 'path_specs',
    'inherit_param_specs', 'build_opt_shardings',
    'quant_scale_spec', 'quant_path_specs', 'build_quant_shardings',
    'shard_pytree', 'abstract_init_sharded', 'create_sharded_model',
    'replicated_like', 'fsdp_size', 'tp_size', 'param_bytes_per_device',
    'activation_bytes_per_device',
]

# Sharding a tiny tensor buys no memory and costs collective latency; params
# below this element count are replicated even when a shard rule matches.
MIN_SHARD_SIZE = 1024


@dataclass(frozen=True)
class PartitionRule:
    """One ordered partition rule: `pattern` is re.search'ed against the
    '.'-joined param path; first match wins.

    `action` is one of 'fsdp_largest' (shard the largest dimension divisible
    by the fsdp axis size), 'megatron_col' / 'megatron_row' (tensor
    parallelism: shard the output / input feature dim over 'model', stacking
    'fsdp' on another dim when both axes exist; with no 'model' axis these
    delegate to 'fsdp_largest' so tp=1 placement is bit-identical to the
    2-axis mesh), 'replicate', or an explicit PartitionSpec-like tuple
    (validated against the leaf's rank/divisibility at apply time).
    """
    pattern: str
    action: Any = 'fsdp_largest'
    name: str = ''

    def matches(self, path: str) -> bool:
        return re.search(self.pattern, path) is not None


# Tensor-parallel kernel paths (Megatron split): column-parallel layers write
# the dimension that gets CONSUMED shard-local downstream (attention heads for
# qkv/q/k/v, MLP hidden for fc1*), row-parallel layers read it back and XLA
# emits one reduce per pair (attn.proj, mlp.fc2). The generic kernel rule
# excludes all four via lookahead so the rule table stays DISJOINT — the
# exactly-one-rule test is what keeps placement auditable.
#
# Hierarchical families route through the same four rules: metaformer wraps
# attention as `token_mixer`, pvt_v2 splits q from kv, and a 1x1 projection
# conv (NHWC Linear) matches the same suffixes — its kernel is rank 2, so the
# megatron specs apply unchanged. Convnext's NHWC MLP fc1/fc2 Linears already
# match the mlp rules.
_TP_ATTN_QKV = r'\.(?:attn|token_mixer)\.(?:qkv|q_proj|k_proj|v_proj|gate_proj|q|kv)\.kernel$'
_TP_ATTN_OUT = r'\.(?:attn|token_mixer)\.proj\.kernel$'
_TP_MLP_IN = r'\.mlp\.(?:fc1|fc1_g|fc1_x)\.kernel$'
_TP_MLP_OUT = r'\.mlp\.fc2\.kernel$'
_TP_KERNEL_PATTERNS = (_TP_ATTN_QKV, _TP_ATTN_OUT, _TP_MLP_IN, _TP_MLP_OUT)
_GENERIC_KERNEL = r'^(?!.*(?:' + '|'.join(_TP_KERNEL_PATTERNS) + r')).*\.kernel$'


def default_partition_rules() -> Tuple[PartitionRule, ...]:
    """FSDP + tensor-parallel rules for the timm_tpu model families. Ordered,
    first-match-wins, mutually exclusive on every ViT param path (tests assert
    exactly one rule matches each param):

      1. attention qkv / q,k,v kernels    -> heads over 'model' (column)
      2. attention output proj kernels    -> input dim over 'model' (row)
      3. MLP fc1 (incl. glu gates)        -> hidden over 'model' (column)
      4. MLP fc2                          -> hidden over 'model' (row)
      5. other 2D+ matmul / conv kernels  -> shard largest divisible dim
      6. biases                           -> replicate
      7. norm scales / LayerScale gammas  -> replicate (a per-head `attn.q_norm` / `attn.k_norm` scale among
         them: one scale of head_dim, every head alike, so tensor-parallel heads each need all of it)
      8. tokens & position embeddings     -> replicate
      9. stacked expert kernels (E, in, out) -> 'fsdp' on the output dim
     10. a router's kernel                -> replicate (every chip scores alike)
     11. a chunk-pooled attention's two learned vectors a head (`attn.phi`, `attn.mu`, (heads, head_dim))
                                          -> replicate (2 x heads x head_dim numbers; the compiler slices
                                             them to the heads a chip's q/k/v columns hold)
     12. a gated short convolution's taps (`conv.taps`, (dim, 3): 6144 numbers a layer)
                                          -> replicate (its two products, `conv.in_proj` / `conv.out_proj`, are
                                             plain kernels under rule 5; a tied embedding is rule 8's, once)
         a gated delta-rule mixer's taps (`kda.q_taps` / `k_taps` / `v_taps`, (heads x head_dim, 4)) likewise; its
         `dt_bias` is rule 6's, its head norm's scale rule 7's, its nine products plain kernels under rule 5
     13. a gated delta-rule mixer's decay rate a head (`kda.A_log`, (heads,)) -> replicate
     14. everything else                  -> replicate (catch-all)

    Rules 1-4 fall back to 'fsdp_largest' placement when the mesh has no
    'model' axis, so tp=1 reproduces the 2-axis table exactly.
    """
    return (
        PartitionRule(_TP_ATTN_QKV, 'megatron_col', name='attn-qkv'),
        PartitionRule(_TP_ATTN_OUT, 'megatron_row', name='attn-out'),
        PartitionRule(_TP_MLP_IN, 'megatron_col', name='mlp-fc1'),
        PartitionRule(_TP_MLP_OUT, 'megatron_row', name='mlp-fc2'),
        PartitionRule(_GENERIC_KERNEL, 'fsdp_largest', name='kernel'),
        # `_bias(es)` covers the decomposed-qkv q/v biases (beit/eva/swinv2)
        # and the levit/efficientformer/tinyvit attention-bias tables
        PartitionRule(r'(\.|_)bias(es)?$', 'replicate', name='bias'),
        PartitionRule(r'(^|\.)(scale|weight|gamma|gamma_1|gamma_2|gamma1|gamma2|gamma3|gamma_xca|'
                      r'lambda_q1|lambda_q2|lambda_k1|lambda_k2|logit_scale|temperature|gain)$',
                      'replicate', name='norm-scale'),
        # the leading lookahead keeps this DISJOINT from the kernel/bias
        # rules when a module is itself named pos_embed/... (xcit's conv
        # positional encoding nests real kernels under `pos_embed.`)
        PartitionRule(r'^(?!.*\.(?:kernel|bias)$)(?:.*\.)?'
                      r'(?:cls_token|reg_token|dist_token|pos_embed|pos_embed_win|pos_embed_x|pos_embed_y|'
                      r'relative_position_bias_table|rel_pos_w|rel_pos_h|embedding|latent|probe|mask_token)($|\.)',
                      'replicate', name='token-embed'),
        # `layers/moe.py` holds its experts as three bare stacks, not as Linears, and its router as a bare kernel
        PartitionRule(r'\.mlp\.(?:w_gate|w_up|w_down)$', 'fsdp_largest', name='expert-stack'),
        PartitionRule(r'\.mlp\.router$', 'replicate', name='router'),
        PartitionRule(r'\.attn\.(?:phi|mu)$', 'replicate', name='head-vector'),
        PartitionRule(r'\.(?:conv\.taps|kda\.[qkv]_taps)$', 'replicate', name='conv-taps'),
        PartitionRule(r'\.kda\.A_log$', 'replicate', name='decay-rate'),
        PartitionRule(r'.*', 'replicate', name='catch-all'),
    )


def fsdp_size(mesh: Mesh) -> int:
    """Size of the 'fsdp' axis, or 1 when the mesh has none."""
    return int(mesh.shape['fsdp']) if 'fsdp' in mesh.axis_names else 1


def tp_size(mesh: Mesh) -> int:
    """Size of the 'model' (tensor-parallel) axis, or 1 when the mesh has none."""
    return int(mesh.shape['model']) if 'model' in mesh.axis_names else 1


def match_rule(path: str, rules: Optional[Sequence[PartitionRule]] = None) -> Tuple[int, PartitionRule]:
    """First-match-wins rule lookup; returns (index, rule). The default rule
    set ends with a catch-all so this always resolves."""
    rules = rules if rules is not None else default_partition_rules()
    for i, rule in enumerate(rules):
        if rule.matches(path):
            return i, rule
    raise ValueError(f'No partition rule matched param path {path!r} '
                     f'(rule sets should end with a catch-all)')


_WARNED_PATHS = set()


def _warn_once(path: str, msg: str):
    """Log a WARNING the first time a given param path degrades — loud enough
    to audit (tests assert on it), quiet enough not to spam every step."""
    if path not in _WARNED_PATHS:
        _WARNED_PATHS.add(path)
        _logger.warning(msg)


def _fsdp_largest_spec(path: str, shape: Sequence[int], mesh: Mesh,
                       min_shard_size: int) -> P:
    """'fsdp_largest' action: shard the largest fsdp-divisible dim.

    Conv kernels (rank >= 3, nnx layout ``(*window, in // groups, out)``)
    always shard the OUTPUT-CHANNEL dim instead of the largest one: the
    spatial window dims are tiny and never divisible, and sharding the input
    dim would force an all-gather of the kernel before the contraction while
    the out dim reduce-scatters for free with the NHWC activation layout.
    Depthwise kernels (in // groups == 1) replicate — their whole weight is
    smaller than one dense row and GSPMD handles grouped convs poorly when
    the group dim is split.
    """
    n_shard = fsdp_size(mesh)
    size = int(np.prod(shape)) if len(shape) else 1
    if n_shard <= 1 or len(shape) < 2 or size < min_shard_size:
        return P()
    if len(shape) >= 3:
        if shape[-2] == 1 or shape[-1] % n_shard != 0:
            _logger.debug(f'fsdp: conv kernel {path} {tuple(shape)} depthwise or out dim '
                          f'not divisible by {n_shard}; replicating')
            return P()
        spec = [None] * len(shape)
        spec[-1] = 'fsdp'
        return P(*spec)
    # largest divisible dim → most even memory split; ties break to the
    # RIGHTMOST such dim (output features; matches megatron convention)
    best = None
    for i, d in enumerate(shape):
        if d % n_shard == 0 and (best is None or d >= shape[best]):
            best = i
    if best is None:
        _logger.debug(f'fsdp: no dim of {path} {tuple(shape)} divisible by {n_shard}; replicating')
        return P()
    spec = [None] * len(shape)
    spec[best] = 'fsdp'
    return P(*spec)


def _megatron_spec(path: str, shape: Sequence[int], mesh: Mesh, rule_name: str,
                   col: bool, min_shard_size: int) -> P:
    """'megatron_col'/'megatron_row' actions: tensor-parallel kernel split.

    Column-parallel shards the LAST dim (output features — stacked heads for
    qkv, MLP hidden for fc1) over 'model'; row-parallel shards the FIRST dim
    (input features). When the mesh also has an fsdp axis the largest
    remaining divisible dim picks up 'fsdp' too (2-D sharded weights,
    MaxText-style), which is what the optimizer m/v inherit so donation
    aliasing stays legal. Without a 'model' axis this IS 'fsdp_largest' —
    tp=1 placement is bit-identical to the 2-axis mesh. A head/hidden dim
    not divisible by the tp size replicates with a logged warning (never
    silently): the checkpoint still loads, placement is just degraded.

    Conv kernels (rank >= 3): column stays the last dim (out channels), row
    becomes dim -2 — the input-channel dim of the nnx ``(*window, in, out)``
    layout — so a 1x1 projection conv gets exactly the Linear placement.
    """
    n_tp = tp_size(mesh)
    if n_tp <= 1:
        return _fsdp_largest_spec(path, shape, mesh, min_shard_size)
    size = int(np.prod(shape)) if len(shape) else 1
    if len(shape) < 2 or size < min_shard_size:
        return P()
    if col:
        model_dim = len(shape) - 1
    else:
        model_dim = len(shape) - 2 if len(shape) >= 3 else 0
    if shape[model_dim] % n_tp != 0:
        _warn_once(path, (
            f"tp rule {rule_name!r}: {'output' if col else 'input'} dim "
            f'{shape[model_dim]} of {path} {tuple(shape)} is not divisible by '
            f"the 'model' axis size {n_tp}; replicating this param"))
        return P()
    spec = [None] * len(shape)
    spec[model_dim] = 'model'
    n_fsdp = fsdp_size(mesh)
    if n_fsdp > 1:
        best = None
        for i, d in enumerate(shape):
            if i != model_dim and d % n_fsdp == 0 and (best is None or d >= shape[best]):
                best = i
        if best is not None:
            spec[best] = 'fsdp'
    return P(*spec)


def spec_for_param(
        path: str,
        shape: Sequence[int],
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
) -> P:
    """Resolve one param's PartitionSpec from the rule table + its shape.

    Shape validation is part of the contract: when the matched rule wants to
    shard but no dimension is divisible by the owning axis size (or the param
    is tiny), the param falls back to replicated so any checkpoint loads on
    any mesh shape.
    """
    if fsdp_size(mesh) <= 1 and tp_size(mesh) <= 1:
        return P()
    _, rule = match_rule(path, rules)
    action = rule.action
    if action == 'replicate':
        return P()
    if action == 'fsdp_largest':
        return _fsdp_largest_spec(path, shape, mesh, min_shard_size)
    if action in ('megatron_col', 'megatron_row'):
        return _megatron_spec(path, shape, mesh, rule.name or rule.pattern,
                              action == 'megatron_col', min_shard_size)
    # explicit spec tuple: validate rank + divisibility, else replicate loudly
    spec = tuple(action)
    if len(spec) != len(shape):
        _logger.warning(f'fsdp rule {rule.name or rule.pattern!r} spec {spec} does not match '
                        f'rank of {path} {tuple(shape)}; replicating')
        return P()
    for axis_name, d in zip(spec, shape):
        if axis_name is not None and d % int(mesh.shape[axis_name]) != 0:
            _logger.warning(f'fsdp rule {rule.name or rule.pattern!r}: dim {d} of {path} not '
                            f'divisible by mesh axis {axis_name!r}; replicating')
            return P()
    return P(*spec)


def _kp_str(kp) -> str:
    parts = []
    for p in kp:
        for attr in ('key', 'idx', 'name'):
            if hasattr(p, attr):
                v = str(getattr(p, attr))
                if v != 'value':  # drop the nnx Variable '.value' hop
                    parts.append(v)
                break
        else:
            parts.append(str(p))
    return '.'.join(parts)


def path_specs(
        tree,
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
) -> Dict[str, P]:
    """{'.'-joined path: PartitionSpec} for every array leaf of `tree`
    (arrays or ShapeDtypeStructs both work)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        _kp_str(kp): spec_for_param(_kp_str(kp), getattr(leaf, 'shape', ()), mesh, rules, min_shard_size)
        for kp, leaf in flat
    }


def build_param_shardings(
        tree,
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
):
    """Tree of NamedShardings with `tree`'s structure (model param pytree →
    its placement). With no 'fsdp' axis every leaf is replicated, so the
    single-axis data mesh behaves exactly as before."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    shardings = [
        NamedSharding(mesh, spec_for_param(_kp_str(kp), getattr(leaf, 'shape', ()), mesh, rules, min_shard_size))
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def replicated_like(tree, mesh: Mesh):
    """Tree of fully-replicated NamedShardings with `tree`'s structure."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: rep, tree)


def inherit_param_specs(
        state_tree,
        param_path_specs: Dict[str, P],
        mesh: Mesh,
):
    """Optimizer-state shardings: each leaf whose path ENDS WITH a param path
    (optax nests the param pytree under mu/nu/trace/... so the param path is
    a suffix, e.g. `0.mu.blocks.0.attn.qkv.kernel`) inherits that param's
    spec when the shapes agree; every other leaf (step counts, injected
    hyperparams, factored-statistics vectors) is replicated.

    This is what makes buffer DONATION legal: XLA aliases a donated input to
    an output only when their shardings match, so m/v must live exactly where
    their param lives.
    """
    # longest param path first so `fc.kernel` can't shadow `blocks.0.fc.kernel`
    by_len = sorted(param_path_specs.items(), key=lambda kv: -len(kv[0]))
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_tree)
    out = []
    for kp, leaf in flat:
        path = _kp_str(kp)
        spec = P()
        for ppath, pspec in by_len:
            if path == ppath or path.endswith('.' + ppath):
                spec = pspec
                break
        # shape guard: bf16-reduced m keeps the param's shape, but factored
        # or scalar slots (adafactor row/col stats, counts) must not inherit
        # a spec of the wrong rank
        shape = getattr(leaf, 'shape', ())
        if len(spec) > len(shape) or any(
                ax is not None and shape[i] % int(mesh.shape[ax]) != 0
                for i, ax in enumerate(spec) if i < len(shape)):
            spec = P()
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def quant_scale_spec(kernel_spec: P, scale_shape: Sequence[int], mesh: Mesh) -> P:
    """Spec for a per-output-channel scale vector: it shards with the LAST
    axis of its kernel's spec (the output-channel dim it indexes), so a
    tensor-parallel column kernel keeps its dequant ``q * scale`` entirely
    shard-local — no collectives enter the serve program. Any mismatch
    (kernel replicated, scale not divisible) falls back to replicated, which
    is always legal for a vector this small."""
    if not kernel_spec or len(kernel_spec) == 0:
        return P()
    last = kernel_spec[-1]
    if last is None or not scale_shape:
        return P()
    axes = last if isinstance(last, tuple) else (last,)
    size = 1
    for ax in axes:
        size *= int(mesh.shape[ax])
    if int(scale_shape[0]) % size != 0:
        return P()
    return P(last)


def quant_path_specs(
        qstate,
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
) -> Dict[str, P]:
    """{path: spec} for a quantized ``{'qvalues', 'scales'}`` pytree.

    The int8 qvalue leaves resolve through the SAME rule table as their
    dense originals (their stripped paths are identical, and the rules are
    shape-based, not dtype-based), so fsdp/tp placement is unchanged by
    quantization. Scales inherit by path exactly like m/v/EMA inherit from
    params — see ``quant_scale_spec``.
    """
    from ..quantize.int8 import QUANT_QVALUES, QUANT_SCALES
    qvalues, scales = qstate[QUANT_QVALUES], qstate[QUANT_SCALES]
    flat, _ = jax.tree_util.tree_flatten_with_path(qvalues)
    specs: Dict[str, P] = {}
    kernel_specs: Dict[str, P] = {}
    for kp, leaf in flat:
        path = _kp_str(kp)
        spec = spec_for_param(path, getattr(leaf, 'shape', ()), mesh, rules, min_shard_size)
        specs[f'{QUANT_QVALUES}.{path}'] = spec
        kernel_specs[path] = spec
    for path, scale in scales.items():
        specs[f'{QUANT_SCALES}.{path}'] = quant_scale_spec(
            kernel_specs.get(path, P()), getattr(scale, 'shape', ()), mesh)
    return specs


def build_quant_shardings(
        qstate,
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
):
    """NamedSharding tree with the quantized pytree's structure (the quant
    analogue of ``build_param_shardings``)."""
    specs = quant_path_specs(qstate, mesh, rules, min_shard_size)
    flat, treedef = jax.tree_util.tree_flatten_with_path(qstate)
    shardings = [NamedSharding(mesh, specs[_kp_str(kp)]) for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def build_opt_shardings(optimizer, params, mesh: Mesh,
                        rules: Optional[Sequence[PartitionRule]] = None):
    """Shardings for `optimizer.init(params)`'s state without materializing
    it: `jax.eval_shape` gives the abstract state tree, then every m/v leaf
    inherits its param's spec."""
    abstract = jax.eval_shape(optimizer.init, params)
    return inherit_param_specs(abstract, path_specs(params, mesh, rules), mesh), abstract


def shard_pytree(tree, shardings):
    """device_put a pytree according to a matching tree of NamedShardings."""
    return jax.device_put(tree, shardings)


def abstract_init_sharded(init_fn: Callable, shardings_fn: Callable, *args):
    """Create state directly on-mesh without a replicated host copy:
    `jax.eval_shape(init_fn, *args)` determines the output structure,
    `shardings_fn(abstract_out)` assigns a NamedSharding per leaf, and the
    jitted init materializes each shard on its owning devices only.

    This is the PERF.md §2 item 3 memory story for optimizer state: AdamW m/v
    for ViT-L is ~2.4 GB fp32 replicated; created through here on an fsdp=4
    axis each device ever holds ~0.6 GB.
    """
    abstract = jax.eval_shape(init_fn, *args)
    shardings = shardings_fn(abstract)
    try:
        return jax.jit(init_fn, out_shardings=shardings)(*args), shardings
    except Exception as e:  # pragma: no cover - exotic non-traceable init
        _logger.warning(f'abstract sharded init failed ({e!r}); falling back to '
                        'eager init + device_put (a transient replicated copy exists)')
        return jax.device_put(init_fn(*args), shardings), shardings


def create_sharded_model(
        factory: Callable[[], Any],
        mesh: Mesh,
        rules: Optional[Sequence[PartitionRule]] = None,
        min_shard_size: int = MIN_SHARD_SIZE,
):
    """Build an nnx model with its params created DIRECTLY on-mesh.

    `nnx.eval_shape(factory)` runs the constructor abstractly (no arrays are
    materialized), the partition rules are resolved against the abstract
    param shapes, and a jitted `factory()` with `out_shardings` initializes
    each param shard on its owning devices — a replicated host copy of the
    full model never exists. Falls back to eager construction + device_put
    for factories that do not trace (e.g. pretrained-weight loading inside
    the constructor), which preserves behaviour at a transient memory cost.
    """
    from flax import nnx

    try:
        abs_model = nnx.eval_shape(factory)
        graphdef, abs_state = nnx.split(abs_model)
        flat, treedef = jax.tree_util.tree_flatten_with_path(abs_state)
        shardings = jax.tree_util.tree_unflatten(treedef, [
            NamedSharding(mesh, spec_for_param(_kp_str(kp), getattr(leaf, 'shape', ()), mesh, rules, min_shard_size))
            for kp, leaf in flat
        ])

        def init_state():
            return nnx.state(factory())

        state = jax.jit(init_state, out_shardings=shardings)()
        return nnx.merge(graphdef, state)
    except Exception as e:
        _logger.warning(f'create_sharded_model: abstract init failed ({e!r}); '
                        'building eagerly and resharding')
        model = factory()
        graphdef, state = nnx.split(model)
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        shardings = jax.tree_util.tree_unflatten(treedef, [
            NamedSharding(mesh, spec_for_param(_kp_str(kp), getattr(leaf, 'shape', ()), mesh, rules, min_shard_size))
            for kp, leaf in flat
        ])
        nnx.update(model, jax.device_put(state, shardings))
        return model


def _spec_shard_count(spec: P, mesh: Mesh) -> int:
    """How many ways a spec splits a tensor: the product of the mesh sizes of
    every named axis in it (a 2-D ('fsdp','model') spec divides bytes by
    fsdp_size * tp_size, not fsdp_size alone)."""
    n = 1
    for ax in spec:
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= int(mesh.shape[a])
    return n


def leaf_itemsize(dtype) -> int:
    """Physical bytes per element, tolerant of extended dtypes: typed PRNG
    key leaves (``key<fry>`` — swin-style blocks keep their DropPath/attn
    Rngs in state) have no numpy dtype; count their uint32 key data
    (threefry = 2 words) instead of crashing the byte accounting."""
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return 8


def param_bytes_per_device(tree, mesh: Mesh,
                           rules: Optional[Sequence[PartitionRule]] = None) -> Tuple[int, int]:
    """(replicated_bytes, sharded_bytes) a single device would hold for
    `tree` under the rule set — the PERF.md 'Sharding & memory' numbers.
    Sharded bytes divide by the product of EVERY mesh axis in the param's
    spec (fsdp x model for the 2-D tensor-parallel kernels)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    rep = shard = 0
    for kp, leaf in flat:
        nbytes = int(np.prod(getattr(leaf, 'shape', ()) or (1,))) * leaf_itemsize(leaf.dtype)
        rep += nbytes
        spec = spec_for_param(_kp_str(kp), getattr(leaf, 'shape', ()), mesh, rules)
        shard += nbytes // _spec_shard_count(spec, mesh)
    return rep, shard


def activation_bytes_per_device(
        mesh: Mesh,
        *,
        batch_size: int,
        seq_len: int,
        width: int,
        depth: int,
        mlp_ratio: float = 4.0,
        bytes_per_elem: int = 4,
) -> Tuple[int, int]:
    """(unconstrained_bytes, constrained_bytes) of transformer-block
    activations one device holds per step — the PERF.md companion to
    `param_bytes_per_device` for fsdp x tp grids.

    Counts the dominant per-block tensors (residual stream, q/k/v, MLP
    hidden ~ seq_len x width x (4 + mlp_ratio) elements) across `depth`
    blocks. 'Unconstrained' is the PR-5 state: the batch dim shards over the
    non-'model' axes but channels replicate, so adding tp devices buys no
    activation memory (this is exactly the involuntary-remat regime).
    'Constrained' applies the parallel/constraints.py specs: channel/head/
    hidden dims additionally shard over 'model' where divisible, so
    activation bytes scale ~1/tp. With tp=1 the two numbers are equal.
    """
    n_tp = tp_size(mesh)
    n_batch = max(1, int(np.prod([int(s) for s in mesh.shape.values()])) // n_tp)
    hidden = int(width * mlp_ratio)

    def elems(channel_div: bool) -> int:
        resid_qkv = 4 * seq_len * width // (n_tp if channel_div and width % n_tp == 0 else 1)
        mlp = seq_len * hidden // (n_tp if channel_div and hidden % n_tp == 0 else 1)
        return batch_size * depth * (resid_qkv + mlp)

    unconstrained = elems(False) * bytes_per_elem // n_batch
    constrained = elems(True) * bytes_per_elem // n_batch
    return unconstrained, constrained
