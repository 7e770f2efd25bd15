"""Device-mesh and sharding helpers.

TPU-native replacement for the reference's DDP/NCCL stack
(reference: timm/utils/distributed.py:79-159, task/classification.py:64-66).

Data parallelism is expressed as a mesh, not processes: batches are sharded
over the batch axes, params are replicated (or fsdp/tensor-sharded, see
parallel/sharding.py), and XLA emits the grad all-reduce over ICI/DCN.

Mesh shapes:
  * `('data',)` — plain data parallelism (the default);
  * `('dcn', 'data')` — multi-host pods with multiple DCN slices, so
    collectives ride ICI within a slice;
  * `('data', 'fsdp')` / `('dcn', 'data', 'fsdp')` — ZeRO-style sharding:
    the BATCH is sharded over the product of every axis (all devices see
    different samples), while params/optimizer state shard over 'fsdp' only;
  * `('data', 'fsdp', 'model')` — adds Megatron-style tensor parallelism:
    attention QKV/proj kernels shard heads and MLP fc1/fc2 kernels shard the
    hidden dim over 'model', and activation sharding constraints
    (parallel/constraints.py) keep the residual stream and attention/MLP
    internals sharded inside the block scan. The INPUT batch still shards
    over the product of all axes (maximum host→device transfer parallelism);
    the model's first residual constraint redistributes it to
    (batch over data×fsdp) × (channels over model).
"""
from __future__ import annotations

import math
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    'create_mesh', 'data_sharding', 'replicate_sharding', 'shard_batch',
    'get_global_mesh', 'set_global_mesh', 'peek_global_mesh', 'batch_axes',
    'nonmodel_batch_axes', 'resolve_elastic_axes', 'place_global',
    'mesh_process_count', 'use_virtual_cpu_devices',
]

_GLOBAL_MESH: Optional[Mesh] = None


def _mesh_axes_str(axes) -> str:
    """'data=2, fsdp=2, model=2 (8 devices)' from {axis: size} pairs."""
    items = list(axes.items() if isinstance(axes, dict) else axes)
    total = int(np.prod([s for _, s in items])) if items else 1
    return ', '.join(f'{n}={s}' for n, s in items) + f' ({total} devices)'


def use_virtual_cpu_devices(n: int) -> None:
    """Hold this process to `n` virtual CPU devices — the declared platform of
    the CPU analysis tools and rehearsals (``python -m timm_tpu.analysis`` /
    ``.perfbudget`` / ``.analysis.coverage``, ``__graft_entry__``). An explicit
    choice that must precede the process's first JAX device call: no chip is
    touched and no child is started. JAX raises if a backend is already up
    with another device count."""
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', n)


def create_mesh(
        devices: Optional[Sequence] = None,
        data_axis: str = 'data',
        num_slices: Optional[int] = None,
        fsdp: Optional[int] = None,
        tp: Optional[int] = None,
) -> Mesh:
    """Data-parallel mesh, optionally with 'fsdp' (parameter sharding) and
    'model' (tensor parallelism) axes.

    `fsdp=N` (or env TIMM_TPU_FSDP) folds N devices of each data group into a
    second axis; `tp=M` (or env TIMM_TPU_TP) folds M more into a trailing
    'model' axis: 8 devices with fsdp=2, tp=2 gives a
    ``('data', 'fsdp', 'model')`` mesh of shape (2, 2, 2). Batches shard over
    the product of ALL axes (see `shard_batch`); params/optimizer state shard
    over 'fsdp', and attention-head / MLP-hidden kernel dims (plus the
    activation constraints) shard over 'model' (parallel/sharding.py). With
    multiple DCN slices the mesh is ``('dcn', data_axis[, 'fsdp'][, 'model'])``
    so collectives ride ICI within a slice. `fsdp=1`/`tp=1` (the defaults)
    omit their axes entirely, reproducing the smaller-mesh behaviour exactly.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if fsdp is None:
        fsdp = int(os.environ.get('TIMM_TPU_FSDP', '1') or 1)
    fsdp = max(1, fsdp)
    if tp is None:
        tp = int(os.environ.get('TIMM_TPU_TP', '1') or 1)
    tp = max(1, tp)
    if num_slices is None:
        # group by slice when the platform reports one (TPU pods); otherwise
        # one DCN group per host process — this is what makes the 'dcn' axis
        # real for multi-process CPU clusters, where devices carry a
        # process_index but no slice_index. jax.devices() is process-major,
        # so reshape(num_slices, -1) puts each process's devices in one row.
        slice_ids = {getattr(d, 'slice_index', None) for d in devices}
        if len(slice_ids) == 1 and getattr(devices[0], 'platform', '') == 'cpu':
            # multi-process CPU clusters report one slice (or none), but the
            # cross-process links are gRPC — DCN-class, not ICI. Group by
            # process so the 'dcn' axis is real. Single-slice TPU pods keep
            # their all-ICI mesh (one slice, no dcn axis).
            slice_ids = {getattr(d, 'process_index', 0) for d in devices}
        num_slices = len(slice_ids)
    # trailing axes (closest ICI neighbours) host the most collective-hungry
    # parallelism: fsdp before model, model innermost
    trailing = []
    if fsdp > 1:
        trailing.append(('fsdp', fsdp))
    if tp > 1:
        trailing.append(('model', tp))
    if trailing:
        per_slice = len(devices) // max(num_slices, 1)
        n_trail = fsdp * tp
        if per_slice % n_trail != 0:
            axes = [('data', per_slice // n_trail if n_trail and per_slice % n_trail == 0 else '?'),
                    ('fsdp', fsdp), ('model', tp)]
            raise ValueError(
                f'mesh axes fsdp={fsdp} x tp={tp} = {n_trail} must divide the {per_slice} '
                f'devices per slice ({len(devices)} devices / {num_slices} slice(s)); '
                f'requested mesh would be ({", ".join(f"{n}={s}" for n, s in axes)})')
        shape = [-1] + [s for _, s in trailing]
        names = (data_axis,) + tuple(n for n, _ in trailing)
        if num_slices > 1:
            dev_array = np.array(devices).reshape(num_slices, *shape)
            return Mesh(dev_array, ('dcn',) + names)
        return Mesh(np.array(devices).reshape(*shape), names)
    if num_slices > 1:
        dev_array = np.array(devices).reshape(num_slices, -1)
        return Mesh(dev_array, ('dcn', data_axis))
    return Mesh(np.array(devices), (data_axis,))


def resolve_elastic_axes(
        n_devices: int,
        fsdp: Optional[int] = None,
        tp: Optional[int] = None,
        num_slices: int = 1,
) -> Tuple[Optional[int], Optional[int]]:
    """Clamp requested fsdp/tp axis sizes to the LIVE topology.

    An elastic restart reuses the dead run's ``--fsdp``/``--tp`` flags, but
    the surviving device count may no longer divide the same way. Each
    request is clamped to the largest divisor of the available per-slice
    device count not exceeding it — tp first (innermost, most
    collective-hungry axis), then fsdp within the remaining factor — so
    ``create_mesh(fsdp=..., tp=...)`` is guaranteed to accept the result.
    Returns ``(fsdp, tp)`` with None where the axis should be omitted,
    matching create_mesh's treatment of ``fsdp=1``/``tp=1``.

    This largest-divisor policy is the DOCUMENTED FALLBACK of elastic resume:
    `plan_elastic_resume` first asks the autotune solver
    (`timm_tpu.autotune.resolve_config_for_topology`) to re-solve
    (fsdp, tp, batch, accum) by cost rank for the new topology — a still-legal
    requested config passes through unchanged — and lands here whenever the
    solver refuses (no model dims, no legal point, any solver error). The
    clamp is topology-only: it guarantees a mesh, not a good one.
    """
    per_slice = max(1, int(n_devices) // max(1, int(num_slices)))

    def largest_divisor(request: int, limit: int) -> int:
        d = min(int(request), limit)
        while limit % d:
            d -= 1
        return d

    tp_eff = largest_divisor(tp, per_slice) if tp and int(tp) > 1 else 1
    fsdp_eff = largest_divisor(fsdp, per_slice // tp_eff) if fsdp and int(fsdp) > 1 else 1
    return (fsdp_eff if fsdp_eff > 1 else None, tp_eff if tp_eff > 1 else None)


def set_global_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = create_mesh()
    return _GLOBAL_MESH


def peek_global_mesh() -> Optional[Mesh]:
    """The global mesh if one was set, WITHOUT creating a default one — the
    zero-cost probe the activation-constraint helpers use on every layer call
    (parallel/constraints.py): no mesh or no 'model' axis → no-op."""
    return _GLOBAL_MESH


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch is sharded over EVERY mesh axis — including 'fsdp' and 'model':
    from the host's view all devices are data-parallel workers; only the
    parameter placement and the in-model activation constraints distinguish
    the fsdp/model sub-axes."""
    return tuple(n for n in mesh.axis_names)


def nonmodel_batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch axes for ACTIVATIONS inside the model: everything but 'model'.
    Under tensor parallelism the 'model' axis carries head/hidden channel
    shards, so the activation batch dim shards over the remaining axes only
    (the residual-stream constraint redistributes the input batch once)."""
    return tuple(n for n in mesh.axis_names if n != 'model')


_batch_axes = batch_axes  # backwards-compat private alias


def data_sharding(mesh: Mesh, ndim: int = 4) -> NamedSharding:
    """Shard the leading (batch) dim over every mesh axis; replicate the rest."""
    return NamedSharding(mesh, P(batch_axes(mesh), *([None] * (ndim - 1))))


def replicate_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_process_count(mesh: Mesh) -> int:
    """How many distinct host processes own devices of this mesh (1 for every
    single-process run, regardless of device count)."""
    return len({getattr(d, 'process_index', 0) for d in mesh.devices.flat})


def place_global(x, sharding: NamedSharding):
    """`jax.device_put` that also works for non-fully-addressable shardings.

    In a multi-process run a sharding spanning other hosts' devices cannot be
    device_put from host data; `make_array_from_callback` builds the global
    array from the locally-addressable pieces instead (each process supplies
    only the index slices its own devices need). Single-process shardings take
    the plain device_put fast path, byte-for-byte identical to before."""
    if getattr(sharding, 'is_fully_addressable', True):
        return jax.device_put(x, sharding)
    xnp = np.asarray(x)
    return jax.make_array_from_callback(xnp.shape, sharding, lambda idx: xnp[idx])


def shard_batch(batch, mesh: Optional[Mesh] = None):
    """Place a host batch (pytree of arrays) sharded over the mesh batch axes
    (their product for multi-axis ('data', 'fsdp'[, 'model']) meshes).
    Non-array leaves pass through; 0-d arrays are replicated (a rank-0 value
    has no batch dim to shard — seq_len/step counters in dict batches).

    Multi-process meshes: each process passes its PROCESS-LOCAL batch (the
    loaders shard by process_index); the global batch is assembled via
    `jax.make_array_from_process_local_data`, with the global batch dim =
    local rows x participating processes. Device order is process-major, so
    process p contributes rows [p*local, (p+1)*local) of the global batch.

    Raises a loud ValueError when the global batch is not divisible by the
    total batch-shard count — the alternative is an opaque XLA reshape error
    from deep inside the jitted step."""
    mesh = mesh or get_global_mesh()
    axes = batch_axes(mesh)
    sizes = [(a, int(mesh.shape[a])) for a in axes]
    n_shards = int(np.prod([s for _, s in sizes]))
    n_procs = mesh_process_count(mesh)

    def put(x):
        ndim = getattr(x, 'ndim', None)
        if ndim is None:
            return x
        if ndim == 0:
            return place_global(x, replicate_sharding(mesh))
        global_b = x.shape[0] * n_procs
        if global_b % n_shards != 0:
            b = x.shape[0]
            step = n_shards * n_procs // math.gcd(n_shards, n_procs)
            lo, hi = (global_b // step) * step, -(-global_b // step) * step
            nearest = f'{hi}' if lo == 0 else f'{lo} or {hi}'
            local_hint = '' if n_procs == 1 else (
                f' ({lo // n_procs} or {hi // n_procs} local rows per process)')
            raise ValueError(
                f'Global batch dim {global_b} ({b} local rows x {n_procs} process(es)) '
                f'is not divisible by the mesh batch-shard '
                f'count {n_shards}: the batch shards over the product of ALL mesh axes '
                f'({_mesh_axes_str(sizes)}). Nearest legal global batch: '
                f'{nearest}{local_hint}. '
                f'Pad the batch or pick a batch size that divides evenly — e.g. '
                f'validate.py pads the final partial batch.')
        sharding = data_sharding(mesh, ndim=ndim)
        if n_procs > 1:
            xnp = np.asarray(x)
            return jax.make_array_from_process_local_data(
                sharding, xnp, (global_b,) + xnp.shape[1:])
        return jax.device_put(x, sharding)
    return jax.tree.map(put, batch)
