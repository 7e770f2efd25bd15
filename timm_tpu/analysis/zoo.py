"""The whole-zoo abstract-trace sweep — ROADMAP item 5's first
model-agnostic gate.

For every registered family, pick one representative, construct it under
``nnx.eval_shape`` (no parameter arrays allocated) and push an abstract
batch through ``jax.eval_shape`` (no compiles). A family that cannot even
trace — a constructor kwarg mismatch, a shape bug at its native input size
— fails here in milliseconds instead of hiding behind `-m slow`. This
sweep is exactly how the res2net/resnest/sknet `aa_layer` constructor bug
was found: those families only ever ran under `-m slow`, so tier-1 never
built them.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from .registry import AnalysisContext, rule
from .report import Finding

__all__ = ['family_representative', 'abstract_batch', 'sweep', 'SMOKE_FAMILIES',
           'SIZE_OVERRIDES']

# families cheap enough for the tier-1 smoke (full sweep: CLI + -m slow);
# swin + metaformer keep the hierarchical stage-scan families represented
# alongside convnext, per ISSUE 20
SMOKE_FAMILIES: Tuple[str, ...] = (
    'vision_transformer', 'resnet', 'convnext', 'naflexvit', 'mlp_mixer',
    'swin_transformer', 'metaformer',
)

# native-input-size overrides where the default cfg size cannot trace:
# halo attention needs its block/halo grid, efficientformer's attention
# bias table is built for the 224px stage-4 resolution
SIZE_OVERRIDES: Dict[str, int] = {
    'halonet26t': 256,
    'efficientformer_l1': 224,
}

_NUM_CLASSES = 10
_BATCH = 2
_SEQ_LEN = 128     # tokens a sequence, for a model whose input is token ids


def abstract_batch(model, size: int):
    """(abstract inputs, output shape owed) for a model, by what the model says it is: a `task_kind` of
    'causal_lm' takes token ids (B, S), one of 'block_diffusion_lm' the noised and the clean ids, and both owe
    logits over `model.num_classes`; every other model is an image classifier built with `_NUM_CLASSES`. No list
    of families: a new token family is swept as it registers."""
    import jax
    import jax.numpy as jnp
    id_tensors = {'causal_lm': 1, 'block_diffusion_lm': 2}.get(getattr(model, 'task_kind', None))
    if id_tensors:
        return (jax.ShapeDtypeStruct((_BATCH, _SEQ_LEN), jnp.int32),) * id_tensors, (_BATCH, _SEQ_LEN, model.num_classes)
    return (jax.ShapeDtypeStruct((_BATCH, size, size, 3), jnp.float32),), (_BATCH, _NUM_CLASSES)


def family_representative(module: str) -> Tuple[str, int]:
    """(model_name, img_size) for one family: prefer the test_* fixture
    model, else the first registered name; size from the pretrained cfg."""
    import timm_tpu
    from ..models._registry import get_pretrained_cfg

    names = timm_tpu.list_models(module=module)
    if not names:
        raise ValueError(f'family {module!r} registers no models')
    test = [n for n in names if n.startswith('test_')]
    name = test[0] if test else names[0]
    if name in SIZE_OVERRIDES:
        return name, SIZE_OVERRIDES[name]
    cfg = get_pretrained_cfg(name)
    size = getattr(cfg, 'input_size', None)
    return name, int(size[-1]) if size else 224


def sweep(families: Optional[Sequence[str]] = None,
          log=None) -> List[Dict]:
    """Abstract-trace every family -> [{'module', 'model', 'img_size',
    'ok', 'out_shape' | 'error'}]. No arrays, no compiles."""
    import jax
    from flax import nnx

    import timm_tpu

    records = []
    for module in (families or timm_tpu.list_modules()):
        name, size = family_representative(module)
        rec: Dict = {'module': module, 'model': name, 'img_size': size}
        try:
            model = nnx.eval_shape(
                lambda n=name: timm_tpu.create_model(n, num_classes=_NUM_CLASSES))
            model.eval()
            graphdef, state = nnx.split(model)
            batch, owed = abstract_batch(model, size)
            if batch[0].ndim == 2:
                rec.update(img_size=None, seq_len=_SEQ_LEN)
            out = jax.eval_shape(lambda s, *x: nnx.merge(graphdef, s)(*x), state, *batch)
            rec['out_shape'] = tuple(out.shape)
            rec['ok'] = tuple(out.shape) == owed
            if not rec['ok']:
                rec['error'] = f'abstract forward returned {rec["out_shape"]}, expected {owed}'
        except Exception as e:  # noqa: BLE001 - each family reports its own failure
            rec['ok'] = False
            rec['error'] = f'{type(e).__name__}: {e}'
        records.append(rec)
        if log is not None:
            status = 'ok' if rec['ok'] else f'FAIL {rec["error"]}'
            log(f'zoo {module}: {name}@{rec.get("seq_len", size)} {status}')
    return records


@rule('zoo-abstract-trace', 'B',
      'every registered family constructs under nnx.eval_shape and its '
      'representative abstract-forwards to (B, num_classes) at its native '
      'input size — no arrays, no compiles (ROADMAP item 5 gate)')
def zoo_abstract_trace(ctx: AnalysisContext) -> List[Finding]:
    records = sweep(families=ctx.zoo_families, log=ctx.log)
    return [Finding('zoo-abstract-trace', f'{r["module"]}:{r["model"]}', 0,
                    r.get('error', 'failed'))
            for r in records if not r['ok']]
