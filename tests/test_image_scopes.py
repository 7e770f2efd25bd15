"""The device scopes of the image models and of every task's step (`tracing.scope`: `img.*` on the shared layers,
`step.*` in `TrainingTask.train_step`), on toy models on the CPU: every scope is in the step's op names, forward and
backward where the scope has a backward pass; the scopes cover the step; they are metadata and nothing else; and the
step program's compiled text is kept for a reader only when somebody lowers the step ahead of time."""
import contextlib
import os
import re
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import timm_tpu  # noqa: E402
from benchmarks.harness import device_scopes, step_scopes  # noqa: E402
from timm_tpu.loss import SoftTargetCrossEntropy  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask, ClassificationTask  # noqa: E402
from timm_tpu.utils import tracing  # noqa: E402

OP_NAME = re.compile(r'"(jit\(train_step\)/[^"]*)"')
STEP = {'step.clip', 'step.update', 'step.guard'}                  # every recipe below clips, updates and guards
IMAGE_STEP = STEP | {'step.input', 'step.loss', 'step.ema'}
VIT = {'img.patch_embed', 'img.block', 'img.norm', 'img.attn.qkv', 'img.attn.core', 'img.attn.proj', 'img.mlp', 'img.head'}
CONVNEXT = {'img.stem', 'img.downsample', 'img.block', 'img.norm', 'img.mlp', 'img.conv_dw', 'img.head'}
GLM = {'glm.embed', 'glm.mla.proj', 'glm.mla.core', 'glm.dense_ffn', 'glm.moe.route', 'glm.moe.shared', 'glm.head_loss'}


def image_task(name: str) -> ClassificationTask:
    """The recipe of the benchmark's image cells at a toy size: soft targets, AdamW, clip, EMA, the guard,
    stochastic depth, the batch normalised inside the step."""
    model = timm_tpu.create_model(name, num_classes=10, drop_path_rate=0.1)
    task = ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05),
                              train_loss_fn=SoftTargetCrossEntropy(), clip_grad=1.0, mean=(0.5,) * 3, std=(0.2,) * 3)
    task.setup_ema(0.999)
    return task


IMAGE_BATCH = {'input': jnp.zeros((2, 160, 160, 3), jnp.uint8), 'target': jnp.full((2, 10), 0.1, jnp.float32)}


def lm_task() -> CausalLMTask:
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    return CausalLMTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1),
                        clip_grad=1.0, loss_chunk=32)


LM_BATCH = {'input': jnp.zeros((2, 64), jnp.int32), 'target': jnp.zeros((2, 64), jnp.int32)}


def lowered(task, batch):
    step_fn, args = task._train_step_args(batch, 1e-3, 0)
    return step_fn.lower(*args)


def innermost(op_names, names) -> dict:
    """scope -> the op names whose innermost declared scope it is."""
    out = {}
    for op in op_names:
        out.setdefault(device_scopes.scope_of(op, names), []).append(op)
    return out


@pytest.mark.parametrize('build,batch,forward,backward', [
    (lambda: image_task('test_vit'), IMAGE_BATCH, VIT | IMAGE_STEP, VIT | {'step.loss'}),
    (lambda: image_task('test_convnext'), IMAGE_BATCH, CONVNEXT | IMAGE_STEP, CONVNEXT | {'step.loss'}),
    (lm_task, LM_BATCH, GLM | STEP, GLM)], ids=['vit', 'convnext', 'glm'])
def test_the_lowered_step_holds_every_scope_its_model_should_have_forward_and_backward(build, batch, forward, backward):
    names = step_scopes.declared_scopes()
    assert forward <= names
    by_scope = innermost(set(OP_NAME.findall(lowered(build(), batch).as_text(debug_info=True))), names)
    found = set(by_scope) - {None}
    # no other model's, and of the step's only what the recipe runs: `step.loss` and `step.input` are not the LM tasks'
    assert forward <= found and not [s for s in found - forward if s.startswith(('img.', 'step.'))], found ^ forward
    for scope in forward:                                          # `backward`: the scopes whose ops have a transpose
        ops = by_scope[scope]
        assert any('transpose(' not in op for op in ops), scope
        assert any('transpose(jvp(' in op for op in ops) == (scope in backward), scope


@pytest.mark.parametrize('name', ['test_vit', 'test_convnext'])
def test_the_scopes_cover_the_compiled_step_of_an_image_model(name):
    """Of the compiled text's instructions that carry an op name of the step's trace (a reducer's body reads
    `reduce_sum`, a parameter its argument's name: no op of a trace), at least 95 % fall under a declared scope."""
    names = step_scopes.declared_scopes()
    text = lowered(image_task(name), IMAGE_BATCH).compile().as_text()
    scoped = device_scopes.instruction_scopes(text, names)
    starts = list(device_scopes.INSTRUCTION.finditer(text))
    traced = 0
    for m, following in zip(starts, starts[1:] + [None]):
        found = device_scopes.OP_NAME.search(text, m.end(), following.start() if following else len(text))
        traced += bool(found and found.group(1).startswith('jit(train_step)/'))
    assert traced > 1000 and len(scoped) >= 0.95 * traced, (len(scoped), traced)


def test_the_lm_step_keeps_its_own_scopes_beside_the_steps():
    """No `step.*` scope takes an op out of a `glm.*` one: the model's ops sit under the model's scopes, the update's
    under the step's, and no op name holds one of each."""
    names = step_scopes.declared_scopes()
    ops = set(OP_NAME.findall(lowered(lm_task(), LM_BATCH).as_text(debug_info=True)))
    mixed = [op for op in ops if {t.split('.')[0] for t in device_scopes.SCOPE_TOKEN.findall(op) if t in names} >= {'glm', 'step'}]
    assert not mixed, mixed[:3]
    assert not [op for op in ops if 'img.' in op]                  # RmsNorm and SwiGLU carry no image scope


@pytest.mark.parametrize('build,batch', [(lambda: image_task('test_vit'), IMAGE_BATCH),
                                         (lambda: image_task('test_convnext'), IMAGE_BATCH), (lm_task, LM_BATCH)],
                         ids=['vit', 'convnext', 'glm'])
def test_scopes_are_metadata_only(build, batch, monkeypatch):
    """The step's lowered text without debug info is byte for byte the same with `tracing.scope` replaced by a null
    context: a scope costs a step nothing, and the compile cache's key (taken from this text) does not see it, so a
    cached executable keeps the op names it was compiled with (PERF.md section 7)."""
    with_scopes = lowered(build(), batch).as_text()
    monkeypatch.setattr(tracing, 'scope', lambda name: contextlib.nullcontext())
    without = lowered(build(), batch).as_text()
    assert with_scopes == without and 'img.' not in with_scopes and 'step.' not in with_scopes


def test_the_step_programs_text_is_kept_only_when_somebody_lowers_the_step_ahead_of_time(monkeypatch):
    monkeypatch.setattr(tracing, '_programs', {})
    assert tracing.program_text('task.step_call') is None
    task = image_task('test_vit')
    task.train_step(IMAGE_BATCH, 1e-3, 0)
    task.drain()
    assert tracing.program_text('task.step_call') is None          # the training path keeps nothing
    compiled = task.lower_train_step(IMAGE_BATCH, 1e-3, 1)
    text = tracing.program_text('task.step_call')
    assert text == compiled.as_text() and 'img.attn.core' in text and step_scopes.module_name(text) == 'jit_train_step'
    other = image_task('test_convnext')
    assert other.lower_train_step(IMAGE_BATCH, 1e-3, 0).as_text() == tracing.program_text('task.step_call') != text
    with pytest.raises(KeyError, match='not declared'):
        tracing.program_text('task.made_up')
