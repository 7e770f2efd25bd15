"""The program's one tracing mechanism: host spans and counters at the
boundaries of the training path, always on, in one bounded ring.

`span(name, step=...)` records wall and thread-CPU time of a block, the span
that encloses it and the step it belongs to, and enters a
`jax.profiler.TraceAnnotation` for the same interval: whenever a profiler
session runs, the span is in the trace's host plane, on the clock the device
planes use. `count`, `busy` and `gauge` keep integers and sampled values at the
same boundaries; every program XLA builds is recorded as an
`xla.backend_compile` span under whatever span was open when it was built.

`scope(name)` names device ops (`jax.named_scope`) and `device_counter(name,
value)` declares a value the step program returns in its metrics: both are
free on the host, and read from a profiler trace / the step's metrics.

Nothing is written out and nothing switches it off: readers (`train.py`'s log
line, `benchmarks/harness/program_spans.py`, tests) take `snapshot()` or
`summary()`. A device scope is in a trace only through the compiled program's
text (an op event carries its instruction, not its metadata), so whoever
compiles a program ahead of time hands it over (`keep_program(name, compiled)`,
under the span that dispatches the program: `TrainingTask.lower_train_step`
keeps the step's under `task.step_call`) and `program_text(name)` gives that
text to any reader in the process, or None: one string a name, replaced by the
next, never kept on the training path. Every name is declared in `SPANS`;
`PERF.md` section 3 says which metric reads which.
"""
from __future__ import annotations

import collections
import itertools
import math
import re
import statistics
import threading
import time
from typing import NamedTuple, Optional

import jax

RING = 32768          # spans kept: set-up plus minutes of steps at ~15 spans a step
SERIES = 4096         # samples kept per gauge, and counter marks (one per step root)

# name -> (layer as PERF.md section 3 has it, what it covers)
SPANS = {
    'train.step': ('entry and compile cache', 'one pass of the loop for one update: fetch, place, step, bookkeeping'),
    'train.loader_next': ('input', "the loop's next() on the loader: all the main thread does to get a batch"),
    'train.batch_to_device': ('input', 'jnp.asarray + shard_batch in the loop (a no-op for device batches)'),
    'train.bookkeeping': ('entry and compile cache', 'scheduler update, recovery-interval check, fault and shutdown poll'),
    'train.log_sync': ('entry and compile cache', "float(metrics['loss']) at --log-interval: a deliberate sync"),
    'setup.model_build': ('entry and compile cache', 'create_model, sharded init included'),
    'setup.task_build': ('entry and compile cache', 'optimizer, task, loss, EMA'),
    'setup.data_build': ('entry and compile cache', 'datasets and both loaders'),
    'xla.backend_compile': ('entry and compile cache', 'one program built or read from the cache (jax.monitoring)'),
    'loader.batch_wait': ('input', "main thread blocked on the collator's queue"),
    'loader.batch_q_depth': ('input', 'gauge: collated batches waiting when the main thread asks; 0 = loader behind'),
    'loader.h2d': ('input', 'host-to-device put of the next uint8 batch'),
    'loader.sample_params': ('input', 'erase / mixup parameter draws, numpy on the main thread'),
    'loader.augment_call': ('input', 'placing the parameters and dispatching the augment program'),
    'loader.samples': ('input', "counter: samples read, decoded and transformed by the decode stage's workers (processes report theirs with each hand-over)"),
    'loader.decode_busy_ns': ('input', 'counter: wall ns the workers spent on those samples, summed over workers'),
    'loader.batches': ('input', 'counter: batches the collator thread handed over'),
    'loader.decode_procs': ('input', 'gauge: decode processes alive when the main thread asks for a batch; 0 after close()'),
    'loader.worker_exits': ('input', 'counter: decode processes that ended before the loader closed them; 0 in a sound run'),
    'task.train_step': ('step', 'the whole TrainingTask.train_step call'),
    'task.state_split': ('step', "the arrays of the model's bound Variables read into two flat tuples"),
    'task.scalars_put': ('step', 'the two jnp.asarray scalar transfers (lr, ema decay)'),
    'task.step_call': ('step', 'the jitted call on flat tuples: dispatch, the drop of the donated optimizer arrays; first call also trace + compile'),
    'task.state_update': ('step', 'the returned arrays written into the same Variables (dropping the donated ones) + EMA / sentinel leaves kept'),
    'task.sentinel_poll': ('step', "sentinel.observe(): the device_get of the counters of the step BEFORE the one just enqueued (drain(): of the last)"),
    'task.sentinel_polls': ('step', 'counter: observe() calls that read the device'),
    'task.polls_host_ahead': ('step', "counter: polls that found the observed step's counters not ready yet: the device still had work queued and did not wait for the host"),
    'task.state_binds': ('step', "counter: times the step was (re)built and bound to the model's Variables; 1 a run"),
    # device scopes (`scope`): jax.named_scope names on the program's ops, read from a trace's XLA Ops line
    'glm.embed': ('step', 'device scope: token embedding lookup'),
    'glm.mla.proj': ('attention', 'device scope: latent attention projections, their two RMSNorms and the rotary turn'),
    'glm.mla.core': ('attention', 'device scope: causal softmax(q k^T) v in query blocks, forward and backward'),
    'glm.dense_ffn': ('step', 'device scope: the leading dense SwiGLU'),
    'glm.moe.route': ('experts', 'device scope: router, top-k, sort, gather into expert order, weighted combine'),
    'glm.moe.experts': ('experts', 'device scope: the grouped products over the experts held'),
    'glm.moe.shared': ('experts', 'device scope: the shared expert'),
    'glm.mtp': ('step', 'device scope: the multi-token-prediction module\'s own projection, norms and head (its block runs outside the scope, under the mla/moe scopes of its layers)'),
    'glm.head_loss': ('step', 'device scope: final norm, output head and cross-entropy, in chunks'),
    'swa.attn.proj': ('attention', 'device scope: grouped-query q/k/v/o products, the norm before them, the rotary turn'),
    'swa.attn.core_full': ('attention', 'device scope: the causal core of a full (position-free) layer, forward and backward'),
    'swa.attn.core_window': ('attention', 'device scope: the causal core of a window layer, forward and backward'),
    'swa.attn.core_bd': ('attention', 'device scope: the core under the block-diffusion mask (a noised copy beside the clean sequence), forward and backward'),
    'evabyte.attn.proj': ('attention', 'device scope: the q/k/v/o products of the heads held, the norm before them, the rotary turn, the float32 residual add'),
    'evabyte.attn.summary': ('attention', 'device scope: the chunk softmax against a head\'s learned vector and the two pooled sums (summary keys and values), forward and backward'),
    'evabyte.attn.core': ('attention', 'device scope: the joint softmax over a window\'s single keys and the earlier windows\' summaries, forward and backward'),
    'evabyte.ffn': ('feed-forward', 'device scope: the dense SwiGLU of every layer with the norm before it and its float32 residual add'),
    # digit-free names: the benchmark's reduction finds a scope by `[a-z]+(?:\.[a-z_]+)+` (`harness/device_scopes.py`)
    'sconv.proj': ('short convolution', 'device scope: a gated short convolution\'s two products (`in_proj` to three gates\' worth of channels, `out_proj` back) and the norm before them'),
    'sconv.mix': ('short convolution', 'device scope: the two elementwise gates and the causal depthwise taps between the products, forward and backward (the taps\' gradient among it)'),
    'kda.proj': ('delta attention', 'device scope: a gated delta-rule mixer\'s products (q, k, v, the two low-rank gates, beta, the output product) and the norm before them'),
    'kda.mix': ('delta attention', 'device scope: the elementwise middle of a gated delta-rule mixer, behind barriers: taps, SiLU, the L2 norms, the decay\'s log and beta before the core, the gated per-head norm after it, forward and backward'),
    'kda.core': ('delta attention', 'device scope: the chunked delta-rule recurrence (the decayed pair sums, the triangular inverse, the scan over chunks that carries the state, whose chunk-boundary states a block keeps), forward and backward'),
    # the image models' scopes, on the shared layers (every model built from them has them), and the step's own,
    # which every task runs. The innermost scope of an op counts: `img.block` holds what no inner scope takes
    'img.patch_embed': ('step', 'device scope: the patch convolution, class / register tokens, position embedding, the norm before the blocks'),
    'img.stem': ('step', 'device scope: a convolutional stem (its norm is `img.norm`)'),
    'img.downsample': ('step', 'device scope: the convolution between two stages, or on a block\'s shortcut'),
    'img.block': ('step', 'device scope: a whole block; what is left to it are LayerScale, stochastic depth, the residual adds and layout changes between its inner scopes'),
    'img.norm': ('step', 'device scope: every LayerNorm (= LayerNorm2d), forward and backward, wherever it is called from'),
    'img.attn.qkv': ('attention', 'device scope: the fused qkv product, bias, split and head transpose'),
    'img.attn.core': ('attention', 'device scope: scores, mask, softmax, attention dropout, P V'),
    'img.attn.proj': ('attention', 'device scope: the (B, H, N, D) -> (B, N, C) transpose, the output product, its dropout'),
    'img.mlp': ('step', 'device scope: fc1, activation, fc2 of `Mlp`: a token MLP or a channels-last pointwise pair'),
    'img.conv_dw': ('step', 'device scope: a block\'s depthwise convolution: forward, input gradient, weight gradient'),
    'img.head': ('step', 'device scope: pool, final norm (as `img.norm`), classifier'),
    'step.input': ('step', 'device scope: the cast / normalisation of the batch inside the step'),
    'step.loss': ('step', 'device scope: the classification loss after the model call, and where its backward pass starts'),
    'step.clip': ('step', 'device scope: the global gradient norm and the scaling of every gradient leaf'),
    'step.update': ('step', 'device scope: the optimizer\'s moments and the parameter write'),
    'step.guard': ('step', 'device scope: the all-finite reduction and the selects over parameters, moments and EMA'),
    'step.ema': ('step', 'device scope: the EMA\'s lerp'),
    'attention.fused_calls': ('attention', 'counter: `Attention` calls traced (or run eagerly) onto the kernel pair of kernels/flash_attention.py'),
    'attention.plain_calls': ('attention', 'counter: `Attention` calls traced (or run eagerly) onto `scaled_dot_product_attention`: `_sdpa` or XLA\'s attention'),
    # step counters (`device_counter`): values computed inside the step program, returned in its metrics
    'moe.local_slots': ('experts', 'step counter: (token, expert) slots routed to experts held here, all expert layers'),
    'moe.load_max': ('experts', 'step counter: largest number of slots on one held expert in one layer'),
    'moe.dropped_slots': ('experts', 'step counter: local slots the dispatch buffer of the branch taken left out; 0 by construction (the bounded buffer of layers/moe.py dispatch_rows is taken only when the local slots fit, else all T * top_k rows): must read 0'),
    'moe.fallback_layers': ('experts', 'step counter: expert layers whose local slots did not fit the bounded dispatch buffer and took the worst-case one'),
    'moe.route_gathers': ('experts', 'gauge: gather fusions under `glm.moe.route` in the step program\'s compiled text, every computation (both branches of every layer\'s conditional, forward, rematerialised and backward); set where the program is kept (`TrainingTask.lower_train_step`), by `scope_gathers`'),
    'moe.route_gathers_fast': ('experts', 'gauge: those of them whose source (the largest operand) the compiler placed in the chip\'s fast memory (`S(1)` in its layout): equal to `moe.route_gathers` when the size rule of layers/moe.py `_column_pieces` engaged on every row gather'),
    'lm.tokens': ('step', 'step counter: tokens the step was given'),
    'attn.full_blocks': ('attention', 'step counter: (query block, key block) tiles with an unmasked pair that the full cores multiply in the forward pass, all layers and sequences'),
    'attn.window_blocks': ('attention', 'step counter: the same for the window cores, from the kernel\'s block map or the XLA path\'s slices'),
    'attn.bd_blocks': ('attention', 'step counter: the same for the cores under the block-diffusion mask'),
    'attn.eva_blocks': ('attention', 'step counter: the same for the cores under the chunk-window mask (queries on summaries and single keys)'),
    'attn.eva_pairs': ('attention', 'step counter: (query, key) pairs the chunk-window mask leaves, single keys and summaries, all heads held, layers and sequences (float32: from the shapes alone)'),
    'sconv.rows': ('short convolution', 'step counter: positions x gated short-convolution layers of the step (from the shapes): the rows its memory-bound middle moves'),
    'ffn.products': ('feed-forward', 'gauge: matrix products (`convolution` / `dot` instructions, those inside fusions too) under `evabyte.ffn` in the step program\'s compiled text, by `scope_products`: 3 forward and 6 backward a layer once the block\'s rematerialisation keeps the two up-products (`layers/mlp.py` `FFN_UP`; 11 a layer when its second forward pass forms them again); set where the program is kept (`TrainingTask.lower_train_step`)'),
    'kda.core_scans': ('delta attention', 'gauge: `while` instructions under `kda.core` in the step program\'s compiled text, by `scope_loops`: one scan over chunks forward and one backward a gated delta-rule layer (the block\'s second forward pass finds the chunk-boundary states kept); set where the program is kept (`TrainingTask.lower_train_step`)'),
    'kda.rows': ('delta attention', 'step counter: positions x gated delta-rule layers of the step (from the shapes): the rows its elementwise middle moves and its recurrence visits'),
    'kda.chunks': ('delta attention', 'step counter: chunks x heads held x gated delta-rule layers of the step (from the shapes): the triangular systems solved and the scan\'s steps x heads'),
    'lm.head_nll': ('step', 'step counter: the mean cross-entropy of each of a model\'s `num_pred_heads` prediction heads over its own valid positions, a vector; over micro-batches the means add'),
    'lm.noised_masked': ('step', 'step counter: positions of the step\'s noised copies that hold the mask token'),
    'lm.masked_nll': ('step', 'step counter: the cross-entropy summed over those positions, unweighted (over `lm.noised_masked`: the mean a masked position)'),
}


class Span(NamedTuple):
    id: int
    parent: int               # id of the enclosing span on the same thread, 0 for none
    name: str
    thread: int
    step: Optional[int]
    start_ns: int             # time.perf_counter_ns
    end_ns: int
    cpu_start_ns: int         # time.thread_time_ns
    cpu_end_ns: int
    failed: bool              # an exception passed through


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_counters: dict = {}
_marks: collections.deque = collections.deque(maxlen=SERIES)   # (end_ns of a step root, the counters then)
_gauges: dict = {}


now_ns = time.perf_counter_ns   # the ring's clock, for a reader's `since_ns`


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _known(name: str) -> str:
    if name not in SPANS:
        raise KeyError(f'{name!r} is not declared in tracing.SPANS')
    return name


class span:
    """Context manager: one record in the ring when the block ends (also when
    it raises) and one `TraceAnnotation` around it. `step=` makes this span the
    root of a step: spans opened inside inherit it, and the counters' totals
    are marked when it ends, so that a reader can take their change over any
    run of steps."""
    __slots__ = ('name', 'step', 'root', 'id', 'parent', 'stack', 'start_ns', 'cpu_start_ns', 'annotation')

    def __init__(self, name: str, **ids):
        self.name = _known(name)
        self.step = ids.get('step')
        self.root = self.step is not None
        self.annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        stack = self.stack = _stack()
        self.parent, inherited = stack[-1] if stack else (0, None)
        if self.step is None:
            self.step = inherited
        self.id = next(_ids)
        stack.append((self.id, self.step))
        self.annotation.__enter__()
        self.cpu_start_ns = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end_ns = time.perf_counter_ns()
        cpu_end_ns = time.thread_time_ns()
        self.annotation.__exit__(exc_type, exc, tb)
        self.stack.pop()
        _ring.append(tuple.__new__(Span, (  # the generated Span.__new__ costs twice this
            self.id, self.parent, self.name, threading.get_ident(), self.step,
            self.start_ns, end_ns, self.cpu_start_ns, cpu_end_ns, exc_type is not None)))
        if self.root:
            with _lock:
                _marks.append((end_ns, dict(_counters)))
        return False


def count(name: str, n: int = 1) -> None:
    _known(name)
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


class busy:
    """Context manager: adds the wall nanoseconds of the block to counter
    `name`. No ring record: this is what a loader worker may do per sample."""
    __slots__ = ('name', 'start_ns')

    def __init__(self, name: str):
        self.name = _known(name)

    def __enter__(self):
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        count(self.name, time.perf_counter_ns() - self.start_ns)
        return False


def scope(name: str):
    """A device scope: `jax.named_scope(name)` under a declared name. It costs the
    host nothing at run time (it names the ops traced inside it); a profiler
    trace carries the name on every device op, forward and backward."""
    return jax.named_scope(_known(name))


_programs: dict = {}     # declared span -> the compiled text of the program it dispatches (`keep_program`)


def keep_program(name: str, compiled) -> None:
    """Keep the text of a program compiled ahead of time (a `jax.stages.Compiled`) under the declared span that
    dispatches it: what a reader of device scopes needs beside the trace. The text, not the executable."""
    _programs[_known(name)] = compiled.as_text()


def program_text(name: str) -> Optional[str]:
    """The compiled text last kept under `name`; None where nobody compiled that program ahead of time."""
    return _programs.get(_known(name))


_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\w+\[[\d,]*\]\S*) ([\w\-]+)\((.*)$')
_CALLS = re.compile(r'calls=%?([\w.\-]+)')


def _op_name(rest: str) -> str:
    """An instruction's `op_name` (the scopes it was traced under) from what follows its opening bracket; '' if none."""
    return rest.partition('op_name="')[2].partition('"')[0]


def _elements(text: str) -> int:
    """Elements of an array type as a compiled program writes it (`bf16[65536,384]{1,0:T(8,128)(2,1)S(1)}`); 0 for a tuple."""
    m = re.match(r'\w+\[([\d,]*)\]', text)
    return math.prod(int(d) for d in m.group(1).split(',') if d) if m else 0


def scope_gathers(text: str, scope: str) -> tuple:
    """(gathers, fast) of a compiled program's text, over ALL its computations (a conditional's branches are
    computations of their own): the fusions whose `op_name` lies under device scope `scope` and which hold a
    `gather` (and the gathers under it that stand in no fusion), and how many of them read their largest operand,
    the source, from memory space `S(1)`, the chip's fast memory, as its layout says. What a trace cannot say: an
    op event carries no operand's placement."""
    types, rows, fused, gathering, computation = {}, [], set(), set(), None
    for line in text.splitlines():
        if line.endswith('{') and not line.startswith(' '):
            computation = re.match(r'(?:ENTRY )?%?([\w.\-]+)', line).group(1)
        m = _INSTRUCTION.match(line)
        if m:
            name, result, op, rest = m.groups()
            types[name] = result
            if op == 'gather':
                gathering.add(computation)
            elif op == 'fusion':
                fused.add(_CALLS.search(rest).group(1))
            if op in ('gather', 'fusion') and scope in _op_name(rest):
                rows.append((computation, op, rest))
    gathers = fast = 0
    for computation, op, rest in rows:
        if _CALLS.search(rest).group(1) in gathering if op == 'fusion' else computation not in fused:
            operands = re.findall(r'[\w.\-]+', rest.partition('), ')[0])
            source = max((types.get(o, '') for o in operands), key=_elements)
            gathers, fast = gathers + 1, fast + ('S(1)' in source)
    return gathers, fast


def _scope_ops(text: str, scope: str, ops: tuple) -> int:
    """The instructions of a program's text, over all its computations (a fusion's among them), that are one of `ops`
    and whose `op_name` lies under device scope `scope`."""
    matches = map(_INSTRUCTION.match, (line for line in text.splitlines() if any(f' {op}(' in line for op in ops)))
    return sum(m.group(3) in ops and scope in _op_name(m.group(4)) for m in matches if m)


def scope_loops(text: str, scope: str) -> int:
    """The `while` instructions of a program's text, over all its computations, whose `op_name` lies under device
    scope `scope`: a `lax.scan` each, as long as the compiler leaves it a loop."""
    return _scope_ops(text, scope, ('while',))


def scope_products(text: str, scope: str) -> int:
    """The matrix products of a program's text, over all its computations, whose `op_name` lies under device scope
    `scope`: `convolution` instructions (what the chip's compiler makes of a `dot_general`) and `dot`s, inside a
    fusion or not. A fusion that holds one is not counted again. What a block's second forward pass multiplies
    again shows here as products under `.../rematted_computation/<scope>/dot_general`."""
    return _scope_ops(text, scope, ('convolution', 'dot'))


def device_counter(name: str, value):
    """A step counter: `value` (an array computed inside the step program)
    under a declared name, for the step's returned metrics. No host read."""
    _known(name)
    return value


def gauge(name: str, value) -> None:
    _known(name)
    with _lock:
        series = _gauges.get(name)
        if series is None:
            series = _gauges[name] = collections.deque(maxlen=SERIES)
    series.append((time.perf_counter_ns(), value))


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    """Every program JAX builds, compiled afresh or read from the persistent
    cache, reports this event once (`utils/compile_cache.py`), on the thread
    that asked for it, as it ends."""
    if event != '/jax/core/compile/backend_compile_duration':
        return
    end_ns = time.perf_counter_ns()
    cpu_ns = time.thread_time_ns()
    stack = _stack()
    parent, step = stack[-1] if stack else (0, None)
    _ring.append(Span(next(_ids), parent, 'xla.backend_compile', threading.get_ident(), step,
                      end_ns - int(duration_secs * 1e9), end_ns, cpu_ns, cpu_ns, False))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """The ring's spans (oldest first), the counters, their marks at the end
    of each step root, and each gauge's (perf_counter_ns, value) series, copied."""
    with _lock:
        return {'spans': list(_ring), 'counters': dict(_counters), 'marks': list(_marks),
                'gauges': {k: list(v) for k, v in _gauges.items()}}


def summary(since_ns: int = 0, spans=None) -> dict:
    """name -> n, median and sum of wall ms and of thread-CPU ms, over the
    ring's spans (or `spans`) that started at `since_ns` or later."""
    by_name: dict = {}
    for s in (list(_ring) if spans is None else spans):
        if s.start_ns >= since_ns:
            by_name.setdefault(s.name, []).append(((s.end_ns - s.start_ns) / 1e6, (s.cpu_end_ns - s.cpu_start_ns) / 1e6))
    return {name: {'n': len(rows),
                   'wall_ms_median': statistics.median(w for w, _ in rows), 'wall_ms_sum': sum(w for w, _ in rows),
                   'cpu_ms_median': statistics.median(c for _, c in rows), 'cpu_ms_sum': sum(c for _, c in rows)}
            for name, rows in by_name.items()}
