"""A sparse mixture-of-experts layer that is told which experts it holds.

Routing is one of two rules a model's entry point fixes (`scoring`).
'sigmoid_bias' is DeepSeek-V3's `noaux_tc` (arXiv:2412.19437 section 2.1.2) as
GLM-4.7-Flash `glm4_moe_lite` configures it: sigmoid scores over ALL
`num_experts`, the `top_k` largest of score + bias chosen, weights = the chosen
scores normalised over all `top_k` chosen and scaled. 'softmax_topk' is
SmallThinker's (arXiv:2507.20984): the `top_k` largest LOGITS chosen, weights =
a softmax over the chosen logits; no bias, no scaling. A third model,
LFM2-8B-A1B (`models/lfm2_moe.py`), uses 'sigmoid_bias' at its own numbers: 4 of
32, no shared expert, scaling 1, and its published code's normaliser epsilon
(`norm_eps` 1e-6 where GLM's is 1e-20), and a fourth,
Solar-Open2-250B (`models/solar_open2.py`), at GLM's own rule (one shared
expert, scaling 1, epsilon 1e-20) and the largest numbers the layer has met:
8 of 320, a router of 320 outputs (2.5 lane tiles), a share of 1/40 (8
experts held: `dispatch_rows` 3328 of 65536 slots at 8192 tokens). The router may read
another tensor than the experts (`router_in`: SmallThinker routes on the
attention's input, so a layer's routing does not wait for its attention), and
the experts' gate activation is SiLU (SwiGLU) or ReLU (ReGLU). The layer holds experts
[expert_offset, expert_offset + experts_held) — one expert-parallel rank's
share — and computes their part of the result; what experts held elsewhere
would add is not computed here and nothing stands in for it (on several chips
the exchange in `parallel/` would bring it; ROADMAP "Reach"). With
`experts_held == num_experts` it is the whole layer.

No token is dropped under any routing: the (token, choice) slots are sorted by
held expert, the local ones first, and the first `C` of that order are gathered
into a buffer of `C` rows, on which the three gated-MLP products run as grouped
products over the experts held (`jax.lax.ragged_dot`, group sizes = slots an
expert, counted by comparing the slots' keys with the held ids). No row is
scattered, forward or backward: `order` is a permutation of the slots, its
inverse `argsort(order)` says at which buffer row a slot's result lies, and a
token's result is the sum, at float32, of the rows its `top_k` slots point at
(a row gather a choice, masked to the live rows). The gather into the buffer
and the gather-sum out of it are each other's transpose, so the two are a
`jax.custom_vjp` pair (`_to_buffer`, `_to_tokens`) in which each is the
other's derivative: two functions serve the four passes. Both read their
source in column pieces where it is too large for the compiler to hold it in
the chip's fast memory whole and small enough for pieces to pay
(`_column_pieces`: by the source's bytes alone; a column split changes no
element's arithmetic).

`C` follows the share of the experts the layer holds, not the worst
case: twice the mean load, `2 * T * top_k * experts_held / num_experts` rounded
up to `TILE` rows and at most `T * top_k` (`dispatch_rows`). A layer whose local
slots do not fit in `C` rows takes the same path over all `T * top_k` rows (the
worst case: every choice of every token on an expert held here), chosen on the
device by `lax.cond`, per layer and per step; `moe.fallback_layers` counts the
layers that did. With `experts_held == num_experts` `C` is `T * top_k` and no
conditional is built. `moe.dropped_slots` counts local slots the buffer of the
branch taken left out: 0 by construction, because the bounded branch runs only
when the local slots fit and the other holds every slot there is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .mlp import SwiGLU
from .weight_init import trunc_normal_

__all__ = ['SparseMoe', 'route', 'merge_counters', 'dispatch_rows']

ACTIVATIONS = {'silu': jax.nn.silu, 'relu': jax.nn.relu}
TILE = 128      # rows: the dispatch buffer is whole tiles of the grouped products' row dimension
LANES = 128     # columns of one lane tile
# A row gather runs faster from a source the compiler holds in the chip's fast memory than from one in HBM (PERF.md
# section 6, PR 42 and PR 45; a v5e has 128 MiB of it, which a source shares with its neighbours). What the
# compiler puts there whole depends on the move: the source of a gather-sum up to half of the fast memory, the
# source of the one gather into the buffer up to `TAKE_WHOLE_BYTES`. A larger source is read in column pieces of
# at most `PIECE_BYTES`, each of which the compiler copies there, up to `PIECED_BYTES`; one over that (every
# fall-back buffer: 480-512 MiB, each row read once) is read whole from HBM: in pieces a layer on its fall-back
# branch read 165.4 ms against 156.0 (LFM2's shape) and 77.8 against 74.0 (SmallThinker's), my chip run, PR 45
FAST_BYTES = 128 << 20
PIECE_BYTES = 48 << 20
TAKE_WHOLE_BYTES = 112 << 20
PIECED_BYTES = 2 * FAST_BYTES


def dispatch_rows(rows: int, experts_held: int, num_experts: int) -> int:
    """Rows of the buffer that `rows` (token, choice) slots are dispatched into by a layer that holds
    `experts_held` of `num_experts`: twice the slots an even routing brings, in whole tiles, at most all."""
    return min(rows, -(-2 * rows * experts_held // (num_experts * TILE)) * TILE)


def merge_counters(a: dict, b: dict) -> dict:
    """Counters of two layers as one: slots and blocks add, the largest load is the larger; a counter only one of
    them has (layers of different kinds in one model) is kept as it is."""
    out = dict(a)
    for k, v in b.items():
        out[k] = v if k not in out else jnp.maximum(out[k], v) if k.endswith('_max') else out[k] + v
    return out


def route(scores_in, router_kernel, bias, top_k: int, scaling: float, scoring: str = 'sigmoid_bias',
          norm_eps: float = 1e-20):
    """x (T, d) -> chosen expert ids (T, k) and weights (T, k), float32.
    The scores, their choice and the weights are float32 at full precision:
    a top-k choice flips on the last bits. `norm_eps` is what 'sigmoid_bias' adds to the chosen scores' sum
    before it divides by it (a model's published code fixes it)."""
    logits = jnp.matmul(scores_in.astype(jnp.float32), router_kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == 'softmax_topk':
        chosen, idx = jax.lax.top_k(logits, top_k)
        return idx, jax.nn.softmax(chosen, axis=-1)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + norm_eps) * scaling


def _group_sizes(slot_expert, held: int):
    """Slots an expert held here: a comparison count of the `T * k` keys against the `held` ids, int32 (the
    key `held` says held elsewhere and is counted nowhere)."""
    return (slot_expert[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)


def _column_pieces(n: int, dim: int, itemsize: int, whole: int) -> list:
    """Column bounds of the pieces a row move reads its (n, dim) source in: one piece up to `whole` bytes and over
    `PIECED_BYTES`, between them pieces of at most `PIECE_BYTES`, whole lane columns each."""
    lanes = dim // LANES
    pieces = 1
    if whole < n * dim * itemsize <= PIECED_BYTES and dim % LANES == 0:
        pieces = -(-lanes // max(1, PIECE_BYTES // (n * LANES * itemsize)))
    return [lanes * c // pieces * LANES for c in range(pieces)] + [dim]


def _by_pieces(source, whole: int, move):
    """`move` (columns (n, w) -> (m, w)) of `source`, column piece by column piece (`_column_pieces`)."""
    bounds = _column_pieces(*source.shape, source.dtype.itemsize, whole)
    if len(bounds) == 2:
        return move(source)
    return jnp.concatenate([move(source[:, lo:hi]) for lo, hi in zip(bounds, bounds[1:])], axis=1)


# The two moves between token order and buffer order, over one set of integer operands: `token[r]` is the token
# whose slot lies at buffer row `r`, `pos[t, j]` the buffer row of token t's j-th slot (the same permutation read
# both ways), and rows from `covered` on are dead.
def _take_rows(x, token, pos, covered):
    """x (T, dim) -> (n, dim): row r is x[token[r]] for r < covered, zero past it."""
    with tracing.scope('glm.moe.route'):
        live = (jnp.arange(token.shape[0]) < covered)[:, None]
        return _by_pieces(x, TAKE_WHOLE_BYTES, lambda piece: jnp.where(live, piece[token], 0))


def _sum_rows(rows, token, pos, covered):
    """rows (n, dim) -> (T, dim): token t's row is the sum over its `k` slots j of rows[pos[t, j]], of the slots
    that lie below row `covered` (`covered <= n`); added choice by choice at float32, cast once."""
    with tracing.scope('glm.moe.route'):
        dead = (pos >= covered).T[:, :, None]
        at = jnp.minimum(pos, rows.shape[0] - 1).T

        def summed(piece):
            return sum(jnp.where(off, 0, piece[p]).astype(jnp.float32) for p, off in zip(at, dead)).astype(rows.dtype)

        return _by_pieces(rows, FAST_BYTES // 2, summed)


def _transposes(move, back):
    """`move` as a function whose derivative is `back`: each scatters what the other gathers, so neither pass
    scatters a row. The integer operands take no cotangent."""
    f = jax.custom_vjp(move)
    f.defvjp(lambda x, *at: (move(x, *at), at), lambda at, d: (back(d, *at), None, None, None))
    return f


_to_buffer, _to_tokens = _transposes(_take_rows, _sum_rows), _transposes(_sum_rows, _take_rows)


@functools.partial(jax.jit, static_argnames=('n', 'top_k', 'activation'))
def _dispatch(x, order, pos, group_sizes, slot_weight, w_gate, w_up, w_down, *, n: int, top_k: int, activation: str):
    """The held experts' part of the result from the first `n` slots of `order` (local slots first, by expert),
    and how many local slots those rows hold. Rows go into the buffer by a gather through `order` and come back
    by a gather through its inverse `pos = argsort(order)` (`_to_buffer`, `_to_tokens`: a `custom_vjp` pair, each
    the other's derivative). A function of its own under `jax.jit`: layers of one shape share one trace of it,
    of its derivative and of its lowering, which is most of what the second buffer size would otherwise add to a
    program's set-up."""
    with tracing.scope('glm.moe.route'):
        slots = order[:n]
        token = slots // top_k
        covered = jnp.minimum(group_sizes.sum(), n)
        # rows past the groups hold nothing defined, in a grouped product's result and in its cotangent
        # alike (the TPU kernel skips their tiles): every operand and result is masked to the live rows,
        # so that nothing undefined reaches a token, forward or backward
        live = (jnp.arange(n) < covered)[:, None]
        xs = _to_buffer(x, token, pos, covered).astype(w_gate.dtype)
    with tracing.scope('glm.moe.experts'):
        gate = jax.lax.ragged_dot(xs, w_gate, group_sizes)
        up = jax.lax.ragged_dot(xs, w_up, group_sizes)
        hidden = jnp.where(live, ACTIVATIONS[activation](gate) * up, 0)
        ys = jax.lax.ragged_dot(hidden, w_down, group_sizes)
    with tracing.scope('glm.moe.route'):
        ys = jnp.where(live, ys * slot_weight[slots][:, None].astype(ys.dtype), 0)
        y = _to_tokens(ys, token, pos, covered)
    return y, covered


class SparseMoe(nnx.Module):
    """(x (B, S, dim), router_in = x) -> (y (B, S, dim), counters). Every expert is a gated MLP of width
    `hidden`, without biases: `activation(x W_gate) * (x W_up)` through `W_down`; `n_shared` shared experts are
    one SwiGLU of width `n_shared * hidden` every token passes through. `scoring` and `activation` are a
    model's, fixed where its entry point builds the layer."""

    def __init__(
            self,
            dim: int,
            hidden: int,
            num_experts: int,
            top_k: int,
            experts_held: int = None,
            expert_offset: int = 0,
            n_shared: int = 1,
            routed_scaling_factor: float = 1.0,
            scoring: str = 'sigmoid_bias',
            activation: str = 'silu',
            norm_eps: float = 1e-20,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        held = num_experts if experts_held is None else experts_held
        if not 0 <= expert_offset <= expert_offset + held <= num_experts:
            raise ValueError(f'experts [{expert_offset}, {expert_offset + held}) are not among {num_experts}')
        if scoring not in ('sigmoid_bias', 'softmax_topk') or activation not in ACTIVATIONS:
            raise ValueError(f'scoring {scoring!r} / activation {activation!r}: not a rule this layer knows')
        self.num_experts, self.top_k = num_experts, top_k
        self.scoring, self.activation = scoring, activation
        self.experts_held, self.expert_offset = held, expert_offset
        self.scaling, self.norm_eps = routed_scaling_factor, norm_eps
        self.dtype = dtype
        init = trunc_normal_(std=0.02)
        key = rngs.params
        self.router = nnx.Param(init(key(), (dim, num_experts), param_dtype))
        # `e_score_correction_bias`: steers the choice, not the weights; a buffer without gradient. Its
        # update from the experts' load is not part of the step (the rate is not in the public config).
        # Zero until somebody places one: a checkpoint's learned values, or a benchmark runner's seeded
        # vector (`benchmarks/harness/sconv_lm_train_runner.py`). The softmax rule has none.
        self.score_bias = nnx.Variable(jnp.zeros((num_experts,), jnp.float32)) if scoring == 'sigmoid_bias' else None
        self.w_gate = nnx.Param(init(key(), (held, dim, hidden), param_dtype))
        self.w_up = nnx.Param(init(key(), (held, dim, hidden), param_dtype))
        self.w_down = nnx.Param(init(key(), (held, hidden, dim), param_dtype))
        self.shared = SwiGLU(dim, hidden * n_shared, bias=False, dtype=dtype, param_dtype=param_dtype,
                             rngs=rngs) if n_shared else None

    def _route(self, router_in):
        bias = None if self.score_bias is None else jax.lax.stop_gradient(self.score_bias[...])
        return route(router_in, self.router[...], bias, self.top_k, self.scaling, self.scoring, self.norm_eps)

    def choose(self, router_in):
        """Chosen expert ids (..., top_k) of tokens whose router input is (..., dim), of all `num_experts`."""
        return self._route(router_in)[0]

    def routed(self, x, router_in=None):
        """The held experts' part of the result for tokens x (T, dim), routed on `router_in` (T, dim; default x),
        and the counters."""
        held, k = self.experts_held, self.top_k
        dt = self.dtype or x.dtype
        rows = x.shape[0] * k                                      # the worst case: every slot local
        bound = dispatch_rows(rows, held, self.num_experts)
        with tracing.scope('glm.moe.route'):
            idx, weights = self._route(x if router_in is None else router_in)
            local = (idx >= self.expert_offset) & (idx < self.expert_offset + held)
            slot_expert = jnp.where(local, idx - self.expert_offset, held).reshape(-1)   # `held` = held elsewhere
            order = jnp.argsort(slot_expert, stable=True)          # local slots first, by expert
            pos = jnp.argsort(order).reshape(-1, k)                # its inverse: the buffer row of a token's slots
            group_sizes = _group_sizes(slot_expert, held)
            slot_weight = jnp.where(local, weights, 0.0).reshape(-1)
            local_slots = group_sizes.sum()
        with tracing.scope('glm.moe.experts'):
            operands = (x, order, pos, group_sizes, slot_weight,
                        self.w_gate[...].astype(dt), self.w_up[...].astype(dt), self.w_down[...].astype(dt))
        dispatch = functools.partial(_dispatch, top_k=k, activation=self.activation)
        if bound == rows:
            fallback = jnp.int32(0)
            y, covered = dispatch(*operands, n=rows)
        else:
            # The conditional stands outside the scopes and each branch opens them inside: the instruction itself
            # carries no scope, so a trace gives the branch's ops, not the branch, to the route and the products.
            # A branch is rematerialised on its own: what its backward needs it computes again inside the
            # conditional, so no buffer of either branch is kept across it for the other's sake.
            over = local_slots > bound
            fallback = over.astype(jnp.int32)
            y, covered = jax.lax.cond(over, jax.checkpoint(functools.partial(dispatch, n=rows)),
                                      jax.checkpoint(functools.partial(dispatch, n=bound)), *operands)
        counters = {
            'moe.local_slots': tracing.device_counter('moe.local_slots', local_slots),
            'moe.load_max': tracing.device_counter('moe.load_max', group_sizes.max()),
            'moe.dropped_slots': tracing.device_counter('moe.dropped_slots', local_slots - covered),
            'moe.fallback_layers': tracing.device_counter('moe.fallback_layers', fallback),
        }
        return y, counters

    def __call__(self, x, router_in=None):
        B, S, dim = x.shape
        y, counters = self.routed(x.reshape(B * S, dim), None if router_in is None else router_in.reshape(B * S, dim))
        y = y.reshape(B, S, dim).astype(x.dtype)
        if self.shared is not None:
            with tracing.scope('glm.moe.shared'):
                y = y + self.shared(x)
        return y, counters
