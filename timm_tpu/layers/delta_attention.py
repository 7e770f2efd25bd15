"""A gated delta-rule linear attention over token sequences with a per-channel
decay (KDA, Kimi Delta Attention: Kimi Linear, arXiv:2510.26692; the mixer of
three layers of four in Solar-Open2-250B, beside a softmax attention on the
fourth): no softmax, no positions, a carried state of (head_dim x head_dim)
float32 numbers a head.

For the normalised input a (B, S, dim), every linear map bias-free, H heads of width D:
  q~, k~, v = SiLU(conv(a W_q)), SiLU(conv(a W_k)), SiLU(conv(a W_v))     conv: causal, depthwise, `conv_size` taps a
                                                                          channel, the LAST tap on the current position
  q = q~ / ||q~|| * D^-1/2,  k = k~ / ||k~||                              per head and position
  g = -exp(A_log) * softplus(a W_f_down W_f_up + dt_bias)                 log of the decay, per CHANNEL, <= 0; A_log a head
  beta = 2 * sigmoid(a W_beta)                                            one a head, in (0, 2): eigenvalues in (-1, 1)
  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T   S_0 = 0 at every sequence's start
  o_t = S_t^T q_t
  y = [RMSNorm_head(o) * sigmoid(a W_g_down W_g_up)] W_o                  one learned scale of D for all heads

The recurrence is computed in CHUNKS of C positions (`chunked_delta_rule`). With
G the running sum of g inside a chunk (inclusive), S the state at the chunk's
start and u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t) the value the rule
writes at t, S_t = Diag(e^{G_t}) S + sum_{j<=t} Diag(e^{G_t - G_j}) k_j u_j^T, so
the chunk's rows satisfy the unit-lower-triangular system

  (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S),   A[t, j] = sum_d k_t k_j e^{G_t - G_j}  (j < t)

whose inverse T gives W = T Diag(beta) (K e^G) and U~ = T Diag(beta) V once a
chunk, for every chunk at once; then, chunk after chunk (`lax.scan`, carrying S
and nothing else, two products a step):

  U = U~ - W S;   S' = Diag(e^{G_C}) S + (K e^{G_C - G})^T U;

and from the stack of the chunks' INCOMING states and of U, every chunk at once,

  O = (Q e^G) S + P U,   P[t, j] = sum_d q_t k_j e^{G_t - G_j}  (j <= t).

No exponent above zero is ever taken, whatever the decays: A and P are built in
sub-blocks of `SUB` rows, a row block against the EARLIER columns through the
decay to its own first row (two factors, each <= 1, so a dense product), and
against its own columns pair by pair. T is the exact block inverse: rows by
substitution inside a sub-block, sub-blocks merged two by two. g, its sums,
both matrices, T and the state are float32 and every product of the core runs
at `Precision.HIGHEST`: a bfloat16 pass in the solve or a bfloat16 state is not
a rounding here but another model (`tests/test_delta_attention.py`).

The chunk-boundary states are first-class values of the core (a
`jax.custom_vjp`, `_core`). They are all the backward pass keeps beside the
core's operands (B x H x S / C x D x D x 4 bytes: 67 MB a layer at 8 heads x
8192; never a state a position), and they carry `checkpoint_name(.., CORE_OUT)`,
as the core's output does where the layer calls it: a block rematerialised
under `save_only_these_names(CORE_OUT)` keeps both, so its second forward pass
makes q, k, v, g, beta again (the cheap middle) and runs no scan. The backward
pass is written by hand, the same products transposed at the same precision:
the chunk terms and U again from the kept states, batched; one reversed scan
that carries the state's cotangent alone,

  dU = P^T dO + (K e^{G_C - G}) dS';   dS = Diag(e^{G_C}) dS' + (Q e^G)^T dO - W^T dU,

P^T dO and (Q e^G)^T dO made for every chunk beforehand, so two products a
step again; then dW = -dU S^T, dU~ = dU, d(Q e^G) = dO S^T, dP = dO U^T,
d(K e^{G_C - G}) = U dS'^T and d e^{G_C} = sum_e dS' * S for every chunk at
once, and autodiff's way (`jax.vjp` of `_chunk_terms`) from the terms back to
q, k, v, g and beta. One scan forward and one backward a layer, each bound by
the latency of S / C dependent steps, is what is left in sequence.

The layer is TOLD WHICH HEADS IT HOLDS (`heads_held`, `head_offset`), as
`ChunkedLinearAttention` is: it projects to those heads only, carries their
taps, `A_log` and `dt_bias`, and returns their part of the output product. The
two low-rank gates' down-products and the norm's scale are whole on every chip.
Nothing is cached here: the (H, D, D) state and `conv_size - 1` rows a decode
step carries belong to `serve/` (ROADMAP "Reach").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from ..utils import tracing
from .helpers import head_slice
from .latent_attention import CORE_OUT
from .norm import RmsNorm
from .short_conv import _shift
from .weight_init import trunc_normal_

__all__ = ['KimiDeltaAttention', 'chunked_delta_rule']

SUB = 16            # rows of a sub-block: pairwise inside it, dense products against the columns before it
L2_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST
_written = jax.lax.optimization_barrier     # as `short_conv.py`: the middle stays an operation of its own


def _decayed_pairs(rows, k, G, sub: int):
    """For each x of `rows` (N, C, D): M[t, j] = sum_d x_t k_j e^{G_t - G_j} for j <= t, 0 above the diagonal; k and
    G (N, C, D), G non-increasing along C. A row sub-block meets the columns of EARLIER sub-blocks through its own
    first row r: (x_t e^{G_t - r}) . (k_j e^{r - G_j}), both exponents <= 0; its own columns pair by pair."""
    N, C, D = G.shape
    ns = C // sub
    Gs, ks = G.reshape(N, ns, sub, D), k.reshape(N, ns, sub, D)
    first = Gs[:, :, :1]
    seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    own_decay = jnp.exp(jnp.where(seen[:, :, None], Gs[:, :, :, None] - Gs[:, :, None, :], -jnp.inf))   # (N, ns, sub, sub, D)
    if ns > 1:
        earlier = (jnp.arange(C) // sub)[None, :] < jnp.arange(ns)[:, None]                              # (ns, C)
        k_before = k[:, None] * jnp.exp(jnp.where(earlier[None, :, :, None], first - G[:, None], -jnp.inf))
        to_first = jnp.exp(Gs - first)
    out = []
    for x in rows:
        xs = x.reshape(N, ns, sub, D)
        own = (xs[:, :, :, None] * ks[:, :, None] * own_decay).sum(-1)                                   # (N, ns, sub, sub)
        if ns == 1:
            out.append(own.reshape(N, C, C))
            continue
        before = jnp.einsum('nasd,nacd->nasc', xs * to_first, k_before, precision=HIGHEST)
        # a row block's own columns padded into place: an identity over blocks would be an (N, ns, sub, ns, sub) tensor
        # with a minor axis of `sub`, an eighth of a lane row, written out forward and again backward
        placed = [jnp.pad(own[:, a], ((0, 0), (0, 0), (a * sub, C - (a + 1) * sub))) for a in range(ns)]
        out.append((before + jnp.stack(placed, axis=1)).reshape(N, C, C))
    return out


def _unit_lower_inverse(L, sub: int):
    """The inverse of unit-lower-triangular L (N, C, C), C = sub x a power of two: inside a diagonal block of `sub` rows by
    substitution (row t of the inverse is e_t - L[t, :t] @ the rows before it), two blocks merged as
    [[T1, 0], [-T2 L21 T1, T2]]. What lies above L's diagonal is not read."""
    C = L.shape[-1]
    if C <= sub:
        eye = jnp.eye(C, dtype=L.dtype)
        rows = [jnp.broadcast_to(eye[0], L.shape[:-2] + (C,))]
        for t in range(1, C):
            rows.append(eye[t] - jnp.einsum('...j,...jc->...c', L[..., t, :t], jnp.stack(rows, axis=-2), precision=HIGHEST))
        return jnp.stack(rows, axis=-2)
    h = C // 2
    T = _unit_lower_inverse(jnp.stack([L[..., :h, :h], L[..., h:, h:]]), sub)
    T1, T2 = T[0], T[1]
    T21 = -jnp.matmul(jnp.matmul(T2, L[..., h:, :h], precision=HIGHEST), T1, precision=HIGHEST)
    return jnp.concatenate([jnp.concatenate([T1, jnp.zeros_like(T21)], axis=-1), jnp.concatenate([T21, T2], axis=-1)], axis=-2)


def _chunk_terms(q, k, v, g, beta, sub: int):
    """What a chunk needs that does not depend on its incoming state, for N chunks at once: q, k, g (N, C, D), v (N, C,
    Dv), beta (N, C) -> W, U~, Q e^G, P, K e^{G_C - G}, e^{G_C}."""
    G = jnp.cumsum(g, axis=1)
    decay = jnp.exp(G)
    A, P = _decayed_pairs((k, q), k, G, sub)
    C = G.shape[1]
    strictly = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    T = _unit_lower_inverse(jnp.eye(C, dtype=G.dtype) + jnp.where(strictly, beta[:, :, None] * A, 0.0), sub)
    both = jnp.matmul(T, beta[:, :, None] * jnp.concatenate([k * decay, v], axis=-1), precision=HIGHEST)
    D = k.shape[-1]
    return both[..., :D], both[..., D:], q * decay, P, k * jnp.exp(G[:, -1:] - G), decay[:, -1]


def _chunk_major(t, C: int):
    """(B, H, S, ..) -> (S / C x B H, C, ..) float32, a sequence's chunks outermost: the scans run over them, every head of
    every sequence at once, and what is batched over chunks needs no other layout."""
    B, H, S = t.shape[:3]
    return t.astype(jnp.float32).reshape((B * H, S // C, C) + t.shape[3:]).swapaxes(0, 1).reshape((S // C * B * H, C) + t.shape[3:])


def _terms(q, k, v, g, beta, C: int):
    """`_chunk_terms` of (B, H, S, ..) tensors, each term (S / C, B H, ..)."""
    terms = _chunk_terms(*(_chunk_major(t, C) for t in (q, k, v, g, beta)), sub=min(SUB, C))
    return tuple(t.reshape((q.shape[2] // C, q.shape[0] * q.shape[1]) + t.shape[1:]) for t in terms)


def _incoming_states(W, Ut, Kd, end, state_dtype):
    """The only sequential part of the forward pass: each chunk's INCOMING state (n, N, D, Dv) in `state_dtype` and what
    the rule writes in it, U (n, N, C, Dv), from terms (n, N, ..). Two products a step."""
    def step(state, xs):
        W, Ut, Kd, end = xs
        s = state.astype(jnp.float32)
        U = Ut - jnp.matmul(W, s, precision=HIGHEST)
        s = end[:, :, None] * s + jnp.einsum('ncd,nce->nde', Kd, U, precision=HIGHEST)
        return s.astype(state_dtype), (state, U)

    _, stacks = jax.lax.scan(step, jnp.zeros((W.shape[1], W.shape[3], Ut.shape[3]), state_dtype), (W, Ut, Kd, end))
    return stacks


def _state_cotangents(W, Kd, end, from_P, from_Q, state_dtype):
    """The only sequential part of the backward pass, the scan reversed: the cotangent dS' of each chunk's OUTGOING state
    (n, N, D, Dv) and dU (n, N, C, Dv), given P^T dO and (Q e^G)^T dO of every chunk. dU = P^T dO + Kd dS' and
    dS = e^{G_C} dS' + (Q e^G)^T dO - W^T dU: two products a step."""
    def step(d_next, xs):
        W, Kd, end, from_P, from_Q = xs
        d = d_next.astype(jnp.float32)
        dU = from_P + jnp.matmul(Kd, d, precision=HIGHEST)
        dS = end[:, :, None] * d + from_Q - jnp.einsum('ncd,nce->nde', W, dU, precision=HIGHEST)
        return dS.astype(state_dtype), (d_next, dU)

    _, stacks = jax.lax.scan(step, jnp.zeros(from_Q.shape[1:], state_dtype), (W, Kd, end, from_P, from_Q), reverse=True)
    return stacks


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, g, beta, C: int, state_dtype):
    return _core_fwd(q, k, v, g, beta, C, state_dtype)[0]


def _core_fwd(q, k, v, g, beta, C: int, state_dtype):
    B, H, S, _ = q.shape
    W, Ut, Qg, P, Kd, end = _terms(q, k, v, g, beta, C)
    states, U = _incoming_states(W, Ut, Kd, end, state_dtype)
    states = checkpoint_name(states, CORE_OUT)      # a block's rematerialisation keeps them: its second pass holds no scan
    out = jnp.matmul(Qg, states.astype(jnp.float32), precision=HIGHEST) + jnp.matmul(P, U, precision=HIGHEST)
    return out.swapaxes(0, 1).reshape(B, H, S, v.shape[-1]), (q, k, v, g, beta, states)


def _core_bwd(C: int, state_dtype, kept, d_out):
    """Every product is the transpose of one of the forward pass's, float32 at `HIGHEST`, batched over chunks but for
    the two that hold the state's cotangent; the way back from the terms to q, k, v, g, beta is autodiff's."""
    q, k, v, g, beta, states = kept
    (W, Ut, Qg, P, Kd, end), back = jax.vjp(functools.partial(_terms, C=C), q, k, v, g, beta)
    S = states.astype(jnp.float32)
    U = Ut - jnp.matmul(W, S, precision=HIGHEST)
    dO = _chunk_major(d_out, C).reshape(U.shape)
    from_P = jnp.einsum('...cj,...ce->...je', P, dO, precision=HIGHEST)
    from_Q = jnp.einsum('...cd,...ce->...de', Qg, dO, precision=HIGHEST)
    d_next, dU = _state_cotangents(W, Kd, end, from_P, from_Q, state_dtype)
    d_next = d_next.astype(jnp.float32)
    by_state = lambda rows, state: jnp.einsum('...ce,...de->...cd', rows, state, precision=HIGHEST)  # noqa: E731
    return back((-by_state(dU, S), dU, by_state(dO, S), jnp.einsum('...ce,...je->...cj', dO, U, precision=HIGHEST),
                 by_state(U, d_next), (d_next * S).sum(-1)))


_core.defvjp(_core_fwd, _core_bwd)


def chunked_delta_rule(q, k, v, g, beta, chunk: int = 64, state_dtype=jnp.float32):
    """The gated delta rule with a per-channel decay, S_0 = 0 for every sequence: q, k, g (B, H, S, D), v (B, H, S, Dv),
    beta (B, H, S); g the log of the decay (<= 0) -> o (B, H, S, Dv) float32, o_t = S_t^T q_t. S a multiple of the
    chunk (a shorter sequence is one chunk), the chunk of `SUB` x a power of two or under `SUB`. `state_dtype` is the carried
    state's: float32; the tests read what bfloat16 costs."""
    S = q.shape[2]
    C = min(chunk, S)
    sub = min(SUB, C)
    if S % C or C % sub or (C // sub) & (C // sub - 1):
        raise ValueError(f'{S} positions in chunks of {C} and sub-blocks of {sub}: the chunk has to divide the sequence, and '
                         f'be {sub} times a power of two')
    return _core(q, k, v, g, beta, C, state_dtype)


def _causal_taps(x, w):
    """x (B, S, ch), w (ch, K) -> y_t = sum_j w[:, j] x_{t - (K - 1 - j)}, zero before the sequence, at float32."""
    K, f32 = w.shape[1], jnp.float32
    return sum(w[:, j].astype(f32) * _shift(x, K - 1 - j).astype(f32) for j in range(K))


class KimiDeltaAttention(nnx.Module):
    """a (B, S, dim) -> this share's part of the output product (B, S, dim)."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            head_dim: int = 128,
            conv_size: int = 4,
            gate_rank: Optional[int] = None,
            heads_held: Optional[int] = None,
            head_offset: int = 0,
            chunk: int = 64,
            eps: float = 1e-5,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        held = heads_held or num_heads
        if head_offset < 0 or head_offset + held > num_heads:
            raise ValueError(f'heads {head_offset} .. {head_offset + held} are not among {num_heads}')
        self.num_heads, self.heads_held, self.head_offset, self.head_dim = num_heads, held, head_offset, head_dim
        self.chunk = chunk
        rank, wide = gate_rank or head_dim, held * head_dim
        init = trunc_normal_(std=0.02)
        linear = functools.partial(nnx.Linear, use_bias=False, dtype=dtype, param_dtype=param_dtype, kernel_init=init, rngs=rngs)
        self.q_proj, self.k_proj, self.v_proj = linear(dim, wide), linear(dim, wide), linear(dim, wide)
        taps = lambda: nnx.Param(init(rngs.params(), (wide, conv_size), param_dtype))  # noqa: E731
        self.q_taps, self.k_taps, self.v_taps = taps(), taps(), taps()
        self.f_down, self.f_up = linear(dim, rank), linear(rank, wide)
        self.beta_proj = linear(dim, held)
        # Kimi Linear's: A = exp(A_log) uniform in (1, 16) a head; dt_bias the inverse softplus of a step in (1e-3, 0.1)
        key = rngs.params
        self.A_log = nnx.Param(jnp.log(jax.random.uniform(key(), (held,), param_dtype, 1.0, 16.0)))
        dt = jnp.exp(jax.random.uniform(key(), (wide,), param_dtype, jnp.log(1e-3), jnp.log(0.1)))
        self.dt_bias = nnx.Param(dt + jnp.log(-jnp.expm1(-dt)))
        self.g_down, self.g_up = linear(dim, rank), linear(rank, wide)
        self.o_norm = RmsNorm(head_dim, eps=eps, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.o_proj = linear(wide, dim)

    def take_heads(self, name: str, whole):
        """This share's slice of a leaf of the whole `num_heads`-head layer, by the leaf's name in this module."""
        cut = functools.partial(head_slice, whole, offset=self.head_offset, held=self.heads_held)
        if name in ('f_down.kernel', 'g_down.kernel', 'o_norm.scale'):
            return whole
        if name in ('A_log', 'beta_proj.kernel'):
            return cut(axis=whole.ndim - 1, width=1)
        return cut(axis=0 if name in ('o_proj.kernel', 'q_taps', 'k_taps', 'v_taps', 'dt_bias') else 1, width=self.head_dim)

    def mix_in(self, qkv, f, b):
        """The elementwise middle before the core: taps, SiLU and the L2 norms of q and k; the decay's log and beta at
        float32. qkv three of (B, S, H D), f (B, S, H D), b (B, S, H) -> q, k, v, g (B, H, S, D), beta (B, H, S)."""
        B, S, _ = f.shape
        H, D, f32 = self.heads_held, self.head_dim, jnp.float32
        heads = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)  # noqa: E731
        q, k, v = (heads(jax.nn.silu(_causal_taps(t, w[...]))) for t, w in zip(qkv, (self.q_taps, self.k_taps, self.v_taps)))
        q = q * jax.lax.rsqrt(jnp.square(q).sum(-1, keepdims=True) + L2_EPS) * D ** -0.5
        k = k * jax.lax.rsqrt(jnp.square(k).sum(-1, keepdims=True) + L2_EPS)
        rate = jnp.exp(self.A_log[...].astype(f32))[None, :, None, None]
        g = -rate * heads(jax.nn.softplus(f.astype(f32) + self.dt_bias[...].astype(f32)))
        beta = 2.0 * jax.nn.sigmoid(b.astype(f32)).transpose(0, 2, 1)
        store = qkv[0].dtype
        return q.astype(store), k.astype(store), v.astype(store), g, beta

    def mix_out(self, o, gate):
        """o (B, H, S, D), gate (B, S, H D) -> the gated, per-head normalised output (B, S, H D) in the gate's dtype."""
        B, H, S, D = o.shape
        o = self.o_norm(o).transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)

    def __call__(self, a):
        with tracing.scope('kda.proj'):
            qkv = (self.q_proj(a), self.k_proj(a), self.v_proj(a))
            f, gate, b = self.f_up(self.f_down(a)), self.g_up(self.g_down(a)), self.beta_proj(a)
        with tracing.scope('kda.mix'):
            q, k, v, g, beta = _written(self.mix_in(*_written((qkv, f, b))))
        with tracing.scope('kda.core'):
            # named as an attention core's output is, in the dtype it is stored in: half the float32 core's bytes to keep
            o = checkpoint_name(chunked_delta_rule(q, k, v, g, beta, self.chunk).astype(v.dtype), CORE_OUT)
        with tracing.scope('kda.mix'):
            y = _written(self.mix_out(*_written((o, gate))))
        with tracing.scope('kda.proj'):
            return self.o_proj(y)
