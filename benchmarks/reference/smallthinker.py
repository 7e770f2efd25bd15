"""Plain reference of SmallThinker-21BA3B-Instruct (config.json of
PowerInfer/SmallThinker-21BA3B-Instruct; family paper arXiv:2507.20984), in
`jax.numpy` float32 at `Precision.HIGHEST`: token embedding, pre-norm layers of
grouped-query attention and a sparse mixture of ReGLU experts, a final RMSNorm,
an untied output head. The loss is next-token cross-entropy; there is no
multi-token-prediction term.

Layer l of h (S, d), every linear map without bias:
  a = RMSNorm_1(h)
  r = a W_r; idx = top-k(r); w = softmax(r[idx])       (the router reads the ATTENTION's input)
  q, k, v = a W_q, a W_k, a W_v; where rope_layout[l] == 1, q and k turn by the rotary table
  key j is seen by query i when j <= i and, where sliding_window_layout[l] == 1, i - j < window
  h' = h + softmax(q k^T / sqrt(head_dim) + mask) v W_o, query head g on key/value head g // group
  h_out = h' + sum over chosen e held here of w_e (relu(b W_gate,e) * (b W_up,e)) W_down,e, b = RMSNorm_2(h')

Given ONE CHIP'S SHARE exactly as the program is: `experts_held` routed experts
from `expert_offset` (the router scores all `moe_num_primary_experts`, the
weights stay a softmax over all chosen, and what experts held elsewhere would
add is left out), `vocab_held` rows of embedding and head.

What `config.json` does not settle, and what is taken here (the configuration
file lists each under `assumed`):
  * the router's input is the attention's NORMALISED input a (not the raw residual h);
  * the window counts the query's own position: i - j < window;
  * rotary dimensions pair as halves (j with j + head_dim / 2), not interleaved;
  * causal attention runs over the whole sequence: no document boundaries.

It computes in blocks so that a sequence of 16384 fits beside the weights: every
layer, every block of queries and every chunk of the head is rematerialised in
the backward pass, and a window layer's query block reads only the keys its
window leaves (a full layer's reads them all, masked). That changes no value.
Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

IGNORE = -1
HEAD_CHUNK = 4096


def init_spec(cfg) -> dict:
    """name -> (shape, kind): matrices 'normal' (std 0.02), norm scales 'ones' (1 + normal)."""
    d, hd = cfg['hidden_size'], cfg['head_dim']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    held, hidden = cfg['experts_held'], cfg['moe_ffn_hidden_size']
    spec = {'embed.embedding': ((cfg['vocab_held'], d), 'normal'), 'norm.scale': ((d,), 'ones'),
            'head.kernel': ((d, cfg['vocab_held']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        b = f'blocks.{i}.'
        spec.update({
            b + 'norm1.scale': ((d,), 'ones'), b + 'norm2.scale': ((d,), 'ones'),
            b + 'attn.q_proj.kernel': ((d, heads * hd), 'normal'), b + 'attn.k_proj.kernel': ((d, kv * hd), 'normal'),
            b + 'attn.v_proj.kernel': ((d, kv * hd), 'normal'), b + 'attn.proj.kernel': ((heads * hd, d), 'normal'),
            b + 'mlp.router': ((d, cfg['moe_num_primary_experts']), 'normal'),
            b + 'mlp.w_gate': ((held, d, hidden), 'normal'), b + 'mlp.w_up': ((held, d, hidden), 'normal'),
            b + 'mlp.w_down': ((held, hidden, d), 'normal')})
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix, the embedding and the expert stacks among them; norm scales are vectors."""
    return False


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary turn of (..., S, D): dimension j pairs with j + D/2, frequency theta^(-2j/D), position = index."""
    S, D = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, p, b, a, turn: bool, window, precision, block_q):
    """Grouped-query attention of the normalised input a (S, d), causal and, with `window`, within it.
    Queries go in blocks of `block_q`, every block against a key slice of ONE length, so that the blocks are the
    iterations of one `lax.map` (256 unrolled blocks with their backward passes took minutes to compile and
    gigabytes of the host): a window layer's block at i0 reads the keys [i0 - window + 1, i0 + block_q), the
    keys before position 0 being zero rows the mask excludes; a full layer's block reads all S keys. Masked
    keys weigh exactly 0 in the softmax, so no value differs from one S x S softmax under the mask."""
    S = a.shape[0]
    H, KV, D = cfg['num_attention_heads'], cfg['num_key_value_heads'], cfg['head_dim']
    heads = lambda t, n: t.reshape(S, n, D).transpose(1, 0, 2)  # noqa: E731
    q = heads(ops.matmul(a, p[b + 'attn.q_proj.kernel'], precision), H)
    k = heads(ops.matmul(a, p[b + 'attn.k_proj.kernel'], precision), KV)
    v = heads(ops.matmul(a, p[b + 'attn.v_proj.kernel'], precision), KV)
    if turn:
        q, k = rope(q, cfg['rope_theta']), rope(k, cfg['rope_theta'])
    G, scale, bq = H // KV, D ** -0.5, min(block_q, S)
    q = q.reshape(KV, G, S, D)                                      # query head g reads key/value head g // group
    if window is None or window >= S:
        window, before, span = None, 0, S
    else:
        before, span = window - 1, window - 1 + bq                  # zero rows before position 0, keys a block reads
        k, v = (jnp.pad(t, ((0, 0), (before, 0), (0, 0))) for t in (k, v))

    @jax.checkpoint
    def block(i):
        first_query = i * bq
        row = first_query if window is not None else 0                          # the slice's first row in (padded) k, v
        first_key = row - before                                                # .. and the position that row holds
        qb = jax.lax.dynamic_slice_in_dim(q, first_query, bq, axis=2)
        kb, vb = (jax.lax.dynamic_slice_in_dim(t, row, span, axis=1) for t in (k, v))
        s = ops.einsum('hgqd,hkd->hgqk', qb, kb, precision) * scale
        qi = (first_query + jnp.arange(bq))[:, None]
        kj = (first_key + jnp.arange(span))[None, :]
        seen = (kj <= qi) & (kj >= 0) if window is None else (kj <= qi) & (kj >= 0) & (qi - kj < window)
        return ops.einsum('hgqk,hkd->hgqd', jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vb, precision)

    out = jax.lax.map(block, jnp.arange(S // bq))                   # (blocks, KV, G, bq, D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(H, S, D)
    return ops.matmul(out.transpose(1, 0, 2).reshape(S, H * D), p[b + 'attn.proj.kernel'], precision)


def routes(cfg, p, b, a):
    """Chosen experts (S, k) of all `moe_num_primary_experts` and their weights, a softmax over the chosen
    logits; float32 at full precision whatever the matmuls' `precision` (the configuration states the router
    in float32)."""
    chosen, idx = jax.lax.top_k(ops.matmul(a, p[b + 'mlp.router'], 'float32'), cfg['moe_num_active_primary_experts'])
    return idx, jax.nn.softmax(chosen, axis=-1)


def experts(cfg, p, b, x, a, precision):
    """sum over the held experts of weight x ReGLU expert(x), routed on a; -> (y, chosen ids). Every held expert
    reads every token and a token's weight for an expert it did not choose is 0; the weight multiplies the
    expert's hidden activation (the down-projection is linear, so that is w_e times its output), and the sum
    over experts is the contraction of one product over (expert, hidden). Three products a layer: a Python loop
    over the experts, 8 layers x 8 experts x 3 products with their backward passes, took the compiler minutes."""
    idx, w = routes(cfg, p, b, a)
    held = cfg['expert_offset'] + jnp.arange(cfg['experts_held'])
    w_held = jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0).sum(1)                # (S, held); 0 where not chosen
    gate = ops.einsum('sd,edh->seh', x, p[b + 'mlp.w_gate'], precision)
    up = ops.einsum('sd,edh->seh', x, p[b + 'mlp.w_up'], precision)
    hidden = jax.nn.relu(gate) * up * w_held[:, :, None]
    return ops.einsum('seh,ehd->sd', hidden, p[b + 'mlp.w_down'], precision), idx


def layer(cfg, p, i, x, precision, block_q):
    b, eps = f'blocks.{i}.', cfg['rms_norm_eps']
    a = rms_norm(x, p[b + 'norm1.scale'], eps)
    window = cfg['sliding_window_size'] if cfg['sliding_window_layout'][i] else None
    x = x + attention(cfg, p, b, a, bool(cfg['rope_layout'][i]), window, precision, block_q)
    y, idx = experts(cfg, p, b, rms_norm(x, p[b + 'norm2.scale'], eps), a, precision)
    return x + y, idx


def cross_entropy_sum(cfg, p, h, target, precision):
    """Summed next-token cross-entropy over the positions whose target is not IGNORE, and the logits."""
    logits = ops.matmul(rms_norm(h, p['norm.scale'], cfg['rms_norm_eps']), p['head.kernel'], precision)
    valid = target != IGNORE
    safe = jnp.where(valid, target, 0)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, nll, 0.0).sum(), logits


def forward(cfg, p, ids, target, precision: str = 'float32', block_q: int = 1024):
    """One sequence: ids, target (S,). -> dict of `loss_main_sum` (summed over its valid positions), `n_main`,
    `logits`, `routes` ((layers, S, k) chosen ids)."""
    cfg = dict(cfg, rope_layout=tuple(cfg['rope_layout']), sliding_window_layout=tuple(cfg['sliding_window_layout']))
    run = jax.checkpoint(lambda p, x, i: layer(cfg, p, i, x, precision, block_q), static_argnums=(2,))
    x = p['embed.embedding'][ids]
    chosen = []
    for i in range(cfg['num_hidden_layers']):
        x, idx = run(p, x, i)
        chosen.append(idx)
    head = jax.checkpoint(lambda p, h, t: cross_entropy_sum(cfg, p, h, t, precision))
    parts = [head(p, x[i:i + HEAD_CHUNK], target[i:i + HEAD_CHUNK]) for i in range(0, x.shape[0], HEAD_CHUNK)]
    return {'loss_main_sum': sum(s for s, _ in parts), 'n_main': (target != IGNORE).sum(),
            'logits': jnp.concatenate([l for _, l in parts], axis=0), 'routes': jnp.stack(chosen)}


def loss(cfg, p, ids, target, n_main, n_mtp=None, precision: str = 'float32', block_q: int = 1024):
    """One sequence's share of the batch's loss: its summed cross-entropy over the BATCH's count of valid
    positions (`n_main`; `n_mtp` is `lm_train_step.py`'s and unused: no MTP term). -> (loss share, chosen ids)."""
    out = forward(cfg, p, ids, target, precision, block_q)
    return out['loss_main_sum'] / n_main, out['routes']
