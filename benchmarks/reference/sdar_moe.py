"""Plain reference of SDAR-30B-A3B-Chat (config.json of JetLM/SDAR-30B-A3B-Chat,
`model_type` `sdar_moe`; SDAR, JetAstra/SDAR 2025; the objective is block
diffusion, BD3-LM arXiv:2503.09573, with LLaDA's forward process
arXiv:2502.09992), in `jax.numpy` float32 at `Precision.HIGHEST`: token
embedding, pre-norm layers of grouped-query attention and a sparse mixture of
SwiGLU experts (Qwen3-MoE's trunk), a final RMSNorm, an untied output head.

A sequence x of L clean ids, block length K, block of position i: b(i) = i div K.
It is GIVEN the noised ids n (MASK where a position was masked, else x_i) and
the masking probability p of the sequence; it draws nothing.

  input: the 2 L rows [E(n_0..n_{L-1}); E(x_0..x_{L-1})]; row r carries position r mod L
  layer, every linear map without bias:
    a = RMSNorm_1(h); q, k, v = a W_q, a W_k, a W_v (query head g reads key/value head g div group)
    q <- RMSNorm_q(q), k <- RMSNorm_k(k) per head over its head_dim (learned scale); rotary turn by r mod L
    h <- h + softmax(q k^T / sqrt(head_dim) + M) v W_o
    e = RMSNorm_2(h); idx = top-k(e W_r); w = softmax over all experts renormalised over the chosen
      (= a softmax over the chosen logits)
    h <- h + sum over chosen j held here of w_j (silu(e W_gate,j) * (e W_up,j)) W_down,j
  mask M (0 where seen, -inf elsewhere), noised row i, clean row L + i:
    noised i sees noised j iff b(j) = b(i);  noised i sees clean j iff b(j) < b(i);
    clean i sees clean j iff b(j) <= b(i);   clean i sees no noised row
  loss: z_i = head(RMSNorm_f(h_i)) at the L noised rows; m_i = (n_i == MASK);
    loss = 1 / (B L) * sum_i m_i nll(z_i, x_i) / p      (the target is the clean token at the SAME position)

Given ONE CHIP'S SHARE exactly as the program is: `experts_held` routed experts
from `expert_offset` (the router scores all `num_experts`, the weights stay a
softmax over all chosen, and what experts held elsewhere would add is left
out), `vocab_held` rows of embedding and head.

What `config.json` does not settle, and what is taken here (the configuration
file lists each under `assumed`): the block length and the forward process;
the 1/p weight and the division by B L; same-position targets; the per-head
q/k norms (Qwen3-MoE's, from which `sdar_moe` derives); rotary dimensions pair
as halves (j with j + head_dim / 2) and all of them turn; no auxiliary router
loss in the step.

It computes in blocks so that 2 x 8192 rows fit beside the weights: every
layer, every block of queries, every chunk of the experts' rows and of the head
is rematerialised in the backward pass. A block of queries reads ALL 2 L keys
under the mask, and every layer computes all 2 L rows, the last one too (the
loss reads its noised rows; what the program leaves out there has no effect on
it). That changes no value. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

HEAD_CHUNK = 4096
EXPERT_ROWS = 4096


def init_spec(cfg) -> dict:
    """name -> (shape, kind): matrices 'normal' (std 0.02), norm scales 'ones' (1 + normal)."""
    d, hd = cfg['hidden_size'], cfg['head_dim']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    held, hidden = cfg['experts_held'], cfg['moe_intermediate_size']
    spec = {'embed.embedding': ((cfg['vocab_held'], d), 'normal'), 'norm.scale': ((d,), 'ones'),
            'head.kernel': ((d, cfg['vocab_held']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        b = f'blocks.{i}.'
        spec.update({
            b + 'norm1.scale': ((d,), 'ones'), b + 'norm2.scale': ((d,), 'ones'),
            b + 'attn.q_proj.kernel': ((d, heads * hd), 'normal'), b + 'attn.k_proj.kernel': ((d, kv * hd), 'normal'),
            b + 'attn.v_proj.kernel': ((d, kv * hd), 'normal'), b + 'attn.proj.kernel': ((heads * hd, d), 'normal'),
            b + 'attn.q_norm.scale': ((hd,), 'ones'), b + 'attn.k_norm.scale': ((hd,), 'ones'),
            b + 'mlp.router': ((d, cfg['num_experts']), 'normal'),
            b + 'mlp.w_gate': ((held, d, hidden), 'normal'), b + 'mlp.w_up': ((held, d, hidden), 'normal'),
            b + 'mlp.w_down': ((held, hidden, d), 'normal')})
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix, the embedding and the expert stacks among them; norm scales are vectors."""
    return False


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta, positions):
    """Rotary turn of (..., R, D) whose row r is at `positions[r]`: dimension j pairs with j + D/2, frequency
    theta^(-2j/D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seen(q_rows, k_rows, length: int, block: int):
    """The mask's four rules for query rows (Q, 1) and key rows (1, K) of the 2 x length rows, noised first."""
    q_noised, k_noised = q_rows < length, k_rows < length
    bq, bk = (q_rows % length) // block, (k_rows % length) // block
    return jnp.where(q_noised,
                     jnp.where(k_noised, bk == bq, bk < bq),         # noised sees its own noised block, earlier clean ones
                     jnp.where(k_noised, False, bk <= bq))          # clean sees clean up to its own block, nothing noised


def attention(cfg, p, b, a, precision, block_q):
    """Grouped-query attention of the normalised input a (2 L, d) under the block-diffusion mask. Queries go in
    blocks of `block_q`, the iterations of one `lax.map`, every block against all 2 L keys under the mask:
    masked keys weigh exactly 0 in the softmax, so no value differs from one (2 L, 2 L) softmax."""
    R = a.shape[0]
    L = R // 2
    H, KV, D, eps = cfg['num_attention_heads'], cfg['num_key_value_heads'], cfg['head_dim'], cfg['rms_norm_eps']
    heads = lambda t, n: t.reshape(R, n, D).transpose(1, 0, 2)  # noqa: E731
    q = heads(ops.matmul(a, p[b + 'attn.q_proj.kernel'], precision), H)
    k = heads(ops.matmul(a, p[b + 'attn.k_proj.kernel'], precision), KV)
    v = heads(ops.matmul(a, p[b + 'attn.v_proj.kernel'], precision), KV)
    positions = jnp.arange(R) % L
    q = rope(rms_norm(q, p[b + 'attn.q_norm.scale'], eps), cfg['rope_theta'], positions)
    k = rope(rms_norm(k, p[b + 'attn.k_norm.scale'], eps), cfg['rope_theta'], positions)
    G, scale, bq = H // KV, D ** -0.5, min(block_q, R)
    q = q.reshape(KV, G, R, D)                                      # query head g reads key/value head g // group

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)
        s = ops.einsum('hgqd,hkd->hgqk', qb, k, precision) * scale
        mask = seen((i * bq + jnp.arange(bq))[:, None], jnp.arange(R)[None, :], L, cfg['block_length'])
        return ops.einsum('hgqk,hkd->hgqd', jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), v, precision)

    out = jax.lax.map(block, jnp.arange(R // bq))                   # (blocks, KV, G, bq, D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(H, R, D)
    return ops.matmul(out.transpose(1, 0, 2).reshape(R, H * D), p[b + 'attn.proj.kernel'], precision)


def routes(cfg, p, b, e):
    """Chosen experts (R, k) of all `num_experts` and their weights: a softmax over all experts, renormalised
    over the chosen; float32 at full precision whatever the matmuls' `precision` (the configuration states the
    router in float32)."""
    scores = jax.nn.softmax(ops.matmul(e, p[b + 'mlp.router'], 'float32'), axis=-1)
    chosen, idx = jax.lax.top_k(scores, cfg['num_experts_per_tok'])
    return idx, chosen / chosen.sum(-1, keepdims=True)


def experts(cfg, p, b, e, precision):
    """sum over the held experts of weight x SwiGLU expert(e); -> (y, chosen ids). Every held expert reads
    every row and a row's weight for an expert it did not choose is 0; the weight multiplies the expert's
    hidden activation (the down-projection is linear), and the sum over experts is the contraction of one
    product over (expert, hidden). Three products a chunk of rows, the chunks the iterations of one `lax.map`
    (a Python loop over experts took the compiler minutes and gigabytes of the host, PERF.md 7 (m))."""
    idx, w = routes(cfg, p, b, e)
    held = cfg['expert_offset'] + jnp.arange(cfg['experts_held'])
    w_held = jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0).sum(1)                # (R, held); 0 where not chosen
    rows = min(EXPERT_ROWS, e.shape[0])

    @jax.checkpoint
    def chunk(args):
        x, wh = args
        gate = ops.einsum('sd,edh->seh', x, p[b + 'mlp.w_gate'], precision)
        up = ops.einsum('sd,edh->seh', x, p[b + 'mlp.w_up'], precision)
        return ops.einsum('seh,ehd->sd', jax.nn.silu(gate) * up * wh[:, :, None], p[b + 'mlp.w_down'], precision)

    y = jax.lax.map(chunk, (e.reshape(-1, rows, e.shape[1]), w_held.reshape(-1, rows, w_held.shape[1])))
    return y.reshape(e.shape), idx


def layer(cfg, p, i, x, precision, block_q):
    b, eps = f'blocks.{i}.', cfg['rms_norm_eps']
    x = x + attention(cfg, p, b, rms_norm(x, p[b + 'norm1.scale'], eps), precision, block_q)
    y, idx = experts(cfg, p, b, rms_norm(x, p[b + 'norm2.scale'], eps), precision)
    return x + y, idx


def cross_entropy(cfg, p, h, target, precision):
    """Cross-entropy of every row against its target, and the logits."""
    logits = ops.matmul(rms_norm(h, p['norm.scale'], cfg['rms_norm_eps']), p['head.kernel'], precision)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0], logits


def forward(cfg, p, noised, clean, prob, precision: str = 'float32', block_q: int = 1024):
    """One sequence: noised, clean ids (L,), its masking probability `prob`. -> dict of `loss_weighted_sum`
    (sum over masked positions of nll / prob), `nll_masked_sum`, `masked` (their count), `logits` (L, vocabulary)
    at the noised rows, `routes` ((layers, 2 L, k) chosen ids)."""
    L = clean.shape[0]
    run = jax.checkpoint(lambda p, x, i: layer(cfg, p, i, x, precision, block_q), static_argnums=(2,))
    x = p['embed.embedding'][jnp.concatenate([noised, clean])]
    chosen = []
    for i in range(cfg['num_hidden_layers']):
        x, idx = run(p, x, i)
        chosen.append(idx)
    head = jax.checkpoint(lambda p, h, t: cross_entropy(cfg, p, h, t, precision))
    parts = [head(p, x[i:min(i + HEAD_CHUNK, L)], clean[i:i + HEAD_CHUNK]) for i in range(0, L, HEAD_CHUNK)]    # the noised rows
    nll = jnp.concatenate([n for n, _ in parts])
    masked = noised == cfg['mask_token_id']
    nll_masked = jnp.where(masked, nll, 0.0).sum()
    return {'loss_weighted_sum': nll_masked / prob, 'nll_masked_sum': nll_masked, 'masked': masked.sum(),
            'logits': jnp.concatenate([l for _, l in parts], axis=0), 'routes': jnp.stack(chosen)}


def loss(cfg, p, noised, clean, prob, positions, precision: str = 'float32', block_q: int = 1024):
    """One sequence's share of the batch's loss: its weighted sum over the BATCH's B L `positions`.
    -> (loss share, (chosen ids, the sequence's plain sum over its masked positions, their count))."""
    out = forward(cfg, p, noised, clean, prob, precision, block_q)
    return out['loss_weighted_sum'] / positions, (out['routes'], out['nll_masked_sum'], out['masked'])
