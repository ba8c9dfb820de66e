"""SDAR (JetLM, arXiv:2510.06303; ``model_type: sdar_moe``): a mixture-of-
experts decoder trained by block diffusion, as BD3-LM trains (arXiv:
2503.09573): the sequence is cut into blocks of ``block`` tokens; each block
is noised at a rate of its own, and the model restores its masked tokens
from the block's other tokens and the clean blocks before it.

One training step runs two streams through every layer, the noised sequence
x_t and the clean x_0, carried as a batch of 2B (a sequence's two streams
side by side, the noisy first), so that every per-token op, rope at
positions 0..L-1 among them, serves them unchanged; they are joined, 2L
positions, for attention alone (``layers.block_diffusion_attention``).

* the noise (``layers.block_diffusion_noise``): block b at the rate t_b =
  (level_b + 1) / ``grid``, token i masked where draw_i <= level_b, a masked
  token replaced by ``mask_id``, the last row of the vocabulary; level and
  draw are integer inputs;
* every layer: ``x = x + attn(RMSNorm(x))``, then ``x = x + moe(RMSNorm(x))``;
* attn: q = n W_q (``num_heads`` heads of ``head_dim``), k = n W_k and v =
  n W_v (``num_kv_heads`` heads), no bias; q and k RMS-normalised a head
  over their ``head_dim`` features, one weight each (QK-norm, the Qwen3-MoE
  convention ``sdar_moe`` inherits), then rope (half-split, ``rope_theta``);
  softmax at head_dim^-1/2 under the block-diffusion mask; o_proj;
* moe: softmax over all ``num_experts`` router outputs in float32, the
  ``experts_per_tok`` largest renormalised to sum 1 (``norm_topk_prob``:
  over their sum + 1e-6), weighting ``(silu(x W_g) * x W_u) W_d`` at
  ``expert_width``; no shared expert; both streams' tokens are routed;
* a final RMSNorm and an untied head over the NOISY stream only;
* loss = (1 / (B L)) sum_i weight_i CE(logits_i, x0_i), weight_i = 1 / t_b
  on the masked tokens and 0 elsewhere: the negative ELBO of the linear
  schedule (alpha_t = 1 - t).  No balance loss.
"""
from __future__ import annotations

import contextlib

from .. import layers
from .hybrid_mixers import p_, proj


def _attention(n, num_heads, num_kv_heads, head_dim, rope_theta, rms_eps,
               block, prefix):
    """n [2B, L, D] -> the attention's output [2B, L, D]."""
    seq_len = n.shape[1]

    def heads(x, count, name):
        x = layers.reshape(x, [-1, seq_len, count, head_dim])
        return layers.rope(layers.rms_norm(x, rms_eps, p_(prefix, name)),
                           rope_theta)

    def joined(x, count):               # [2B, L, h, d] -> [B, 2L, h, d]
        return layers.reshape(x, [-1, 2 * seq_len, count, head_dim])

    q = heads(proj(n, num_heads * head_dim, prefix, "q_proj"), num_heads,
              "q_norm")
    k = heads(proj(n, num_kv_heads * head_dim, prefix, "k_proj"),
              num_kv_heads, "k_norm")
    v = proj(n, num_kv_heads * head_dim, prefix, "v_proj")
    o = layers.block_diffusion_attention(
        joined(q, num_heads), joined(k, num_kv_heads),
        joined(layers.reshape(v, [-1, seq_len, num_kv_heads, head_dim]),
               num_kv_heads), block)
    return proj(layers.reshape(o, [-1, seq_len, num_heads * head_dim]),
                n.shape[2], prefix, "o_proj")


def sdar_moe(ids, level, draw, vocab_size, num_layers=48, hidden_size=2048,
             num_heads=32, num_kv_heads=4, head_dim=128, num_experts=128,
             experts_per_tok=8, expert_width=768, renormalize=True,
             rope_theta=1e6, rms_eps=1e-6, block=4, grid=1024,
             recompute=False, prefix="sdar"):
    """``ids`` [B, L] int64 clean tokens (none of them ``vocab_size - 1``,
    the mask), ``level`` [B, L / block] and ``draw`` [B, L] int64 in [0,
    grid) -> (logits over the noisy stream [B, L, vocab_size], loss weight
    [B, L]).  ``recompute``: True makes every layer two ``layers.recompute``
    stretches (its attention's and its experts'), a list of layer indices
    those layers."""
    seq_len = ids.shape[1]
    noisy, weight = layers.block_diffusion_noise(ids, level, draw, block,
                                                 vocab_size - 1, grid)

    def stream(x):
        return layers.reshape(x, [-1, 1, seq_len])

    both = layers.reshape(layers.concat([stream(noisy), stream(ids)], axis=1),
                          [-1, seq_len])                    # [2B, L]
    x = layers.embedding(both, size=[vocab_size, hidden_size],
                         param_attr=p_(prefix, "embed"))
    for i in range(num_layers):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute

        def stretch():
            return layers.recompute() if again else contextlib.nullcontext()

        with stretch():
            n = layers.rms_norm(x, rms_eps, p_(at, "input_norm"))
            x = layers.elementwise_add(x, _attention(
                n, num_heads, num_kv_heads, head_dim, rope_theta, rms_eps,
                block, at))
        with stretch():
            n = layers.rms_norm(x, rms_eps, p_(at, "post_norm"))
            o, _, _ = layers.moe(
                n, num_experts, expert_width, top_k=experts_per_tok,
                capacity_factor=None, act="silu", gated=True,
                gate_attr=p_(at, "router"), param_attr=p_(at, "experts"),
                renormalize=renormalize)
            x = layers.elementwise_add(x, o)
    x = layers.reshape(layers.slice(
        layers.reshape(x, [-1, 2, seq_len, hidden_size]), axes=[1],
        starts=[0], ends=[1]), [-1, seq_len, hidden_size])  # the noisy
    x = layers.rms_norm(x, rms_eps, p_(prefix, "final_norm"))
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=p_(prefix, "head"), bias_attr=False)
    return logits, weight


def sdar_moe_loss(ids, level, draw, vocab_size, **model):
    """(1 / (B L)) sum_i weight_i CE(logits_i, ids_i) of ``sdar_moe(ids,
    level, draw, ...)``: the masked tokens' cross-entropy at 1 / t_b, over
    every token of the batch; ``model`` are ``sdar_moe``'s keywords."""
    logits, weight = sdar_moe(ids, level, draw, vocab_size, **model)
    ce = layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(ids, [-1, 1]))
    return layers.mean(layers.elementwise_mul(
        ce, layers.reshape(weight, [-1, 1])))
