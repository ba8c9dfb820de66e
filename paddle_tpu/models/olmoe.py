"""OLMoE: a decoder of sparse-expert blocks (Muennighoff et al.,
arXiv:2409.02060; ``model_type: olmoe`` in transformers).

One block: RMSNorm, attention whose queries and keys are RMS-normalized
over ALL their features before the split into heads (QK-norm), rotary
positions on both, causal; RMSNorm, then ``experts_per_tok`` of
``num_experts`` SiLU-gated experts picked by the largest router
probabilities, which weight them as they are (not renormalized); both with
a residual.  No bias anywhere, a final RMSNorm, and a head untied from the
embedding.  The expert layer is ``layers.moe``'s dropless lowering
(``capacity_factor=None``): no token is dropped, as the model was trained.
"""
from __future__ import annotations

from .. import layers
from ..param_attr import ParamAttr


def _p(prefix, name):
    return ParamAttr(name=f"{prefix}.{name}")


def _block(x, hidden_size, num_heads, num_experts, experts_per_tok,
           expert_width, rope_theta, rms_eps, prefix):
    """(x + attention + experts, (aux_loss, z_loss)) for x [B, T, hidden]."""
    seq_len = x.shape[1]
    heads = [-1, seq_len, num_heads, hidden_size // num_heads]

    def proj(inp, name):
        return layers.fc(inp, size=hidden_size, num_flatten_dims=2,
                         param_attr=_p(prefix, name), bias_attr=False)

    a = layers.rms_norm(x, rms_eps, _p(prefix, "input_norm"))
    q = layers.rms_norm(proj(a, "wq"), rms_eps, _p(prefix, "q_norm"))
    k = layers.rms_norm(proj(a, "wk"), rms_eps, _p(prefix, "k_norm"))
    v = proj(a, "wv")
    q = layers.rope(layers.reshape(q, heads), rope_theta)
    k = layers.rope(layers.reshape(k, heads), rope_theta)
    o = layers.flash_attention(q, k, layers.reshape(v, heads), causal=True)
    x = layers.elementwise_add(
        x, proj(layers.reshape(o, [-1, seq_len, hidden_size]), "wo"))

    m = layers.rms_norm(x, rms_eps, _p(prefix, "post_norm"))
    y, aux, z = layers.moe(
        m, num_experts, expert_width, top_k=experts_per_tok,
        capacity_factor=None, act="silu", gated=True,
        gate_attr=_p(prefix, "router"), param_attr=_p(prefix, "experts"))
    return layers.elementwise_add(x, y), (aux, z)


def olmoe(ids, vocab_size, hidden_size=2048, num_layers=16, num_heads=16,
          num_experts=64, experts_per_tok=8, expert_width=1024,
          rope_theta=10000.0, rms_eps=1e-5, prefix="olmoe"):
    """``ids`` [B, T] int64 -> (logits [B, T, vocab_size], aux_losses): the
    router's (load-balancing loss, z-loss) of every layer, for the caller
    to weight into the training loss."""
    x = layers.embedding(ids, size=[vocab_size, hidden_size],
                         param_attr=_p(prefix, "embed"))
    aux_losses = []
    for i in range(num_layers):
        x, aux = _block(x, hidden_size, num_heads, num_experts,
                        experts_per_tok, expert_width, rope_theta, rms_eps,
                        f"{prefix}.l{i}")
        aux_losses.append(aux)
    x = layers.rms_norm(x, rms_eps, _p(prefix, "final_norm"))
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=_p(prefix, "head"), bias_attr=False)
    return logits, aux_losses
