"""Qwen3-Next (Qwen3-Next-80B-A3B-Instruct, `model_type: qwen3_next`; the
model's public `config.json`, and the public `modeling_qwen3_next.py` of
`transformers` for what the config leaves open): a decoder whose mixers
are Gated DeltaNet layers (the delta rule with one decay a head, key heads
shared by pairs of value heads) with gated softmax attention every
`full_attention_interval`-th layer, every layer followed by an expert
layer with a softmax router and one shared expert that the token gates.
Built through the layers API; the expert layer may hold a share of the
experts, the vocabulary may be a slice and the layers a run of the
published ones, which is how one chip of an expert-parallel job sees the
model.

The equations. `x` is `[s, hidden]`; no projection has a bias; no dropout.
Layer `l` counts from 0 as published and is a full-attention layer iff
`(l + 1) % full_attention_interval == 0`.

  RMS0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w), w seeded 0: the
    model's zero-centred norm (input, post-attention, final, q_norm,
    k_norm). The program's `rms_norm` holds `1 + w` as one weight seeded
    1: the same function and, under Adam with no decay, the same update.
  x0 = E[tokens]                                              (no scale)
  x  = x + Mixer_l(RMS0(x; w_in_l));  x = x + MoE_l(RMS0(x; w_post_l))
  Mixer_l, linear_attention, H_k key heads and H_v value heads of d:
    [q ; k ; v ; z] = W_qkvz u          [b ; a] = W_ba u   (H_v each)
    [q ; k ; v] = SiLU(conv([q ; k ; v]))   causal, depthwise,
      linear_conv_kernel_dim taps, zero state before the row's start
    q, k -> [s, H_k, d], each L2-normalised over d;  v, z -> [s, H_v, d]
    beta_t[n] = sigmoid(b_t[n])
    g_t[n] = -exp(A_log[n]) * softplus(a_t[n] + dt_bias[n])     float32
    value head n reads key head n // (H_v / H_k); state S in R^{d x d}:
      S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = d^-1/2 S_t^T q_t
    y_t[n] = w_norm * (o_t[n] / sqrt(mean(o_t[n]^2) + eps)) * SiLU(z_t[n])
    out = W_out y
  Mixer_l, full_attention, h query heads over g key/value heads of d:
    q, gate = W_q u, W_gate u  [s, h, d] each;  k, v = W_k u, W_v u
    q, k = RMS0 over d (one weight each), then rotary positions on the
      first `partial_rotary_factor * d` lanes (rotate-half within them)
    a[i, n] = sum_{j <= i} softmax_j(q[i, n] . k[j, n // (h/g)] / sqrt(d))
              v[j, n // (h/g)];   out = W_o (a * sigmoid(gate))
  MoE_l:  p = softmax(W_r u) in float32;  sel = top-k(p);
    w = p[sel] / sum p[sel]                                (norm_topk_prob)
    f = sum over e in sel held here of w_e expert_e(u)
    out = f + sigmoid(w_sg . u) * shared(u)
  logits = W_head RMS0(x; w_final)                              (untied)
  loss = mean over positions of the next-token cross-entropy, float32

The published `q_proj` and `in_proj_qkvz`/`in_proj_ba` interleave their
outputs by head; with seeded weights any fixed order of columns is the
same model, and here the parts lie side by side (`.q` and `.gate` are two
products in `decoder_parts.attention`). The router's balancing term is not
in the loss and the router has no correction (`Bias` zeros, untrained).
The mixers are `decoder_parts.gated_delta_net` and
`decoder_parts.attention`, the expert layer `decoder_parts.expert_ffn`
with the shared expert's gate. `build_qwen3_next` sets three gauges:
`gated_delta_layers`, `attention_layers` and `expert_layers`.
"""

from __future__ import annotations

from .. import layers, profiler
from .decoder_parts import (attention, attr, expert_ffn, gated_delta_net,
                            norm, proj)

__all__ = ["Qwen3NextConfig", "build_qwen3_next"]


class Qwen3NextConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` published layers from `first_layer` on,
    `experts_held` of `num_experts` from `held_from` on, and `vocab_size`
    rows of the vocabulary."""

    score_func = "softmax"  # the router's; `decoder_parts.expert_ffn` reads it
    shared_expert_gate = True
    routed_scaling_factor = 1.0

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, first_layer=0,
                 full_attention_interval=4, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_experts=512,
                 experts_held=None, held_from=0, num_experts_per_token=10,
                 norm_topk_prob=True, rms_norm_eps=1e-6,
                 initializer_range=0.02, l2norm_epsilon=1e-6):
        if shared_expert_intermediate_size % moe_intermediate_size:
            raise ValueError(
                "qwen3_next: the shared expert's width "
                f"{shared_expert_intermediate_size} is no multiple of an "
                f"expert's {moe_intermediate_size}")
        if linear_key_head_dim != linear_value_head_dim:
            raise ValueError("qwen3_next: the delta rule's key and value "
                             "heads are one width")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_layer = first_layer
        self.full_attention_interval = full_attention_interval
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = rope_theta
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_shared_experts = (shared_expert_intermediate_size
                                   // moe_intermediate_size)
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.moe_renormalize = norm_topk_prob
        self.router_bias_scale = 0.0  # the model has no correction
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.l2norm_epsilon = l2norm_epsilon

    def layer_kinds(self):
        """(published index, "linear_attention" or "full_attention") of
        each layer held."""
        return [(l, "full_attention"
                 if (l + 1) % self.full_attention_interval == 0
                 else "linear_attention")
                for l in range(self.first_layer,
                               self.first_layer + self.num_hidden_layers)]


def build_qwen3_next(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the mean next-token loss over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, and `loads`, one `[experts_held]` int32
    var for each expert layer."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(tokens, (cfg.vocab_size, cfg.hidden_size),
                         param_attr=attr("qwen3next.embed", cfg))
    loads = []
    kinds = cfg.layer_kinds()
    for l, kind in kinds:
        name = f"qwen3next.layer{l}"
        u = norm(x, name + ".input_norm", cfg)
        if kind == "linear_attention":
            mixed = gated_delta_net(u, cfg, name + ".gdn")
        else:
            mixed = attention(u, cfg, name + ".attn", gated=True,
                              rope_theta=cfg.rope_theta,
                              rotary_dim=cfg.rotary_dim)
        x = layers.elementwise_add(x, mixed)
        out, load = expert_ffn(norm(x, name + ".post_attn_norm", cfg), cfg,
                               name)
        loads.append(load)
        x = layers.elementwise_add(x, out)
    logits = proj(norm(x, "qwen3next.final_norm", cfg), cfg.vocab_size,
                  "qwen3next.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    linear = sum(kind == "linear_attention" for _, kind in kinds)
    profiler.set_counter("gated_delta_layers", linear)
    profiler.set_counter("attention_layers", len(kinds) - linear)
    profiler.set_counter("expert_layers", len(loads))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
