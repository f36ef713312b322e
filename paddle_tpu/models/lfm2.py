"""LFM2 (LiquidAI LFM2-24B-A2B, `model_type: lfm2_moe`; the model's public
`config.json`, and the public `modeling_lfm2_moe.py` of `transformers` for
what the config leaves open): a decoder whose mixers are double-gated
short convolutions, with grouped-query attention every fourth layer, each
followed by a dense SiLU-gated feed-forward (the leading layers) or by an
expert layer with a sigmoid router and no shared expert. Built through
the layers API; the expert layer may hold a share of the experts, the
vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of an expert-parallel job sees the model.

The equations. `x` is `[s, hidden]`, `h` query heads and `g` key/value
heads of `d`; no projection has a bias; every RMSNorm has a learned weight
and `norm_eps`; no dropout. Layer `l` counts from 0 as published.

  x0 = E[tokens]                                              (no scale)
  x  = x + Mixer_l(RMSNorm(x; w_operator_norm))
  x  = x + FFN_l(RMSNorm(x; w_ffn_norm))
  Mixer_l, `layer_types[l]` "conv":
    [B ; C ; xs] = W_in u                  (hidden -> 3 hidden, this order)
    c_t = sum_{i < L} w[:, i] * (B * xs)_{t-L+1+i}    depthwise, causal,
      L = conv_L_cache taps, zero state before the row's start, no bias,
      no activation
    out = W_out (C * c)
  Mixer_l, "full_attention":
    q, k, v = W_q u [s, h, d], W_k u [s, g, d], W_v u [s, g, d]
    q, k = RMSNorm(q; w_qn), RMSNorm(k; w_kn) over d  (one weight of d each)
    q, k = RoPE(q, k; rope_theta, rotate-half, positions 0..s-1)
    a[i, n] = sum_{j <= i} softmax_j(q[i, n] . k[j, n // (h/g)] / sqrt(d))
              v[j, n // (h/g)];   out = W_o a                    (no gate)
  FFN_l, l < num_dense_layers:  W_down(silu(W_gate u) * W_up u)
  FFN_l, else:  p = sigmoid(W_r u) in float32;  sel = top-k(p + b);
    w = routed_scaling_factor * p[sel] / (sum p[sel] + 1e-6)  (norm_topk_prob)
    f = sum over e in sel held here of w_e expert_e(u);  no shared expert
  logits = E^T RMSNorm(x; w_embedding_norm)                         (tied)
  loss = mean over positions of the next-token cross-entropy, float32

The router's correction `b` (`use_expert_bias`) is persistable, seeded,
and not trained (the published model moves it by a load-balancing rule
outside the gradient, which this repo lacks). The convolution is the op
`short_conv1d` with no activation between two `elementwise_mul`s
(`decoder_parts.gated_short_conv`), the expert layer the op
`moe_experts`, the attention `fused_multihead_attention`, which also norms
q and k and gives them their positions. `build_lfm2` sets three gauges:
`gated_conv_layers`, `attention_layers` and `expert_layers`.
"""

from __future__ import annotations

from .. import layers, profiler
from ..framework import default_main_program
from .decoder_parts import (attention, attr, expert_ffn, ffn,
                            gated_short_conv, norm)

__all__ = ["Lfm2Config", "build_lfm2"]

# the published pattern: attention at layers 2, 6, ..., 38
PUBLISHED_LAYER_TYPES = (["conv", "conv", "full_attention"]
                         + (["conv"] * 3 + ["full_attention"]) * 9 + ["conv"])


class Lfm2Config:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held: `layer_types`
    and `first_layer` (the kinds of the layers held and the published
    index of the first, which names the parameters), `dense_layers` of
    them leading with a dense feed-forward, `experts_held` of
    `num_experts` from `held_from` on, and `vocab_size` rows of the
    vocabulary."""

    score_func = "sigmoid"  # the router's; `decoder_parts.expert_ffn` reads it
    num_shared_experts = 0
    router_norm_eps = 1e-6  # beside the sum the selected scores are divided by

    def __init__(self, vocab_size=65536, hidden_size=2048, layer_types=None,
                 first_layer=0, dense_layers=2, num_attention_heads=32,
                 num_key_value_heads=8, head_dim=None, conv_L_cache=3,
                 rope_theta=1000000.0, intermediate_size=11776,
                 moe_intermediate_size=1536, num_experts=64,
                 experts_held=None, held_from=0, num_experts_per_token=4,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 norm_eps=1e-5, initializer_range=0.02,
                 router_bias_scale=0.0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = list(PUBLISHED_LAYER_TYPES if layer_types is None
                                else layer_types)
        self.first_layer = first_layer
        self.dense_layers = dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.conv_L_cache = conv_L_cache
        self.rope_theta = rope_theta
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = norm_topk_prob
        self.rms_norm_eps = norm_eps
        self.initializer_range = initializer_range
        self.router_bias_scale = router_bias_scale


def build_lfm2(cfg, batch_size, seq_len):
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
                         param_attr=attr("lfm2.embed", cfg))
    table = default_main_program().global_block().var("lfm2.embed")
    loads = []
    for at, kind in enumerate(cfg.layer_types):
        name = f"lfm2.layer{cfg.first_layer + at}"
        u = norm(x, name + ".operator_norm", cfg)
        if kind == "conv":
            mixed = gated_short_conv(u, cfg, name + ".conv")
        elif kind == "full_attention":
            mixed = attention(u, cfg, name + ".attn",
                              rope_theta=cfg.rope_theta)
        else:
            raise ValueError(f"lfm2: layer {cfg.first_layer + at} is of kind "
                             f"{kind!r}: expected 'conv' or 'full_attention'")
        x = layers.elementwise_add(x, mixed)
        u = norm(x, name + ".ffn_norm", cfg)
        if at < cfg.dense_layers:
            out = ffn(u, cfg.intermediate_size, name + ".mlp", cfg)
        else:
            out, load = expert_ffn(u, cfg, name, cfg.router_norm_eps)
            loads.append(load)
        x = layers.elementwise_add(x, out)
    logits = layers.matmul(norm(x, "lfm2.embedding_norm", cfg), table,
                           transpose_y=True)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    profiler.set_counter("gated_conv_layers", cfg.layer_types.count("conv"))
    profiler.set_counter("attention_layers",
                         cfg.layer_types.count("full_attention"))
    profiler.set_counter("expert_layers", len(loads))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
