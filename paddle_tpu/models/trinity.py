"""Trinity (arcee-ai Trinity-Mini, `model_type: afmoe`; the model's public
`config.json`, and the public `modeling_afmoe.py` of `transformers` for
what the config leaves open): a decoder whose layers attend through a
window of `sliding_window` keys, with a full layer every fourth, over
grouped key/value heads, each followed by a dense SiLU-gated feed-forward
(the leading layers) or by an expert layer. Built through the layers API;
the expert layer may hold a share of the experts, the vocabulary may be a
slice and the layers a run of the published ones, which is how one chip
of an expert-parallel job sees the model.

The equations. `x` is `[s, hidden]`, `h` query heads and `g` key/value
heads of `d`; no projection has a bias; every RMSNorm has a learned weight
and `rms_norm_eps`.

  x0 = E[tokens] * sqrt(hidden)                              (mup_enabled)
  u  = RMSNorm(x; w_in)
  q, k, v, gate = W_q u [s, h, d], W_k u [s, g, d], W_v u [s, g, d], W_g u [s, h*d]
  q, k = RMSNorm(q; w_qn), RMSNorm(k; w_kn) over d    (one weight of d for all heads)
  window layers only: q, k = RoPE(q, k; rope_theta, rotate-half,
    positions 0..s-1); a full layer has no positions
  a[i, n] = sum_j softmax_j(q[i, n] . k[j, n // (h/g)] / sqrt(d)) v[j, n // (h/g)]
    over j <= i, and on a window layer i - j < sliding_window
  m  = W_o (a * sigmoid(gate))
  x  = x + RMSNorm(m; w_post_attn)
  u2 = RMSNorm(x; w_pre_mlp)
  dense layer:  f = W_down(silu(W_gate u2) * W_up u2)
  expert layer: p = sigmoid(W_r u2) in float32; sel = top-k(p + b);
    w = route_scale * p[sel] / sum p[sel]  (route_norm);
    f = shared(u2) + sum over e in sel held here of w_e expert_e(u2)
  x  = x + RMSNorm(f; w_post_mlp)
  logits = W_head RMSNorm(x_last; w_final)
  loss = mean over positions of the next-token cross-entropy, float32

The router's correction `b` is persistable, seeded, and not trained (the
published model moves it by a load-balancing rule outside the gradient,
which this repo lacks). The expert layer is the op `moe_experts`, the
attention `fused_multihead_attention` with `window` and four key/value
heads, which also norms q and k and gives them their positions.
"""

from __future__ import annotations

import math

from .. import layers
from .decoder_parts import attention, attr, expert_ffn, ffn, norm, proj

__all__ = ["TrinityConfig", "build_trinity"]


class TrinityConfig:
    """The published `config.json`'s keys under the names
    `decoder_parts.expert_ffn` reads, and what says which share of the
    model is held: `layer_types` and `first_layer` (the kinds of the
    layers held and the published index of the first, which names the
    parameters), `dense_layers` of them leading with a dense
    feed-forward, `experts_held` of `num_experts` from `held_from` on, and
    `vocab_size` rows of the vocabulary."""

    score_func = "sigmoid"  # the router's; `decoder_parts.expert_ffn` reads it

    def __init__(self, vocab_size=200192, hidden_size=2048, layer_types=None,
                 first_layer=0, dense_layers=2, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, sliding_window=2048,
                 rope_theta=10000.0, intermediate_size=6144,
                 moe_intermediate_size=1024, num_experts=128,
                 experts_held=None, held_from=0, num_experts_per_token=8,
                 num_shared_experts=1, routed_scaling_factor=2.826,
                 moe_renormalize=True, mup_enabled=True, rms_norm_eps=1e-5,
                 initializer_range=0.02, router_bias_scale=0.0):
        if layer_types is None:  # three window layers, then a full one
            layer_types = (["sliding_attention"] * 3 + ["full_attention"]) * 8
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = list(layer_types)
        self.first_layer = first_layer
        self.dense_layers = dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = moe_renormalize
        self.mup_enabled = mup_enabled
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.router_bias_scale = router_bias_scale


def _attention(u, cfg, name, window):
    """`window` 0: a full layer, which has no positions."""
    return attention(u, cfg, name, window,
                     rope_theta=cfg.rope_theta if window else 0.0, gated=True)


def build_trinity(cfg, batch_size, seq_len):
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
                         param_attr=attr("trinity.embed", cfg))
    if cfg.mup_enabled:
        x = layers.scale(x, scale=math.sqrt(cfg.hidden_size))
    loads = []
    for at, kind in enumerate(cfg.layer_types):
        name = f"trinity.layer{cfg.first_layer + at}"
        window = cfg.sliding_window if kind == "sliding_attention" else 0
        mixed = _attention(norm(x, name + ".input_norm", cfg), cfg,
                           name + ".attn", window)
        x = layers.elementwise_add(x, norm(mixed, name + ".post_attn_norm", cfg))
        u = norm(x, name + ".pre_mlp_norm", cfg)
        if at < cfg.dense_layers:
            out = ffn(u, cfg.intermediate_size, name + ".mlp", cfg)
        else:
            out, load = expert_ffn(u, cfg, name)
            loads.append(load)
        x = layers.elementwise_add(x, norm(out, name + ".post_mlp_norm", cfg))
    logits = proj(norm(x, "trinity.final_norm", cfg), cfg.vocab_size,
                  "trinity.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
