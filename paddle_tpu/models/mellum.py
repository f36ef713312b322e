"""Mellum 2 (JetBrains Mellum2-12B-A2.5B, `model_type: mellum`, the key
set of Qwen3-MoE; the model's public `config.json`): a decoder whose
layers attend through a window of `sliding_window` keys, with a full layer
every fourth, over grouped key/value heads, every layer followed by an
expert layer with a softmax router and no shared expert. Positions are
rotary on every layer; the full layers' tables are scaled by YaRN. Built
through the layers API; the expert layer may hold a share of the experts,
the vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of an expert-parallel host sees the model.

The equations. `x` is `[s, 2304]`, h=32 query heads over g=4 key/value
heads of d=128; no projection has a bias; every RMSNorm has a learned
weight and eps 1e-6.

  x0 = E[tokens]                                   (no embedding scale)
  u  = RMSNorm(x; w_in)
  q, k, v = W_q u [s,32,128], W_k u [s,4,128], W_v u [s,4,128]
  q, k = RMSNorm(q; w_qn), RMSNorm(k; w_kn) over d (assumed: the
    configuration file says why)
  q, k = RoPE(q, k; tables of the layer's kind, rotate-half, positions 0..s-1)
    window layer: f_i = theta^(-2i/d), theta 500000; cos(p f_i), sin(p f_i)
    full layer (YaRN): e_i = theta^(-2i/d); n_i = e_i / 16
      c(r) = d ln(8192 / (2 pi r)) / (2 ln theta)
      low = max(floor(c(32)), 0); high = min(ceil(c(1)), d - 1)
      ramp_i = clip((i - low) / (high - low), 0, 1), i = 0..d/2-1
      f_i = n_i ramp_i + e_i (1 - ramp_i)
      cos(p f_i) * A, sin(p f_i) * A, A = 1.2772588722239782
  a[i,n] = sum_j softmax_j(q[i,n] . k[j,n//8] / sqrt(d)) v[j,n//8],
    j <= i, and on a window layer i - j < 1024
  x  = x + W_o a
  u2 = RMSNorm(x; w_post)
  p  = softmax(W_r u2) over 64, float32; sel = top-8(p); w = p[sel] / sum p[sel]
  x  = x + sum over e in sel held here of
         w_e W_down_e(silu(W_gate_e u2) * W_up_e u2)
  logits = W_head RMSNorm(x_last; w_final)
  loss = mean next-token cross-entropy, float32

The router has no correction: the op's `Bias` input is zeros, seeded and
never trained. No balancing term is added to the loss, and the "MTP head"
some descriptions mention is in no key of the config: left out. The
expert layer is the op `moe_experts` with `score_func` "softmax", the
attention `fused_multihead_attention` through `decoder_parts.attention`
(no gate), which norms q and k and gives them their positions.

Counters a lowering leaves at trace time, beside the ones the other
expert decoders leave: `moe_route_softmax` (one an expert-layer
lowering), the gauge `moe_block_rows` (the first block of the layer's
sorted assignments: 28,672 of 65,536 at 16 of 64 experts and 8,192
tokens), `attn_rope_scaled` (one an attention lowering whose tables are
scaled: the full layer's).
"""

from __future__ import annotations

from .. import layers
from ..initializer import Normal
from ..param_attr import ParamAttr
from .decoder_parts import attention, expert_ffn, norm, proj

__all__ = ["MellumConfig", "build_mellum"]


class MellumConfig:
    """The published `config.json`'s keys under the names
    `decoder_parts` reads, and what says which share of the model is
    held: `layer_types` and `first_layer` (the kinds of the layers held
    and the published index of the first, which names the parameters),
    `experts_held` of `num_experts` from `held_from` on, and `vocab_size`
    rows of the vocabulary. `rope_parameters` is the published group: one
    entry a kind of layer, each with its `rope_theta`.
    `embedding_initializer_range` seeds the embedding apart from the
    matrices (`initializer_range`, the default): with seeded weights it
    decides whether a token's own row or what attention averaged over
    the row's tokens leads the residual stream, and so whether the
    router tells tokens apart."""

    score_func = "softmax"
    routed_scaling_factor = 1.0
    num_shared_experts = 0
    router_bias_scale = 0.0  # no correction: the op's Bias stays zeros

    def __init__(self, vocab_size=98304, hidden_size=2304, layer_types=None,
                 first_layer=0, num_attention_heads=32, num_key_value_heads=4,
                 head_dim=128, sliding_window=1024, rope_parameters=None,
                 moe_intermediate_size=896, num_experts=64, experts_held=None,
                 held_from=0, num_experts_per_token=8, norm_topk_prob=True,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 embedding_initializer_range=None):
        if layer_types is None:  # three window layers, then a full one
            layer_types = (["sliding_attention"] * 3 + ["full_attention"]) * 7
        if rope_parameters is None:
            rope_parameters = {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                    "original_max_position_embeddings": 8192,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.2772588722239782},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 500000}}
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = list(layer_types)
        self.first_layer = first_layer
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.moe_renormalize = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)


def build_mellum(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the mean next-token loss over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, and `loads`, one `[experts_held]` int32
    var for each layer."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(
        tokens, (cfg.vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="mellum.embed", initializer=Normal(
            0.0, cfg.embedding_initializer_range)))
    loads = []
    for at, kind in enumerate(cfg.layer_types):
        name = f"mellum.layer{cfg.first_layer + at}"
        rope = cfg.rope_parameters[kind]
        mixed = attention(
            norm(x, name + ".input_norm", cfg), cfg, name + ".attn",
            window=cfg.sliding_window if kind == "sliding_attention" else 0,
            rope_theta=rope["rope_theta"], rope_scaling=rope)
        x = layers.elementwise_add(x, mixed)
        out, load = expert_ffn(norm(x, name + ".post_attn_norm", cfg), cfg,
                               name)
        loads.append(load)
        x = layers.elementwise_add(x, out)
    logits = proj(norm(x, "mellum.final_norm", cfg), cfg.vocab_size,
                  "mellum.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
