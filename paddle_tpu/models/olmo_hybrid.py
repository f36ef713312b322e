"""Olmo-Hybrid (allenai Olmo-Hybrid-7B, `model_type: olmo_hybrid`; the
model's public `config.json`; for what the config leaves open the public
`modeling_olmo3.py` of `transformers`, the family's convention, and
`modeling_qwen3_next.py`, whose `linear_*` keys the config repeats, with
the public flash-linear-attention `GatedDeltaNet` for `allow_neg_eigval`):
a dense decoder whose mixers are Gated DeltaNet layers three times in four
and full attention every fourth, every sublayer's *output* normed and its
input not, and no positions anywhere. Built through the layers API; the
vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of a pipeline stage sees the model.

The equations. `x` is `[s, hidden]`; no projection has a bias; no dropout.
Layer `l` counts from 0 as published and its kind is `layer_types[l]`.

  RMS(x; w) = w * x / sqrt(mean(x^2) + eps), the statistic in float32,
    w seeded 1 (`Olmo3RMSNorm`)
  x0 = E[tokens]                                              (no scale)
  h = x + RMS(Mixer_l(x); w_post_attn_l)
  x = h + RMS(FFN_l(h); w_post_ffn_l)       the sublayer's output is
    normed, its input is not (`Olmo3DecoderLayer`)
  FFN(u) = W_down (SiLU(W_gate u) * W_up u)
  Mixer_l, linear_attention (Gated DeltaNet), H heads of d_k key lanes
  and d_v value lanes (96 and 192 as published):
    [q ; k ; v ; z] = W_qkvz u  (H d_k, H d_k, H d_v, H d_v)
    [b ; a] = W_ba u            (H each)
    [q ; k ; v] = SiLU(conv([q ; k ; v]))   causal, depthwise,
      linear_conv_kernel_dim taps, zero state before the row's start
    q, k -> [s, H, d_k], each L2-normalised over d_k;  v, z -> [s, H, d_v]
    beta_t[n] = 2 sigmoid(b_t[n])      (linear_allow_neg_eigval: beta in
      (0, 2), so that I - beta k k^T has an eigenvalue in (-1, 1) along k)
    g_t[n] = -exp(A_log[n]) * softplus(a_t[n] + dt_bias[n])     float32
    value head n reads key head n; state S in R^{d_k x d_v} from zero:
      S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = d_k^-1/2 S_t^T q_t
    y_t[n] = w_norm * (o_t[n] / sqrt(mean(o_t[n]^2) + eps)) * SiLU(z_t[n])
      (one weight of d_v shared by the heads)
    out = W_out y
  Mixer_l, full_attention, h heads of d over as many key/value heads:
    q = RMS(W_q u; w_q), k = RMS(W_k u; w_k) over all h d lanes of the
      projection (one weight and one statistic for all the heads:
      `Olmo3Attention`'s `q_norm` of `num_attention_heads * head_dim`)
    **no rotary positions** (`rope_theta` null): the attention layers see
      order only through what the Gated DeltaNet layers before them wrote
      into the stream
    a[i, n] = sum_{j <= i} softmax_j(q[i, n] . k[j, n] / sqrt(d)) v[j, n]
    out = W_o a
  logits = W_head RMS(x; w_final)                               (untied)
  loss = mean over positions of the next-token cross-entropy, float32

The published checkpoints' linear layers have q, k, v, z, b and a as
projections of their own and a convolution each for q, k and v; with
seeded weights any fixed order of columns is the same model, and here they
lie side by side as Qwen3-Next's do (`decoder_parts.gated_delta_net`: two
products and one convolution over the 2 H d_k + H d_v channels). The
mixers are `decoder_parts.gated_delta_net` with `linear_beta_scale` 2 and
`decoder_parts.attention` with `qk_norm="projection"`. `build_olmo_hybrid`
sets five gauges: `gated_delta_layers`, `attention_layers`,
`delta_rule_key_lanes`, `delta_rule_value_lanes`, `delta_rule_beta_scale`;
`gated_delta_net` counts `delta_rule_lanes_published` and
`delta_rule_lanes_computed` once a layer built. The embedding may be
seeded wider than the matrices (`embedding_initializer_range`): no norm
stands between it and the first mixer, and every sublayer after it adds a
contribution of unit scale.
"""

from __future__ import annotations

from .. import layers, profiler
from .decoder_parts import attention, attr, ffn, gated_delta_net, norm, proj

__all__ = ["OlmoHybridConfig", "build_olmo_hybrid"]

KINDS = ("linear_attention", "full_attention")


class OlmoHybridConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` published layers from `first_layer` on (their
    kinds read from `layer_types`, the published list whole), and
    `vocab_size` rows of the vocabulary. `embedding_initializer_range`:
    the embedding's seeding where it is not `initializer_range` (every
    sublayer's output norm puts it at unit scale beside the embedding,
    whatever the matrices' scale)."""

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 num_hidden_layers=32, first_layer=0, layer_types=None,
                 num_attention_heads=30, num_key_value_heads=30, head_dim=128,
                 intermediate_size=11008, linear_num_key_heads=30,
                 linear_num_value_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
                 initializer_range=0.02, embedding_initializer_range=None,
                 l2norm_epsilon=1e-6):
        if layer_types is None:
            layer_types = (["linear_attention"] * 3 + ["full_attention"]) * (
                -(-(first_layer + num_hidden_layers) // 4))
        held = list(layer_types[first_layer:first_layer + num_hidden_layers])
        if len(held) != num_hidden_layers or set(held) - set(KINDS):
            raise ValueError(
                f"olmo_hybrid: layers {first_layer} to "
                f"{first_layer + num_hidden_layers - 1} of `layer_types` "
                f"are {held}: expected {num_hidden_layers} of {KINDS}")
        if linear_num_key_heads != linear_num_value_heads:
            raise ValueError("olmo_hybrid: a value head reads the key head "
                             "of its own number")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_layer = first_layer
        self.layer_types = held
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_beta_scale = 2.0 if linear_allow_neg_eigval else 1.0
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)
        self.l2norm_epsilon = l2norm_epsilon

    def layer_kinds(self):
        """(published index, kind) of each layer held."""
        return list(enumerate(self.layer_types, self.first_layer))


def build_olmo_hybrid(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the mean next-token loss over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, and `loads` (empty: no expert layer)."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(
        tokens, (cfg.vocab_size, cfg.hidden_size),
        param_attr=attr("olmohybrid.embed", cfg,
                        cfg.embedding_initializer_range))
    kinds = cfg.layer_kinds()
    for l, kind in kinds:
        name = f"olmohybrid.layer{l}"
        if kind == "linear_attention":
            mixed = gated_delta_net(x, cfg, name + ".gdn")
        else:
            mixed = attention(x, cfg, name + ".attn", qk_norm="projection")
        x = layers.elementwise_add(x, norm(mixed, name + ".post_attn_norm",
                                           cfg))
        fed = ffn(x, cfg.intermediate_size, name + ".mlp", cfg)
        x = layers.elementwise_add(x, norm(fed, name + ".post_ffn_norm", cfg))
    logits = proj(norm(x, "olmohybrid.final_norm", cfg), cfg.vocab_size,
                  "olmohybrid.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    linear = sum(kind == "linear_attention" for _, kind in kinds)
    profiler.set_counter("gated_delta_layers", linear)
    profiler.set_counter("attention_layers", len(kinds) - linear)
    profiler.set_counter("delta_rule_key_lanes", cfg.linear_key_head_dim)
    profiler.set_counter("delta_rule_value_lanes", cfg.linear_value_head_dim)
    profiler.set_counter("delta_rule_beta_scale", cfg.linear_beta_scale)
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": []}
