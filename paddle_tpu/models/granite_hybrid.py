"""Granite 4.0-H (ibm-granite granite-4.0-h-micro, `model_type:
granitemoehybrid`; the model's public `config.json`, and the public
`modeling_granitemoehybrid.py` of `transformers` for what the config
leaves open): a dense decoder whose layers are a whole-width Mamba-2 mixer
nine times in ten and attention without positions every tenth, each
followed by a SiLU-gated feed-forward, under the family's four
multipliers: the embedding times 12, every sublayer added to the stream
times 0.22, the attention's scores times 1/64, the logits over 8, on one
table for embedding and head. Built through the layers API; the
vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of a pipeline stage sees the model.

The equations. `x` is `[s, hidden]`; no projection has a bias, the
convolution has one; no dropout. Layer `l` counts from 0 as published and
its kind is `layer_types[l]`.

  RMS(x; w) = w * x / sqrt(mean(x^2) + eps), the statistic in float32,
    w seeded 1
  x0 = embedding_multiplier * E[tokens]
  u  = RMS(x; w_in_l);   x = x + residual_multiplier * Mixer_l(u)
  u' = RMS(x; w_post_l); x = x + residual_multiplier * W_out (SiLU(g) * y),
    [g ; y] = W_in u'      hidden -> 2 x intermediate -> hidden, the gate
                           first, in every layer
  Mixer_l, mamba (H heads of P, G groups, a state of N):
    [z ; xBC ; dt] = W_in u           hidden -> H P + (H P + 2 G N) + H
    xBC = SiLU(conv(xBC) + b_conv)    causal, depthwise, mamba_d_conv
      taps, zero before the row's start
    x [s, H, P], B [s, G, N], C [s, G, N] = split(xBC)
    Delta = softplus(dt + dt_bias), a = -exp(A_log)    float32, no clamp
      (the class's time_step_limit is (0, inf))
    head h reads group h // (H / G); state h [P, N], zero at the start:
      h_t = exp(Delta_t a) h_{t-1} + Delta_t x_t B_t^T
      y_t = h_t C_t + D x_t
    g = y * SiLU(z);  o = w * g / sqrt(mean over a group's H P / G
      channels of g^2 + eps)          the gate, then the norm (one group
      as published: all H P channels)
    out = W_out o
  Mixer_l, attention (h query heads over g key/value heads of d):
    q, k, v = W_q u, W_k u, W_v u;  no positions
      (position_embedding_type "nope"), no QK-norm
    a[i, n] = sum_{j <= i} softmax_j(attention_multiplier *
              q[i, n] . k[j, n // (h/g)]) v[j, n // (h/g)]
    out = W_o a                       attention_multiplier, not d^-1/2
  logits = (E RMS(x; w_final)) / logits_scaling       tied: E is the
    embedding's table, so its gradient is the lookup's (times
    embedding_multiplier) plus the head's (over logits_scaling)
  loss = mean over positions of the next-token cross-entropy, float32

The mixers are `decoder_parts.mamba2_mixer` (op `ssd_scan`) and
`decoder_parts.attention` with `qk_norm=False` and `scale=
attention_multiplier`; the feed-forward is `decoder_parts.fused_ffn`; the
multipliers are `scale` ops and the head's `matmul`'s `alpha`.
`build_granite_hybrid` sets three gauges: `mamba2_layers`,
`attention_layers` and `dense_ffn_layers`.
"""

from __future__ import annotations

from .. import layers, profiler
from ..framework import default_main_program
from .decoder_parts import attention, attr, fused_ffn, mamba2_mixer, norm

__all__ = ["GraniteHybridConfig", "build_granite_hybrid"]

KINDS = ("mamba", "attention")


class GraniteHybridConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` published layers from `first_layer` on (their
    kinds read from `layer_types`, the published list whole), and
    `vocab_size` rows of the vocabulary."""

    def __init__(self, layer_types, vocab_size=100352, hidden_size=2048,
                 num_hidden_layers=40, first_layer=0,
                 num_attention_heads=32, num_key_value_heads=8, head_dim=64,
                 intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
                 mamba_n_groups=1, mamba_d_state=128, mamba_d_conv=4,
                 mamba_chunk_size=256, attention_multiplier=0.015625,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 logits_scaling=8.0, rms_norm_eps=1e-5,
                 initializer_range=0.02):
        held = list(layer_types[first_layer:first_layer + num_hidden_layers])
        if len(held) != num_hidden_layers or set(held) - set(KINDS):
            raise ValueError(
                f"granite_hybrid: layers {first_layer} to "
                f"{first_layer + num_hidden_layers - 1} of `layer_types` "
                f"are {held}: expected {num_hidden_layers} of {KINDS}")
        if mamba_n_heads % mamba_n_groups:
            raise ValueError(f"granite_hybrid: {mamba_n_groups} groups do "
                             f"not divide {mamba_n_heads} Mamba-2 heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_layer = first_layer
        self.layer_types = held
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.mamba_num_heads = mamba_n_heads
        self.mamba_head_dim = mamba_d_head
        self.mamba_n_groups = mamba_n_groups
        self.ssm_state_size = mamba_d_state
        self.mamba_conv_kernel = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range

    def layer_kinds(self):
        """(published index, kind) of each layer held."""
        return list(enumerate(self.layer_types, self.first_layer))


def build_granite_hybrid(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the mean next-token loss over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, and `loads` (empty: no expert layer)."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.scale(
        layers.embedding(tokens, (cfg.vocab_size, cfg.hidden_size),
                         param_attr=attr("granite.embed", cfg)),
        scale=cfg.embedding_multiplier)
    table = default_main_program().global_block().var("granite.embed")

    def added(x, sublayer):
        return layers.elementwise_add(
            x, layers.scale(sublayer, scale=cfg.residual_multiplier))

    kinds = cfg.layer_kinds()
    for l, kind in kinds:
        name = f"granite.layer{l}"
        u = norm(x, name + ".input_norm", cfg)
        if kind == "mamba":
            mixed = mamba2_mixer(u, cfg, name + ".mamba")
        else:
            mixed = attention(u, cfg, name + ".attn", qk_norm=False,
                              scale=cfg.attention_multiplier)
        x = added(x, mixed)
        x = added(x, fused_ffn(norm(x, name + ".post_norm", cfg),
                               cfg.intermediate_size, name + ".mlp", cfg))
    logits = layers.matmul(norm(x, "granite.final_norm", cfg), table,
                           transpose_y=True, alpha=1.0 / cfg.logits_scaling)
    # the loss from the logits as float32: the op hands its per-token
    # losses back in its input's type, and under bf16 AMP the head's
    # logits are bf16, whose neighbours near ln(vocabulary) lie 0.0625
    # apart; a cast after the op is too late wherever the compiler keeps
    # that rounding (XLA:CPU does, XLA:TPU folds the pair away)
    per_token = layers.softmax_with_cross_entropy(
        layers.cast(logits, "float32"),
        layers.reshape(labels, [batch_size, seq_len, 1]))
    loss = layers.mean(per_token)
    mamba = sum(kind == "mamba" for _, kind in kinds)
    profiler.set_counter("mamba2_layers", mamba)
    profiler.set_counter("attention_layers", len(kinds) - mamba)
    profiler.set_counter("dense_ffn_layers", len(kinds))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": []}
