"""Nemotron-H (NVIDIA-Nemotron-3-Super-120B-A12B, `model_type:
nemotron_h`; the model's public `config.json`, and the public
`modeling_nemotron_h.py` for what the config leaves open): a decoder whose
every block is one mixer behind one RMSNorm, the kind read off a pattern
string: `M` a Mamba-2 mixer, `E` an expert layer whose experts work in a
latent of the token and have no gate, `*` attention without positions.
Built through the layers API; a mixer may hold a share of its heads, the
expert layer a share of the experts, the vocabulary may be a slice and the
blocks a run of the published ones, which is how one chip of a layer
group sees the model.

The equations. `x` is `[s, hidden]`; no projection has a bias (the
convolution has one); no dropout. Block `l` counts from 0 as published.

  RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, w seeded 1
  x0 = E[tokens]                                              (no scale)
  x  = x + Mixer_l(RMS(x; w_l))           one mixer a block, of kind
                                          hybrid_override_pattern[l]
  M, Mamba-2 with H heads of P, a state of N, G groups (all as held):
    [z ; xBC ; dt] = W_in u       (hidden -> H P + (H P + 2 G N) + H)
    xBC = SiLU(conv(xBC) + b_conv)     causal, depthwise, conv_kernel
      taps, zero state before the row's start
    x [s, H, P], B [s, G, N], C [s, G, N] = split(xBC)
    Delta_t[h] = softplus(dt_t[h] + dt_bias[h]);  a[h] = -exp(A_log[h])
    head h reads group h // (H / G); state h [P, N], zero at the start:
      h_t = exp(Delta_t a) h_{t-1} + Delta_t x_t B_t^T
      y_t = h_t C_t + D x_t                                   float32
    g = y * SiLU(z);  o = w * g / sqrt(mean over the group's H P / G
      channels of g^2 + eps)                         the norm by groups
    out = W_out o
  *, attention, h query heads over g key/value heads of d (as held):
    q, k, v = W_q u, W_k u, W_v u;  no positions, no QK-norm
    a[i, n] = sum_{j <= i} softmax_j(q[i, n] . k[j, n // (h/g)] / sqrt(d))
              v[j, n // (h/g)];   out = W_o a
  E, the latent expert layer:
    s = sigmoid(W_r u) in float32;  sel = top-k(s + b)   (b untrained)
    w = scaling * s[sel] / (sum s[sel] + 1e-20)
    l = W_lat_in u                                  (hidden -> latent)
    r = sum over e in sel held here of w_e W_down^e relu(W_up^e l)^2
    out = W_lat_out r + W_down^s relu(W_up^s u)^2
  logits = W_head RMS(x; w_final)                               (untied)
  loss = mean over positions of the next-token cross-entropy, float32

Seeding: matrices Normal(0, `initializer_range`), and with
`rescale_prenorm_residual` the last product of every block (`W_out`,
`W_o`, `W_lat_out` and the shared expert's `W_down`) Normal(0,
`initializer_range` / sqrt(2 x `layers_published`)). The mixers are
`decoder_parts.mamba2_mixer` (op `ssd_scan`) and
`decoder_parts.attention`, the expert layer `decoder_parts.expert_ffn`.
`build_nemotron_h` sets three gauges: `mamba2_layers`, `attention_layers`
and `expert_layers`.
"""

from __future__ import annotations

import math

from .. import layers, profiler
from .decoder_parts import attention, attr, expert_ffn, mamba2_mixer, norm, proj

__all__ = ["NemotronHConfig", "build_nemotron_h"]

KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


class NemotronHConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held: the blocks of
    `hybrid_override_pattern` (published indices from `first_layer` on),
    `mamba_num_heads` heads in `mamba_n_groups` groups and
    `num_attention_heads` over `num_key_value_heads` as held here,
    `experts_held` of `num_experts` from `held_from` on, and `vocab_size`
    rows of the vocabulary."""

    score_func = "sigmoid"  # the router's; `decoder_parts.expert_ffn` reads it
    expert_form = "relu2"

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern="MEMEMEM*EME", first_layer=0,
                 layers_published=88, mamba_num_heads=128, mamba_head_dim=64,
                 mamba_n_groups=8, ssm_state_size=128, mamba_conv_kernel=4,
                 mamba_chunk_size=128, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376, num_experts=512,
                 experts_held=None, held_from=0, num_experts_per_token=22,
                 norm_topk_prob=True, routed_scaling_factor=5.0,
                 router_bias_scale=0.0, rms_norm_eps=1e-5,
                 initializer_range=0.02, rescale_prenorm_residual=True):
        unknown = set(hybrid_override_pattern) - set(KINDS)
        if unknown:
            raise ValueError(f"nemotron_h: hybrid_override_pattern has "
                             f"{sorted(unknown)}: a block is one of M, E, *")
        if moe_shared_expert_intermediate_size % moe_intermediate_size:
            raise ValueError(
                "nemotron_h: the shared expert's width "
                f"{moe_shared_expert_intermediate_size} is no multiple of an "
                f"expert's {moe_intermediate_size}")
        if mamba_num_heads % mamba_n_groups:
            raise ValueError(f"nemotron_h: {mamba_n_groups} groups do not "
                             f"divide {mamba_num_heads} Mamba-2 heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = hybrid_override_pattern
        self.first_layer = first_layer
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.mamba_n_groups = mamba_n_groups
        self.ssm_state_size = ssm_state_size
        self.mamba_conv_kernel = mamba_conv_kernel
        self.mamba_chunk_size = mamba_chunk_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_latent_size = moe_latent_size
        self.num_shared_experts = (moe_shared_expert_intermediate_size
                                   // moe_intermediate_size)
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.moe_renormalize = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.router_bias_scale = router_bias_scale
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        # the deviation of a block's last product at seeding
        self.out_std = (initializer_range / math.sqrt(2 * layers_published)
                        if rescale_prenorm_residual else None)

    def layer_kinds(self):
        """(published index, "mamba2", "experts" or "attention") of each
        block held."""
        return [(self.first_layer + i, KINDS[c])
                for i, c in enumerate(self.pattern)]


def build_nemotron_h(cfg, batch_size, seq_len):
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
                         param_attr=attr("nemotron.embed", cfg))
    loads = []
    kinds = cfg.layer_kinds()
    for l, kind in kinds:
        name = f"nemotron.layer{l}"
        u = norm(x, name + ".norm", cfg)
        if kind == "mamba2":
            mixed = mamba2_mixer(u, cfg, name + ".mamba", cfg.out_std)
        elif kind == "attention":
            mixed = attention(u, cfg, name + ".attn", qk_norm=False,
                              out_std=cfg.out_std)
        else:
            mixed, load = expert_ffn(u, cfg, name, norm_eps=1e-20,
                                     out_std=cfg.out_std)
            loads.append(load)
        x = layers.elementwise_add(x, mixed)
    logits = proj(norm(x, "nemotron.final_norm", cfg), cfg.vocab_size,
                  "nemotron.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    for kind, gauge in (("mamba2", "mamba2_layers"),
                        ("attention", "attention_layers"),
                        ("experts", "expert_layers")):
        profiler.set_counter(gauge, sum(k == kind for _, k in kinds))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
