"""Kimi Linear (Kimi-Linear-48B-A3B; Kimi Team 2025, arXiv:2510.26692, and
the model's public `config.json`): a decoder whose layers alternate Kimi
Delta Attention (KDA, three layers) with latent attention that has no
positional encoding (one layer), each followed by a dense SiLU-gated
feed-forward (the leading layers) or by an expert layer. Built through the
layers API; the expert layer may hold a share of the experts and the
vocabulary may be a slice, which is how one chip of an expert-parallel job
sees the model.

The equations. `x` is a token of the residual stream, `u = RMSNorm(x)`
what a mixer or a feed-forward reads; no projection has a bias; RMSNorm
has a learned weight and `rms_norm_eps`.

Block:  `x = x + Mixer(RMSNorm(x))`; `x = x + FFN(RMSNorm(x))`. After the
last layer a final RMSNorm and `logits = W_head x`. Layers count from 1, as
`linear_attn_config` counts them.

KDA mixer, `h` heads of `d` (= `d_k` = `d_v`):
  `q~, k~, v~ = W_q u, W_k u, W_v u`.
  Short convolution, per channel, causal, zero state at the start of a
  sequence: `c_t = sum_{i=0..w-1} f[:, i] * a_{t-w+1+i}`, then SiLU.
  `q_t^h, k_t^h = L2Norm(SiLU(conv(.)))` over the head's `d`;
  `v_t^h = SiLU(conv(v~))`.
  Decay, per channel, float32:
  `g_t = -exp(A_log^h) * softplus(W_fb W_fa u + dt_bias)`, `alpha_t = exp(g_t)`.
  `beta_t^h = sigmoid(w_b^h . u)`.
  State `S^h` in R^{d x d}, zero at the start of a sequence:
  `S' = Diag(alpha_t) S_{t-1}`; `S_t = S' + beta_t k_t (v_t - S'^T k_t)^T`;
  `o_t = d^{-1/2} S_t^T q_t`.
  `y = W_o( sigmoid(W_gb W_ga u) * RMSNorm_head(o_t) )`, the norm over each
  head's `d` with one learned `[d]` weight.
  The op `kda_attention` computes the recurrence chunk by chunk
  (ops/linear_attn_ops.py).

Latent mixer (`decoder_parts.latent_attention`), without positions
(`mla_use_nope`) and with one query projection (`q_lora_rank` null):
  `q_t = W_q u` as `h` heads of `d_n + d_r`; `[c_t, k^R_t] = W_kva u`
  (`kv_lora_rank` + `d_r`); `[k^N_t, v_t] = W_kvb RMSNorm(c_t)` as `h` heads
  of `d_n + d_v`; `k_t^h = [k^{N,h}_t ; k^R_t]`, the `d_r`-wide part shared
  by all heads and not rotated; causal softmax of
  `q . k / sqrt(d_n + d_r)`; `y = W_o o`, `o` of `h x d_v`.

Feed-forward: `FFN(u) = W_down(SiLU(W_gate u) * W_up u)`.

Expert layer: `s = sigmoid(W_r u)` over all the experts, float32; the
`num_experts_per_token` largest of `s + b` are selected (`b`: the router's
correction, persistable and untrained); `w_i = routed_scaling_factor *
s_i / sum_selected s_j`;
`y = Shared(u) + sum_{i selected and held here} w_i Expert_i(u)`, experts
and the shared expert FFNs of `moe_intermediate_size`. Every assignment to
a held expert is computed (op `moe_experts`).
"""

from __future__ import annotations

from .. import layers
from ..initializer import Normal
from ..param_attr import ParamAttr
from .decoder_parts import (attr as _attr, expert_ffn as _expert_ffn,
                            ffn as _ffn, latent_attention as _latent_mixer,
                            norm as _norm, proj as _proj)

__all__ = ["KimiLinearConfig", "build_kimi_linear"]


class KimiLinearConfig:
    """The published `config.json`'s keys, and three that say which share
    of the model is held: `experts_held` of `num_experts` from `held_from`
    on, and `vocab_size` rows of the vocabulary."""

    score_func = "sigmoid"  # the router's; `decoder_parts.expert_ffn` reads it
    # what `decoder_parts.latent_attention` reads beside the widths: one
    # query projection (`q_lora_rank` null) and no positions (`mla_use_nope`)
    q_lora_rank = None
    rope_theta = 0.0
    rope_interleave = False

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, kda_layers=None, num_heads=32,
                 kda_head_dim=128, short_conv_kernel_size=4, kda_rank=None,
                 num_attention_heads=32, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_experts=256, experts_held=None, held_from=0,
                 num_experts_per_token=8, num_shared_experts=1,
                 first_k_dense_replace=1, routed_scaling_factor=2.446,
                 moe_renormalize=True, rms_norm_eps=1e-5,
                 initializer_range=0.02, router_bias_scale=0.0,
                 l2norm_epsilon=1e-6):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        if kda_layers is None:  # three KDA layers, then one latent layer
            kda_layers = [i for i in range(1, num_hidden_layers + 1) if i % 4]
        self.kda_layers = set(kda_layers)
        self.num_heads = num_heads
        self.kda_head_dim = kda_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_rank = kda_rank or kda_head_dim
        self.num_attention_heads = num_attention_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = moe_renormalize
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.router_bias_scale = router_bias_scale
        self.l2norm_epsilon = l2norm_epsilon


def _kda_mixer(u, cfg, name):
    b, s, _ = u.shape
    h, d = cfg.num_heads, cfg.kda_head_dim

    def conv(t, which):
        return layers.short_conv1d(
            t, width=cfg.short_conv_kernel_size,
            param_attr=ParamAttr(name=f"{name}.{which}_conv.w_0",
                                 initializer=Normal(0.0, 0.5)))

    q, k, v = (conv(_proj(u, h * d, f"{name}.{t}", cfg), t) for t in "qkv")
    g = _proj(_proj(u, cfg.kda_rank, name + ".f_a", cfg), h * d,
              name + ".f_b", cfg)
    beta = _proj(u, h, name + ".b", cfg)
    o = layers.kda_attention(
        q, k, v, g, beta, num_heads=h, l2norm_epsilon=cfg.l2norm_epsilon,
        a_log_attr=ParamAttr(name=name + ".A_log"),
        dt_bias_attr=ParamAttr(name=name + ".dt_bias"))
    o = _norm(layers.reshape(o, [b, s, h, d]), name + ".o_norm", cfg, axis=3)
    gate = layers.sigmoid(
        _proj(_proj(u, cfg.kda_rank, name + ".g_a", cfg), h * d,
              name + ".g_b", cfg))
    o = layers.elementwise_mul(layers.reshape(o, [b, s, h * d]), gate)
    return _proj(o, cfg.hidden_size, name + ".o", cfg)


def build_kimi_linear(cfg, batch_size, seq_len):
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
                         param_attr=_attr("kimi.embed", cfg))
    loads = []
    for i in range(1, cfg.num_hidden_layers + 1):
        name = f"kimi.layer{i}"
        u = _norm(x, name + ".attn_norm", cfg)
        mixer = (_kda_mixer(u, cfg, name + ".kda") if i in cfg.kda_layers
                 else _latent_mixer(u, cfg, name + ".mla"))
        x = layers.elementwise_add(x, mixer)
        u = _norm(x, name + ".ffn_norm", cfg)
        if i <= cfg.first_k_dense_replace:
            ffn = _ffn(u, cfg.intermediate_size, name + ".mlp", cfg)
        else:
            ffn, load = _expert_ffn(u, cfg, name)
            loads.append(load)
        x = layers.elementwise_add(x, ffn)
    logits = _proj(_norm(x, "kimi.final_norm", cfg), cfg.vocab_size,
                   "kimi.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": loads}
