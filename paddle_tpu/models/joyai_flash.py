"""JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash, `model_type:
joyai_llm_flash`; the model's public `config.json`, whose keys are the
DeepSeek-V3 family's, arXiv:2412.19437 sections 2.1 and 2.2): a decoder
with latent attention in every layer (a compressed query, decoupled rotary
positions), one leading dense layer and then expert layers (sigmoid
router with a correction, one shared expert), and a multi-token-prediction
module of depth 1 that shares the embedding and the head with the main
model. Built through the layers API; the expert layers may hold a share
of the experts, the vocabulary may be a slice and the layers a run of the
leading ones, which is how one chip of an expert-parallel job sees the
model.

The equations. `x` is `[s, 2048]`, h = 32; no projection has a bias;
every RMSNorm has a learned weight and eps 1e-6; layers count from 0 as
the config counts them.

  x0 = E[tokens]                                   (no embedding scale)
  layer l, u = RMSNorm(x; w_in):
    c_q = RMSNorm(W_qa u; w_qa)                              [s,1536]
    q   = W_qb c_q                    [s,32,192] = [q^N (128) ; q^R (64)]
    [c_kv ; k^R] = W_kva u            [s,512] ; [s,64], k^R one for all heads
    [k^N ; v]    = W_kvb RMSNorm(c_kv; w_kva)   [s,32,128] ; [s,32,128]
    q^R, k^R = RoPE(q^R), RoPE(k^R): lanes (2i, 2i+1), i = 0..31, turned
      by p * theta^(-2i/64), theta 32e6, p = 0..s-1, float32
      (`rope_interleave`: true; no scaling)
    k^h = [k^{N,h} ; k^R]
    a[i,h] = sum_{j<=i} softmax_j(q[i,h] . k[j,h] / sqrt(192)) v[j,h]
    x  = x + W_o a                                           (4096 -> 2048)
    u2 = RMSNorm(x; w_post)
    l = 0:  x = x + W_down(silu(W_gate u2) * W_up u2), width 7168
    l >= 1: sc = sigmoid(W_r u2) over 256, float32; sel = top-8(sc + b);
            w = 2.5 * sc[sel] / sum sc[sel]
            x = x + Shared(u2) + sum over e in sel held here of
                w_e Expert_e(u2), widths 768
  h = x after the last layer held
  main: logits_i  = W_head RMSNorm(h_i; w_final)
        L_main = mean_i CE(logits_i, t_{i+1})
  MTP, depth 1:  e_i = E[t_{i+1}]                            (the same E)
        h'_i  = M [RMSNorm(h_i; w_hn) ; RMSNorm(e_i; w_en)]   M: 4096 -> 2048
        h''   = Block(h')   one expert layer as l >= 1, its own weights,
                            causal over i
        logits'_i = W_head RMSNorm(h''_i; w_mtp)             (the same head)
        L_mtp = mean_i CE(logits'_i, t_{i+2})
  loss = L_main + lambda * L_mtp, float32

`b` is the router's correction, persistable and never trained; with
`n_group` 1 and `topk_group` 1 the grouped selection is the plain one. No
balancing term is added to the loss. What the config leaves open (the
module's order `[h ; e]`, its norms, `lambda`) is listed under `assumed` in
the benchmark's configuration file.

Latent attention is `decoder_parts.latent_attention`, the expert layer
`decoder_parts.expert_ffn` as Kimi Linear builds it. The embedding and the
head are each one parameter read twice in the Program (by `tokens` and by
`labels`; by both heads), so each receives the sum of two gradients from
the backward pass (`backward._accumulate`'s `sum`); the startup program
seeds such a parameter once for each use and the last draw stands, as for
the transformer's shared table.

Counters a lowering leaves at trace time, beside the ones the other
expert decoders leave: `attn_latent_q_lora` (one an attention lowering
whose query was compressed) and `rope_interleaved` (one a
`rotary_embedding` lowering by pairs of neighbouring lanes). The gradient
op replays the forward lowering, so one train step's trace of the five
layers and the module counts `attn_latent_q_lora` 12, `rope_interleaved`
24 (q^R and k^R, six blocks, twice), `attn_dispatch_flash` 12 on the chip
(`attn_dispatch_xla` on the CPU) and `moe_dispatch_grouped` 10 (five
expert layers). The builder sets two gauges when it declares the module,
which is no op of its own: `mtp_depth` (1) and `loss_terms` (2).
"""

from __future__ import annotations

from .. import layers, profiler
from ..initializer import Normal
from ..param_attr import ParamAttr
from .decoder_parts import expert_ffn, ffn, latent_attention, norm, proj

__all__ = ["JoyAIFlashConfig", "build_joyai_flash"]


class JoyAIFlashConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` leading layers, `experts_held` of `num_experts`
    from `held_from` on, and `vocab_size` rows of the vocabulary.
    `mtp_loss_weight` is the equations' lambda.
    `embedding_initializer_range` seeds the embedding apart from the
    matrices (`initializer_range`, the default), as `MellumConfig` does
    and for its reason: with seeded weights it decides whether a token's
    own row or what attention averaged over the row's tokens leads the
    residual stream, and so whether the router tells tokens apart."""

    score_func = "sigmoid"  # `scoring_func`

    def __init__(self, vocab_size=129280, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=32000000.0,
                 rope_interleave=True, intermediate_size=7168,
                 moe_intermediate_size=768, num_experts=256,
                 experts_held=None, held_from=0, num_experts_per_token=8,
                 num_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 num_nextn_predict_layers=1, mtp_loss_weight=0.3,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 embedding_initializer_range=None, router_bias_scale=0.0):
        if num_nextn_predict_layers != 1:
            raise ValueError("num_nextn_predict_layers: a module of depth 1 "
                             f"is built, not {num_nextn_predict_layers}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_interleave = rope_interleave
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = norm_topk_prob
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.mtp_loss_weight = mtp_loss_weight
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)
        self.router_bias_scale = router_bias_scale


def _block(x, cfg, name, dense, loads):
    """One layer: latent attention, then the dense feed-forward or the
    expert layer, whose load joins `loads`."""
    x = layers.elementwise_add(
        x, latent_attention(norm(x, name + ".input_norm", cfg), cfg,
                            name + ".attn"))
    u = norm(x, name + ".post_attn_norm", cfg)
    if dense:
        out = ffn(u, cfg.intermediate_size, name + ".mlp", cfg)
    else:
        out, load = expert_ffn(u, cfg, name)
        loads.append(load)
    return layers.elementwise_add(x, out)


def _mean_nll(logits, labels):
    b, s = labels.shape
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [b, s, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    return layers.mean(layers.cast(per_token, "float32"))


def build_joyai_flash(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` (t_i), `labels` (t_{i+1}) and
    `labels_mtp` (t_{i+2}) ([b, s] int64, ids in the slice of the
    vocabulary held) and the loss of the equations over every position,
    float32. Returns a dict of handles: `feeds`, `logits` and `mtp_logits`
    ([b, s, vocab_size] each), `loss`, its two terms `main_loss` and
    `mtp_loss`, and `loads`, one `[experts_held]` int32 var for each expert
    layer, the module's last."""
    def ids(name):
        return layers.data(name, [batch_size, seq_len], dtype="int64",
                           append_batch_size=False)

    def embed(t):
        return layers.embedding(
            t, (cfg.vocab_size, cfg.hidden_size),
            param_attr=ParamAttr(name="joyai.embed", initializer=Normal(
                0.0, cfg.embedding_initializer_range)))

    def head(h, final_norm):
        return proj(norm(h, final_norm, cfg), cfg.vocab_size, "joyai.head",
                    cfg)

    tokens, labels, labels_mtp = ids("tokens"), ids("labels"), ids("labels_mtp")
    x = embed(tokens)
    loads = []
    for i in range(cfg.num_hidden_layers):
        x = _block(x, cfg, f"joyai.layer{i}", i < cfg.first_k_dense_replace,
                   loads)
    logits = head(x, "joyai.final_norm")

    joined = layers.concat([norm(x, "joyai.mtp.hnorm", cfg),
                            norm(embed(labels), "joyai.mtp.enorm", cfg)],
                           axis=2)
    h = _block(proj(joined, cfg.hidden_size, "joyai.mtp.proj", cfg), cfg,
               "joyai.mtp", False, loads)
    mtp_logits = head(h, "joyai.mtp.final_norm")

    main_loss = _mean_nll(logits, labels)
    mtp_loss = _mean_nll(mtp_logits, labels_mtp)
    loss = layers.elementwise_add(
        main_loss, layers.scale(mtp_loss, scale=cfg.mtp_loss_weight))
    profiler.set_counter("mtp_depth", cfg.num_nextn_predict_layers)
    profiler.set_counter("loss_terms", 2)
    return {"feeds": ["tokens", "labels", "labels_mtp"], "logits": logits,
            "mtp_logits": mtp_logits, "loss": loss, "main_loss": main_loss,
            "mtp_loss": mtp_loss, "loads": loads}
