"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, `model_type: KeyeVL2`;
the model's public `config.json`): a decoder of grouped-query attention
layers, each followed by an expert layer with a softmax router and no
shared expert, in which every attention layer reads, for each query, only
the `sa_config.topk` keys that a small learned indexer scores highest
(DeepSeek-V3.2-Exp's sparse attention, arXiv:2512.02556), and the indexer
is trained by a loss of its own beside the language-model loss. Built
through the layers API; the expert layer may hold a share of the experts,
the vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of an expert-parallel group sees the model. The
vision tower is not built: text rows only, on which the three position
channels of `mrope_section` all count 0..s-1 and the rotation is the plain
rotate-half one.

The equations of layer l. `h` is `[s, 2048]`; N an RMSNorm (learned
weight, eps 1e-6), LN a LayerNorm with weight and bias, sg a
stop-gradient, R the rotate-half rotation at theta 1e7 by positions
0..s-1 over all of a head's lanes, K = 2,048, H_I = 16, d_I = 64.

  a      = N1(h)
  q      = R(Nq(W_q a)) [32 x 128]   k = R(Nk(W_k a)) [4 x 128]
  v      = W_v a [4 x 128]                          g(head) = head // 8
  qI     = R(W_qI sg(a)) [16 x 64]   kI = R(LN(W_kI sg(a))) [1 x 64]
  w      = W_w sg(a) [16]
  I[t,s] = sum_j w[t,j] H_I^-1/2 d_I^-1/2 relu(qI[t,j] . kI[s]), s <= t,
           float32
  tau[t] = the K-th largest of I[t, 0..t]  (-inf where t < K)
  S_t    = {s <= t : I[t,s] >= tau[t]}                    (no gradient)
  A[t,head,s] = softmax over s in S_t of q[t,head] . k[s,g(head)] / sqrt(128)
  o[t,head]   = sum over s in S_t of A[t,head,s] v[s,g(head)]
  h      = h + W_o o
  p[t,s] = sg((1/32) sum_head A[t,head,s])
  L_I^l  = mean over t of sum over s in S_t of
             p[t,s] (log p[t,s] - log softmax_{s' in S_t}(I[t,s'])[s])
  u2     = N2(h)
  r      = softmax(W_r u2) over 128, float32; sel = top-8(r)
  h      = h + sum over e in sel held here of
             (r_e / sum r[sel]) W_down_e(silu(W_gate_e u2) * W_up_e u2)
  loss   = mean CE(W_head N_f(h_L), label) + lambda_I sum_l L_I^l

The indexer's parameters (W_qI, W_kI, LN's pair, W_w) receive L_I's
gradient and nothing of the language-model loss, whose only path to them
is the selection; every other parameter receives the language-model
loss's and nothing of L_I. Both follow from the two stop-gradients and
are what one `minimize` of `loss` gives.

The attention is `decoder_parts.sparse_attention` (the ops `sparse_index`,
`sparse_select`, `fused_multihead_attention` with an admission,
`index_kl`), the expert layer the op `moe_experts` with `score_func`
"softmax". The builder sets the gauge `loss_terms` (2); a build bumps
`sparse_attn_layers`, `attn_pairs_admitted` and `attn_pairs_causal` once a
layer, and the lowerings set `sparse_attn_topk` and `sparse_index_heads`.
"""

from __future__ import annotations

from .. import layers, profiler
from ..initializer import Normal
from ..param_attr import ParamAttr
from .decoder_parts import expert_ffn, norm, proj, sparse_attention

__all__ = ["KeyeVL2Config", "build_keye_vl2"]


class KeyeVL2Config:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` layers from the published `first_layer` on,
    `experts_held` of `num_experts` from `held_from` on, and `vocab_size`
    rows of the vocabulary. `sa_config` is the published group
    (`indexer_num_heads`, `indexer_head_dim`, `indexer_num_kv_heads`,
    `topk`; the two chunk sizes are a kernel's tiling and change no
    equation). `index_loss_weight` is the equations' lambda_I;
    `layer_norm_eps` the indexer's LayerNorm's. `mrope_section` is kept
    and not read: text rows count one position on all three channels.
    `embedding_initializer_range` as `MellumConfig`'s, for its reason."""

    score_func = "softmax"
    routed_scaling_factor = 1.0
    num_shared_experts = 0
    router_bias_scale = 0.0  # no correction: the op's Bias stays zeros

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, first_layer=0, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, rope_theta=10000000.0,
                 mrope_section=(16, 24, 24), sa_config=None,
                 moe_intermediate_size=768, num_experts=128,
                 experts_held=None, held_from=0, num_experts_per_token=8,
                 norm_topk_prob=True, rms_norm_eps=1e-6, layer_norm_eps=1e-6,
                 index_loss_weight=1.0, initializer_range=0.02,
                 embedding_initializer_range=None):
        sa = {"indexer_head_dim": 64, "indexer_num_heads": 16,
              "indexer_num_kv_heads": 1, "topk": 2048, **(sa_config or {})}
        if sa["indexer_num_kv_heads"] != 1:
            raise ValueError("sa_config: one indexer key head is built, not "
                             f"{sa['indexer_num_kv_heads']}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_layer = first_layer
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.mrope_section = tuple(mrope_section)
        self.indexer_num_heads = sa["indexer_num_heads"]
        self.indexer_head_dim = sa["indexer_head_dim"]
        self.topk = sa["topk"]
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.moe_renormalize = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.layer_norm_eps = layer_norm_eps
        self.index_loss_weight = index_loss_weight
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)


def build_keye_vl2(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the loss of the equations over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, its two terms `lm_loss` and
    `index_loss` (the sum of the layers' L_I, before lambda_I), `loads`,
    one `[experts_held]` int32 var for each layer, and `admits`, each
    layer's selection ([b, s, s] int8)."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(
        tokens, (cfg.vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="keye.embed", initializer=Normal(
            0.0, cfg.embedding_initializer_range)))
    loads, index_losses, admits = [], [], []
    for at in range(cfg.num_hidden_layers):
        name = f"keye.layer{cfg.first_layer + at}"
        mixed, kl, admit = sparse_attention(
            norm(x, name + ".input_norm", cfg), cfg, name + ".attn",
            cfg.rope_theta)
        index_losses.append(layers.mean(kl))
        admits.append(admit)
        x = layers.elementwise_add(x, mixed)
        out, load = expert_ffn(norm(x, name + ".post_attn_norm", cfg), cfg,
                               name)
        loads.append(load)
        x = layers.elementwise_add(x, out)
    logits = proj(norm(x, "keye.final_norm", cfg), cfg.vocab_size,
                  "keye.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    lm_loss = layers.mean(layers.cast(per_token, "float32"))
    index_loss = (index_losses[0] if len(index_losses) == 1
                  else layers.sums(index_losses))
    loss = layers.elementwise_add(
        lm_loss, layers.scale(index_loss, scale=cfg.index_loss_weight))
    profiler.set_counter("loss_terms", 2)
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "lm_loss": lm_loss, "index_loss": index_loss, "loads": loads,
            "admits": admits}
