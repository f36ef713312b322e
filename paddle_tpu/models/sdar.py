"""SDAR-30B-A3B-Chat's language model (JetLM, `model_type: sdar_moe`; the
model's public `config.json`) as it trains: a decoder of grouped-query
attention layers, each followed by an expert layer with a softmax router
and no shared expert, trained as a block-diffusion denoiser (BD3-LM,
arXiv:2503.09573; SDAR's report, arXiv:2510.06303, adopts its objective)
and not as a next-token predictor. Built through the layers API; the
expert layer may hold a share of the experts, the vocabulary may be a
slice and the layers a run of the published ones, which is how one chip of
an expert-parallel group sees the model.

A row is `x[0..L-1]`, cut in blocks of B tokens, `beta(i) = i // B`. The
batch maker draws a noise level `t` in (0, 1] a block and replaces each
token of the block by the mask id with probability t, independently: that
is `x~`. The layers run on the 2L rows `[x~ ; x]` (the noisy copy `n`, then
the clean copy `c`), both at positions 0..L-1. Layer l, `h` `[2L, 2048]`;
N an RMSNorm (learned weight, eps 1e-6), `R_p` the rotate-half rotation at
theta 1e6 by position p over all of a head's lanes:

  a      = N1(h)
  q      = R_p(Nq(W_q a)) [32 x 128]   k = R_p(Nk(W_k a)) [4 x 128]
  v      = W_v a [4 x 128]             g(head) = head // 8,  p = i either copy
  admit((n,i),(n,j)) = beta(j) == beta(i)         own block, both ways
  admit((n,i),(c,j)) = beta(j) <  beta(i)         the clean past, whole blocks
  admit((c,i),(c,j)) = beta(j) <= beta(i)         block-causal
  admit((c,i),(n,j)) = false
  A      = softmax over the admitted keys of q . k[g] / sqrt(128)
  h      = h + W_o (A v[g])
  u2     = N2(h);  r = softmax(W_r u2) over all 128, float32;  sel = top-8(r)
  h      = h + sum over e in sel held here of
             (r_e / sum r[sel]) W_down_e(silu(W_gate_e u2) * W_up_e u2)

  logits = W_head N_f(h_last[(n, i)])              the noisy rows alone
  loss   = (1/L) sum over i with x~_i = MASK of
             (1 / t_beta(i)) CE(logits_i, x_i)     float32

A masked position predicts its own token (no shift by one). The weights
`w_i = [x~_i = MASK] / t_beta(i)` are data, a feed beside the tokens: the
program has no random op, and the batch maker's draw is the run's seed's.
The sum `admit` over a head is L B + (L^2 - L B) / 2 + (L^2 + L B) / 2
pairs: at L = 4,096 and B = 4, 16,793,600, half of the doubled row's causal
pairs.

The attention is `decoder_parts.attention` with `diffusion_block` (the op
`fused_multihead_attention` under `diffusion_block`: three calls of the
flash kernels under a granule where they run, the mask as an admission
elsewhere), the expert layer the op `moe_experts` with `score_func`
"softmax". The builder sets the gauge `loss_terms` (1); a build bumps
`diffusion_layers`, `attn_pairs_admitted` and `attn_pairs_causal` once a
layer and sets `diffusion_block_length`.
"""

from __future__ import annotations

from .. import layers, profiler
from ..initializer import Normal
from ..param_attr import ParamAttr
from .decoder_parts import attention, expert_ffn, norm, proj

__all__ = ["SdarConfig", "build_sdar"]


class SdarConfig:
    """The published `config.json`'s keys under the names `decoder_parts`
    reads, and what says which share of the model is held:
    `num_hidden_layers` layers from the published `first_layer` on,
    `experts_held` of `num_experts` from `held_from` on, and `vocab_size`
    rows of the vocabulary, of which `mask_token_id` is the mask's (the
    last held by default: the published id lies outside a slice).
    `block_length` is B. `embedding_initializer_range` as
    `MellumConfig`'s, for its reason."""

    score_func = "softmax"
    routed_scaling_factor = 1.0
    num_shared_experts = 0
    router_bias_scale = 0.0  # no correction: the op's Bias stays zeros

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, first_layer=0, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, rope_theta=1000000.0,
                 moe_intermediate_size=768, num_experts=128,
                 experts_held=None, held_from=0, num_experts_per_token=8,
                 norm_topk_prob=True, rms_norm_eps=1e-6, block_length=4,
                 mask_token_id=None, initializer_range=0.02,
                 embedding_initializer_range=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_layer = first_layer
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.experts_held = num_experts if experts_held is None else experts_held
        self.held_from = held_from
        self.num_experts_per_token = num_experts_per_token
        self.moe_renormalize = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.block_length = block_length
        self.mask_token_id = (vocab_size - 1 if mask_token_id is None
                              else mask_token_id)
        if not 0 <= self.mask_token_id < vocab_size:
            raise ValueError(f"SdarConfig: mask id {self.mask_token_id} "
                             f"outside the {vocab_size} rows held")
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)


def build_sdar(cfg, batch_size, seq_len):
    """Declares the data vars `noisy`, `tokens` ([b, L] int64: `x~` and
    `x`, ids in the slice of the vocabulary held) and `weights` ([b, L]
    float32: 1/t of a masked position's block, 0 elsewhere) and the loss
    of the equations, float32. Returns a dict of handles: `feeds`,
    `logits` ([b, L, vocab_size], the noisy rows'), `loss`, and `loads`,
    one `[experts_held]` int32 var for each layer."""
    if seq_len % cfg.block_length:
        raise ValueError(f"build_sdar: {seq_len} tokens a row are no whole "
                         f"blocks of {cfg.block_length}")
    noisy = layers.data("noisy", [batch_size, seq_len], dtype="int64",
                        append_batch_size=False)
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    weights = layers.data("weights", [batch_size, seq_len], dtype="float32",
                          append_batch_size=False)
    x = layers.embedding(
        layers.concat([noisy, tokens], axis=1),
        (cfg.vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="sdar.embed", initializer=Normal(
            0.0, cfg.embedding_initializer_range)))
    loads = []
    for at in range(cfg.num_hidden_layers):
        name = f"sdar.layer{cfg.first_layer + at}"
        mixed = attention(norm(x, name + ".input_norm", cfg), cfg,
                          name + ".attn", rope_theta=cfg.rope_theta,
                          diffusion_block=cfg.block_length)
        x = layers.elementwise_add(x, mixed)
        out, load = expert_ffn(norm(x, name + ".post_attn_norm", cfg), cfg,
                               name)
        loads.append(load)
        x = layers.elementwise_add(x, out)
    # the clean copy's rows out of the last layer go on to the next
    # pipeline stage where there is one; the loss reads the noisy rows
    x = layers.slice(x, axes=[1], starts=[0], ends=[seq_len])
    logits = proj(norm(x, "sdar.final_norm", cfg), cfg.vocab_size,
                  "sdar.head", cfg)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(tokens, [batch_size, seq_len, 1]))
    # weighted and summed in float32: under bf16 AMP the per-token losses
    # are bf16, whose neighbours near ln(vocabulary) lie 0.0625 apart
    weighted = layers.elementwise_mul(
        layers.cast(layers.reshape(per_token, [batch_size, seq_len]),
                    "float32"), weights)
    loss = layers.scale(layers.reduce_sum(weighted),
                        scale=1.0 / (batch_size * seq_len))
    profiler.set_counter("loss_terms", 1)
    return {"feeds": ["noisy", "tokens", "weights"], "logits": logits,
            "loss": loss, "loads": loads}
