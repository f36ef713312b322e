"""Ouro (ByteDance Ouro-2.6B, `model_type: ouro`; the model's public
`config.json`, the public `modeling_ouro.py` beside it for what the config
leaves open, and "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, for the training objective): a decoder that runs its
stack of layers `total_ut_steps` times with one set of weights, whose
blocks norm each sublayer's output as well as its input, and whose loss
weighs the exits after every step by a learned gate. Built through the
layers API; the vocabulary may be a slice and the layers a run of the
published ones, which is how one chip of a pipeline stage sees the model.

The equations. `x` is `[s, hidden]`, `T = total_ut_steps`, `L` the layers
held, `N` an RMSNorm with a learned weight seeded 1 and `rms_norm_eps`; no
projection has a bias but the gate's.

  x^(0)   = E[tokens]
  for t = 1..T:                                   (the same weights at every t)
      h = x^(t-1)
      for l = 1..L:
          h = h + N2a_l( Attn_l( N1a_l(h) ) )     (input_norm, input_norm_2)
          h = h + N2f_l( FFN_l ( N1f_l(h) ) )     (post_attn_norm, its _2)
      x^(t)  = N_final(h)                         (what step t+1 reads)
      z^(t)  = W_head x^(t)                       (one untied head, T uses)
      lam_t  = sigmoid(w_gate . x^(t) + b_gate)   (a token, float32)
  Attn: q, k, v, o; `h` heads of `d`; RoPE (rotate-half, `rope_theta`,
        positions 0..s-1, float32 angles) on q and k; full causal; scores
        x d^-1/2; no QK-norm, no window
  FFN : W_down( silu(W_gate u) * W_up u )
  S_0 = 1, S_t = prod_{j<=t} (1 - lam_j)
  p_t = lam_t S_{t-1} for t < T,  p_T = S_{T-1}          (sums to 1 a token)
  loss = mean over positions of [ sum_t p_t CE(z^(t), label) - beta H(p) ],
         H(p) = - sum_t p_t log p_t                        (float32)

The loop is stated as a Python loop: a layer's `ParamAttr` names are the
same at every step, so a parameter has T uses in the Program and its
gradient is the sum of T partials (`backward._accumulate`'s counters
`param_grads_summed` and `param_grad_partials`). The gate is a product of
width 1 written as a multiply and a sum in float32, so that mixed
precision leaves it alone. With `total_ut_steps` 1 there is no exit to
choose: no gate is built and the loss is the mean cross-entropy. With
`sandwich_norm` False the blocks are pre-norm blocks (`N2a`, `N2f` left
out): with both, the Program is a plain decoder's, op for op.

`build_ouro` sets three gauges: `loop_steps` (T), `loop_layers` (L) and
`loss_terms` (T exits and the entropy term).
"""

from __future__ import annotations

from .. import layers, profiler
from ..initializer import Constant, Normal
from ..param_attr import ParamAttr
from .decoder_parts import attention, ffn, norm, proj

__all__ = ["OuroConfig", "build_ouro"]


class OuroConfig:
    """The published `config.json`'s keys, the weight `entropy_weight` of
    the loss's entropy term (the paper's beta), and what says which share
    of the model is held: `layers_held` layers from the published index
    `first_layer` on, and `vocab_size` rows of the vocabulary."""

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 num_hidden_layers=48, first_layer=0, layers_held=None,
                 num_attention_heads=16, num_key_value_heads=16, head_dim=128,
                 intermediate_size=5632, rope_theta=1000000.0,
                 rms_norm_eps=1e-6, total_ut_steps=4, entropy_weight=0.1,
                 sandwich_norm=True, initializer_range=0.02,
                 embedding_initializer_range=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers  # as published
        self.first_layer = first_layer
        self.layers_held = (num_hidden_layers - first_layer
                            if layers_held is None else layers_held)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.total_ut_steps = total_ut_steps
        self.entropy_weight = entropy_weight
        self.sandwich_norm = sandwich_norm
        self.initializer_range = initializer_range
        self.embedding_initializer_range = (
            initializer_range if embedding_initializer_range is None
            else embedding_initializer_range)


def sandwich_block(h, cfg, name):
    """One layer: each sublayer between a norm of its input and, with
    `cfg.sandwich_norm`, a norm of its output, which is what the residual
    stream takes."""
    def out(y, which):
        return norm(y, f"{name}.{which}_2", cfg) if cfg.sandwich_norm else y

    a = attention(norm(h, name + ".input_norm", cfg), cfg, name + ".attn",
                  rope_theta=cfg.rope_theta, qk_norm=False)
    h = layers.elementwise_add(h, out(a, "input_norm"))
    f = ffn(norm(h, name + ".post_attn_norm", cfg), cfg.intermediate_size,
            name + ".mlp", cfg)
    return layers.elementwise_add(h, out(f, "post_attn_norm"))


def _exit_gate(x, cfg):
    """`sigmoid(w . x + b)` a token, [b, s, 1] float32: a multiply and a
    sum over the lanes, no product on the MXU."""
    w = layers.create_parameter(
        [cfg.hidden_size], "float32", attr=ParamAttr(
            name="ouro.exit_gate.w_0",
            initializer=Normal(0.0, cfg.initializer_range)))
    b = layers.create_parameter(
        [1], "float32", attr=ParamAttr(name="ouro.exit_gate.b_0",
                                       initializer=Constant(0.0)))
    dot = layers.reduce_sum(
        layers.elementwise_mul(layers.cast(x, "float32"), w), dim=2,
        keep_dim=True)
    return layers.sigmoid(layers.elementwise_add(dot, b))


def _exit_distribution(gates):
    """`p_t = lam_t S_{t-1}` for t < T and `p_T = S_{T-1}`, from the T - 1
    first gates: what is left after the last step exits there, so the
    last step's own gate is in no term of the loss and none is built."""
    ps, survived = [], None
    for lam in gates:
        ps.append(lam if survived is None
                  else layers.elementwise_mul(lam, survived))
        stay = layers.scale(lam, scale=-1.0, bias=1.0)
        survived = (stay if survived is None
                    else layers.elementwise_mul(survived, stay))
    return ps + [survived]


def build_ouro(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the loss of the equations over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    (the last step's, [b, s, vocab_size]), `step_logits` (every step's,
    the last's last), `loss`, its two terms `task_loss` (the expected
    cross-entropy under the exit distribution) and `entropy` (the mean of
    H(p); both None with one step), and `loads` (empty: no expert
    layer)."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    steps = cfg.total_ut_steps
    x = layers.embedding(
        tokens, (cfg.vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="ouro.embed", initializer=Normal(
            0.0, cfg.embedding_initializer_range)))
    label_col = layers.reshape(labels, [batch_size, seq_len, 1])
    step_logits, nlls, gates = [], [], []
    for t in range(steps):
        for l in range(cfg.first_layer, cfg.first_layer + cfg.layers_held):
            x = sandwich_block(x, cfg, f"ouro.layer{l}")
        x = norm(x, "ouro.final_norm", cfg)
        logits = proj(x, cfg.vocab_size, "ouro.head", cfg)
        step_logits.append(logits)
        # float32 from here: under bf16 AMP the per-token losses are bf16,
        # whose neighbours near ln(vocabulary) lie 0.0625 apart
        nlls.append(layers.cast(
            layers.softmax_with_cross_entropy(logits, label_col), "float32"))
        if t < steps - 1:
            gates.append(_exit_gate(x, cfg))
    profiler.set_counter("loop_steps", steps)
    profiler.set_counter("loop_layers", cfg.layers_held)
    profiler.set_counter("loss_terms", steps + 1 if steps > 1 else 1)
    handles = {"feeds": ["tokens", "labels"], "logits": step_logits[-1],
               "step_logits": step_logits, "loads": []}
    if steps == 1:
        return {**handles, "loss": layers.mean(nlls[0]), "task_loss": None,
                "entropy": None}
    ps = _exit_distribution(gates)
    expected = layers.sums([layers.elementwise_mul(p, nll)
                            for p, nll in zip(ps, nlls)])
    entropy = layers.scale(layers.sums(
        [layers.elementwise_mul(p, layers.log(p)) for p in ps]), scale=-1.0)
    task_loss, mean_entropy = layers.mean(expected), layers.mean(entropy)
    loss = layers.elementwise_sub(
        task_loss, layers.scale(mean_entropy, scale=cfg.entropy_weight))
    return {**handles, "loss": loss, "task_loss": task_loss,
            "entropy": mean_entropy}
