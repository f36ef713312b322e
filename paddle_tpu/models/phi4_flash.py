"""Phi-4-mini-flash (microsoft Phi-4-mini-flash-reasoning, `model_type:
phi4flash`; the model's public `config.json`, and the SambaY paper,
arXiv:2507.06607, with Mamba, arXiv:2312.00752, Samba, arXiv:2406.07522,
and the Differential Transformer, arXiv:2410.05258, for what the config
leaves open): a decoder-hybrid-decoder. Its first half alternates Mamba-1
mixers with differential attention through a window; one full-attention
layer follows; the second half alternates Gated Memory Units, which gate
the last Mamba layer's scan output, with differential cross attention
over that one full layer's keys and values. Built through the layers API;
the vocabulary may be a slice and the layers a run of the published ones,
which is how one chip of a pipeline stage sees the model.

The equations. `x` is `[s, hidden]`; `LN` is LayerNorm with weight and
bias; no dropout and no positions anywhere. Layer `l` counts from 0 as
published, `L` layers in all, `half = L / 2`.

  x0 = E[tokens]                                              (no scale)
  x  = x + Mixer_l(LN1_l(x));   x = x + MLP_l(LN2_l(x))
  MLP:  [g ; y] = W_fc1 u (no bias);  f = W_fc2 (y * silu(g))
  Mixer_l:  l even, l <= half: Mamba        l odd, l < half: differential attention, window
            l even, l >  half: GMU          l = half + 1: differential attention, full; exports k, v
                                            l odd, l > half + 1: differential cross attention over layer half + 1's k, v
  Mamba (d_inner = 2 hidden, d_state 16, d_conv 4, dt_rank = hidden / 16):
    [xs ; z] = W_in u (no bias);  xc = silu(causal_conv(xs; w, b))   (zero state at the row's start)
    [dt ; B ; C] = W_x xc (no bias);  Delta = softplus(W_dt dt + b_dt)
    A = -exp(A_log) [d_inner, d_state]
    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t * xc_t) B_t^T,  h_0 = 0, float32
    y_t = h_t C_t + D * xc_t;   out = W_out (y * silu(z))            (no bias)
    Layer `half` also exports m = y.
  GMU:  out = W_out (m * silu(W_in u)),  no bias, m from layer `half`.
  Differential attention (h query and g key/value heads of d in pairs;
  pair n is heads 2n, 2n + 1):
    [q ; k ; v] = W_qkv u + b     (cross layers: q = W_q u + b alone)
    S_c[i, j] = softmax_j(q[i, n, c] . k[j, n // (h / g), c] / sqrt(d))
      over j <= i, and on a window layer i - j < sliding_window;  c = 1, 2
    a_c = S_c [v[n // (h / g), 1] ; v[n // (h / g), 2]]          (2 d wide)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),  lam0(l) = 0.8 - 0.6 exp(-0.3 l)
    o_n = (1 - lam0(l)) RMSNorm(a_1 - lam a_2; w_sub);  out = W_o concat_n(o_n) + b_o
  logits = E^T LN_f(x)   (tied, no bias)
  loss = mean over positions of the next-token cross-entropy, float32

The scan is the op `selective_scan`, the convolution `short_conv1d` with
its bias, each score map a `fused_multihead_attention` call whose values
are twice as wide as its keys. `build_phi4_flash` sets three gauges:
`diff_attn_layers`, `shared_kv_layers` (the cross layers) and
`gmu_layers`.
"""

from __future__ import annotations

import math

import numpy as np

from .. import layers, profiler
from ..framework import default_main_program
from ..initializer import Constant, NumpyArrayInitializer, Uniform
from ..param_attr import ParamAttr
from .decoder_parts import (attr, differential_attention, fused_ffn,
                            layer_norm, proj)

__all__ = ["Phi4FlashConfig", "build_phi4_flash"]


class Phi4FlashConfig:
    """The published `config.json`'s keys, the Mamba sizes its config
    class defaults to, and what says which share of the model is held:
    `layers_held` layers from the published index `first_layer` on (their
    kinds follow from the index, `layer_kind`), and `vocab_size` rows of
    the vocabulary."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 num_hidden_layers=32, first_layer=0, layers_held=None,
                 num_attention_heads=40, num_key_value_heads=20,
                 intermediate_size=10240, sliding_window=512, mb_per_layer=2,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=None, layer_norm_eps=1e-5,
                 initializer_range=0.02):
        if mb_per_layer != 2:
            raise ValueError("phi4flash: mb_per_layer 2 alone is built (a "
                             "scan or a memory unit every other layer)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers  # as published
        self.first_layer = first_layer
        self.layers_held = (num_hidden_layers - first_layer
                            if layers_held is None else layers_held)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.intermediate_size = intermediate_size
        self.sliding_window = sliding_window
        self.d_state = mamba_d_state
        self.d_conv = mamba_d_conv
        self.d_inner = mamba_expand * hidden_size
        self.dt_rank = mamba_dt_rank or math.ceil(hidden_size / 16)
        self.layer_norm_eps = layer_norm_eps
        self.initializer_range = initializer_range

    def layer_kind(self, l):
        """"mamba", "gmu", "window", "full" or "cross", by published index."""
        half = self.num_hidden_layers // 2
        if l % 2 == 0:
            return "mamba" if l <= half else "gmu"
        return "window" if l < half else "full" if l == half + 1 else "cross"

    @staticmethod
    def lambda_init(l):
        return 0.8 - 0.6 * math.exp(-0.3 * l)


def mamba(u, cfg, name):
    """Returns (the mixer's output [b, s, hidden], the scan's output
    [b, s, d_inner] before its gate)."""
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    xs, z = layers.split(proj(u, 2 * di, name + ".in_proj", cfg), 2, dim=2)
    edge = cfg.d_conv ** -0.5  # a depthwise filter's fan-in is its width
    xc = layers.short_conv1d(
        xs, cfg.d_conv,
        param_attr=ParamAttr(name=name + ".conv.w_0",
                             initializer=Uniform(-edge, edge)),
        bias_attr=ParamAttr(name=name + ".conv.b_0",
                            initializer=Uniform(-edge, edge)))
    dt, bm, cm = layers.split(proj(xc, r + 2 * n, name + ".x_proj", cfg),
                              [r, n, n], dim=2)
    # the step softplus(b_dt) log-uniform in [0.001, 0.1], as the KDA
    # layer seeds its own (layers.kda_attention)
    delta = layers.softplus(layers.fc(
        dt, di, num_flatten_dims=2,
        param_attr=ParamAttr(name=name + ".dt_proj.w_0",
                             initializer=Uniform(-r ** -0.5, r ** -0.5)),
        bias_attr=ParamAttr(name=name + ".dt_proj.b_0",
                            initializer=Uniform(-6.9, -2.25))))
    a_log = layers.create_parameter(
        [di, n], "float32", attr=ParamAttr(
            name=name + ".A_log", initializer=NumpyArrayInitializer(
                np.tile(np.log(np.arange(1, n + 1, dtype=np.float32)),
                        (di, 1)))))
    dskip = layers.create_parameter(
        [di], "float32",
        attr=ParamAttr(name=name + ".D", initializer=Constant(1.0)))
    y = layers.selective_scan(
        xc, delta, layers.scale(layers.exp(a_log), scale=-1.0), bm, cm, dskip)
    return proj(layers.elementwise_mul(y, layers.swish(z)), cfg.hidden_size,
                name + ".out_proj", cfg), y


def gmu(u, memory, cfg, name):
    gate = layers.swish(proj(u, cfg.d_inner, name + ".in_proj", cfg))
    return proj(layers.elementwise_mul(memory, gate), cfg.hidden_size,
                name + ".out_proj", cfg)


def build_phi4_flash(cfg, batch_size, seq_len):
    """Declares the data vars `tokens` and `labels` ([b, s] int64, ids in
    the slice of the vocabulary held) and the mean next-token loss over
    every position, float32. Returns a dict of handles: `feeds`, `logits`
    ([b, s, vocab_size]), `loss`, and `loads` (empty: no expert layer)."""
    tokens = layers.data("tokens", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", [batch_size, seq_len], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(tokens, (cfg.vocab_size, cfg.hidden_size),
                         param_attr=attr("phi4.embed", cfg))
    table = default_main_program().global_block().var("phi4.embed")
    memory = shared_kv = None
    kinds = [cfg.layer_kind(l) for l in range(
        cfg.first_layer, cfg.first_layer + cfg.layers_held)]
    for l, kind in enumerate(kinds, cfg.first_layer):
        name = f"phi4.layer{l}"
        u = layer_norm(x, name + ".norm1", cfg)
        if kind == "mamba":
            mixed, y = mamba(u, cfg, name + ".mamba")
            if l == cfg.num_hidden_layers // 2:
                memory = y
        elif kind == "gmu":
            if memory is None:
                raise ValueError(f"layer {l} is a memory unit and the layer "
                                 "whose scan it gates is not held")
            mixed = gmu(u, memory, cfg, name + ".gmu")
        else:
            if kind == "cross" and shared_kv is None:
                raise ValueError(f"layer {l} attends over the full layer's "
                                 "keys and values, which are not held")
            mixed, kv = differential_attention(
                u, cfg, name + ".attn",
                window=cfg.sliding_window if kind == "window" else 0,
                kv=shared_kv if kind == "cross" else None,
                lam0=cfg.lambda_init(l))
            if kind == "full":
                shared_kv = kv
        x = layers.elementwise_add(x, mixed)
        x = layers.elementwise_add(x, fused_ffn(
            layer_norm(x, name + ".norm2", cfg), cfg.intermediate_size,
            name + ".mlp", cfg))
    logits = layers.matmul(layer_norm(x, "phi4.final_norm", cfg), table,
                           transpose_y=True)
    per_token = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [batch_size, seq_len, 1]))
    # the mean in float32: under bf16 AMP the per-token losses are bf16,
    # whose neighbours near ln(vocabulary) lie 0.0625 apart
    loss = layers.mean(layers.cast(per_token, "float32"))
    profiler.set_counter("diff_attn_layers", sum(
        k in ("window", "full", "cross") for k in kinds))
    profiler.set_counter("shared_kv_layers", kinds.count("cross"))
    profiler.set_counter("gmu_layers", kinds.count("gmu"))
    return {"feeds": ["tokens", "labels"], "logits": logits, "loss": loss,
            "loads": []}
