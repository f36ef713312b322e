"""What the expert-parallel decoders of the zoo (`kimi_linear`, `trinity`,
`mellum`) build their layers from: bias-free projections seeded Normal(0,
`initializer_range`), RMSNorm with a learned weight, the SiLU-gated
feed-forward, attention over grouped key/value heads with QK-norm and
rotary positions, and the expert layer that holds a share of the experts.
A `cfg` gives `hidden_size`, `initializer_range`, `rms_norm_eps`, for
`attention` the heads, and for `expert_ffn` the router's keys as
`KimiLinearConfig` names them."""

from __future__ import annotations

import math

from .. import layers
from ..initializer import Normal
from ..param_attr import ParamAttr


def attr(name, cfg):
    return ParamAttr(name=name, initializer=Normal(0.0, cfg.initializer_range))


def proj(x, size, name, cfg):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=attr(name + ".w_0", cfg), bias_attr=False)


def norm(x, name, cfg, axis=2):
    return layers.rms_norm(x, begin_norm_axis=axis, epsilon=cfg.rms_norm_eps,
                           param_attr=ParamAttr(name=name + ".w_0"))


def ffn(u, width, name, cfg):
    gate = layers.swish(proj(u, width, name + ".gate", cfg))
    up = proj(u, width, name + ".up", cfg)
    return proj(layers.elementwise_mul(gate, up), cfg.hidden_size,
                name + ".down", cfg)


def attention(u, cfg, name, window=0, rope_theta=0.0, rope_scaling=None,
              gated=False):
    """Causal attention of `num_attention_heads` query heads over
    `num_key_value_heads` key/value heads of `head_dim`, u [b, s, hidden]
    to [b, s, hidden]: q and k normed over a head's width (one weight of
    `head_dim` each), turned by rotary positions where `rope_theta` is
    not 0 (`rope_scaling`: a YaRN group), `window` keys wide where it is
    not 0, and with `gated` the output times `sigmoid(W_g u)` before the
    output projection."""
    b, s, _ = u.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = layers.reshape(proj(u, h * d, name + ".q", cfg), [b, s, h, d])
    k = layers.reshape(proj(u, g * d, name + ".k", cfg), [b, s, g, d])
    v = layers.reshape(proj(u, g * d, name + ".v", cfg), [b, s, g, d])
    if gated:
        gate = layers.sigmoid(proj(u, h * d, name + ".gate", cfg))
    # QK-norm and the positions inside the attention op, where they and
    # the kernel's head-major write are one pass over q and k
    a = layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d), layout="bshd",
        window=window, q_norm_attr=ParamAttr(name=name + ".q_norm.w_0"),
        k_norm_attr=ParamAttr(name=name + ".k_norm.w_0"),
        qk_norm_epsilon=cfg.rms_norm_eps, rope_theta=rope_theta,
        rope_scaling=rope_scaling)
    a = layers.reshape(a, [b, s, h * d])
    if gated:
        a = layers.elementwise_mul(a, gate)
    return proj(a, cfg.hidden_size, name + ".o", cfg)


def expert_ffn(u, cfg, name):
    """Returns (what the shared expert and the held experts add, load)."""
    routed, load = layers.moe_experts(
        u, experts_total=cfg.num_experts, experts_held=cfg.experts_held,
        d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_token,
        held_from=cfg.held_from, scaling=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, bias_scale=cfg.router_bias_scale,
        param_attr=attr(name + ".moe", cfg),
        score_func=cfg.score_func)
    if not cfg.num_shared_experts:
        return routed, load
    shared = ffn(u, cfg.moe_intermediate_size * cfg.num_shared_experts,
                 name + ".shared", cfg)
    return layers.elementwise_add(shared, routed), load
