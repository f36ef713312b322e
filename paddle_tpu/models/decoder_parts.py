"""What the expert-parallel decoders of the zoo (`kimi_linear`, `trinity`)
build their layers from: bias-free projections seeded Normal(0,
`initializer_range`), RMSNorm with a learned weight, the SiLU-gated
feed-forward, and the expert layer that holds a share of the experts. A
`cfg` gives `hidden_size`, `initializer_range`, `rms_norm_eps` and, for
`expert_ffn`, the router's keys as `KimiLinearConfig` names them."""

from __future__ import annotations

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


def expert_ffn(u, cfg, name):
    """Returns (what the shared expert and the held experts add, load)."""
    routed, load = layers.moe_experts(
        u, experts_total=cfg.num_experts, experts_held=cfg.experts_held,
        d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_token,
        held_from=cfg.held_from, scaling=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, bias_scale=cfg.router_bias_scale,
        param_attr=attr(name + ".moe", cfg))
    if not cfg.num_shared_experts:
        return routed, load
    shared = ffn(u, cfg.moe_intermediate_size * cfg.num_shared_experts,
                 name + ".shared", cfg)
    return layers.elementwise_add(shared, routed), load
