"""What the decoders of the zoo (`kimi_linear`, `trinity`, `mellum`,
`joyai_flash`, `phi4_flash`, `lfm2`, `qwen3_next`, `nemotron_h`, `ouro`,
`keye_vl2`, `olmo_hybrid`, `granite_hybrid`) build their layers from:
projections seeded
Normal(0, `initializer_range`), with a bias where asked, RMSNorm with a learned
weight and LayerNorm with weight and bias, the SiLU-gated feed-forward as
three products or with gate and up in one, the squared-ReLU feed-forward without a gate
(`nemotron_h`), attention over grouped key/value heads with or without
QK-norm (a head at a time or over the whole projection) and with rotary
positions on a head, on its first lanes or not at all, latent attention (`kimi_linear`, `joyai_flash`), differential
attention (`phi4_flash`), attention that keeps for each query the keys a
learned indexer scores highest (`keye_vl2`), the double-gated short
convolution (`lfm2`),
Gated DeltaNet (`qwen3_next`, `olmo_hybrid`: the delta rule with a decay a
head, key heads shared by groups of value heads or key and value heads
of two widths), the Mamba-2 mixer (`nemotron_h`, `granite_hybrid`:
a decay a head and a token, a norm by groups behind the gate), and the
expert layer that holds a share of the experts, with a shared expert that
a token may gate, and with experts that may read a latent of the token
and have no gate. A mixer builds the heads its `cfg` counts: where a chip
holds a share of a mixer's heads, `cfg` gives the share. A `cfg` gives
`hidden_size`, `initializer_range`, `rms_norm_eps` (or `layer_norm_eps`),
for `attention` and `differential_attention` the heads, for
`latent_attention` the keys its docstring lists, for `gated_short_conv`
`conv_L_cache`, for `gated_delta_net` the `linear_*` keys, for
`mamba2_mixer` the `mamba_*` keys, and for `expert_ffn` the router's keys
as `KimiLinearConfig` names them."""

from __future__ import annotations

import math

from .. import layers, profiler
from ..initializer import Normal, Uniform
from ..ops.linear_attn_ops import delta_rule_lanes
from ..ops.pallas import cost
from ..param_attr import ParamAttr


def attr(name, cfg, std=None):
    return ParamAttr(name=name, initializer=Normal(
        0.0, cfg.initializer_range if std is None else std))


def proj(x, size, name, cfg, bias=False, std=None):
    """`std`: the seeding's deviation where it is not `initializer_range`
    (a block's last product under `rescale_prenorm_residual`)."""
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=attr(name + ".w_0", cfg, std),
                     bias_attr=ParamAttr(name=name + ".b_0") if bias else False)


def norm(x, name, cfg, axis=2):
    return layers.rms_norm(x, begin_norm_axis=axis, epsilon=cfg.rms_norm_eps,
                           param_attr=ParamAttr(name=name + ".w_0"))


def layer_norm(x, name, cfg, axis=2):
    return layers.layer_norm(
        x, begin_norm_axis=axis, epsilon=cfg.layer_norm_eps,
        param_attr=ParamAttr(name=name + ".w_0"),
        bias_attr=ParamAttr(name=name + ".b_0"))


def ffn(u, width, name, cfg):
    gate = layers.swish(proj(u, width, name + ".gate", cfg))
    up = proj(u, width, name + ".up", cfg)
    return proj(layers.elementwise_mul(gate, up), cfg.hidden_size,
                name + ".down", cfg)


def relu2_ffn(u, width, name, cfg, out_std=None):
    """`W_down relu(W_up u)^2`: two products and no gate."""
    up = layers.relu(proj(u, width, name + ".up", cfg))
    return proj(layers.square(up), cfg.hidden_size, name + ".down", cfg,
                std=out_std)


def fused_ffn(u, width, name, cfg):
    """`W_fc2 (y * silu(g))` with `[g ; y] = W_fc1 u`: gate and up come
    from one product, the gate first."""
    gate, up = layers.split(proj(u, 2 * width, name + ".fc1", cfg), 2, dim=2)
    return proj(layers.elementwise_mul(up, layers.swish(gate)),
                cfg.hidden_size, name + ".fc2", cfg)


def gated_short_conv(u, cfg, name):
    """LFM2's mixer, u [b, s, hidden] to [b, s, hidden]:
    `W_out (C * conv(B * x))` with `[B ; C ; x] = W_in u`, three chunks of
    `hidden` in this order, and `conv` the causal depthwise convolution
    over `conv_L_cache` taps from a zero state, with no bias and no
    activation: the two gates are what is non-linear. The filter is
    seeded uniform in +-`conv_L_cache`^-1/2 (a depthwise filter's fan-in
    is its width). Products and gates are the Program's ordinary ops."""
    b_gate, c_gate, xs = layers.split(
        proj(u, 3 * cfg.hidden_size, name + ".in_proj", cfg), 3, dim=2)
    edge = cfg.conv_L_cache ** -0.5
    conv = layers.short_conv1d(
        layers.elementwise_mul(b_gate, xs), cfg.conv_L_cache,
        param_attr=ParamAttr(name=name + ".conv.w_0",
                             initializer=Uniform(-edge, edge)), act=None)
    return proj(layers.elementwise_mul(c_gate, conv), cfg.hidden_size,
                name + ".out_proj", cfg)


def gated_delta_net(u, cfg, name):
    """Qwen3-Next's and Olmo-Hybrid's linear mixer (Gated DeltaNet,
    arXiv:2412.06464), u [b, s, hidden] to [b, s, hidden]:
    `linear_num_key_heads` heads of `linear_key_head_dim` for q and k under
    `linear_num_value_heads` heads of `linear_value_head_dim` for v and the
    output gate z (the two widths need not agree: the state of a head is
    key lanes by value lanes), value head n reading key head n // group;
    `[q ; k ; v ; z] = W_qkvz u` and
    `[b ; a] = W_ba u`, one number a value head each; q, k and v pass
    together through one causal depthwise convolution of
    `linear_conv_kernel_dim` taps and a SiLU (the filter uniform in
    +-taps^-1/2, no bias); the op `kda_attention` norms q and k, makes
    `beta = cfg.linear_beta_scale * sigmoid(b)` (absent: 1; 2 is the
    public `allow_neg_eigval`) and the head's log decay `-exp(A_log) *
    softplus(a + dt_bias)`, and runs the delta rule; each head's output
    is RMS-normed over its lanes with one learned weight of
    `linear_value_head_dim` and multiplied by `SiLU(z)` before `W_out`.
    Every piece is one of the Program's ordinary ops. Counters, once a
    layer built: `delta_rule_lanes_published`, the heads x key lanes x
    value lanes of the states, and `delta_rule_lanes_computed`, the same
    over the lanes the lowering this backend will take multiplies
    (`ops/linear_attn_ops.py::delta_rule_lanes`)."""
    b, s, _ = u.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    published, computed = delta_rule_lanes(s, hv, hk, dk, dv, per_head=True)
    profiler.bump_counter("delta_rule_lanes_published", published)
    profiler.bump_counter("delta_rule_lanes_computed", computed)
    qkv, z = layers.split(
        proj(u, 2 * hk * dk + 2 * hv * dv, name + ".in_proj_qkvz", cfg),
        [2 * hk * dk + hv * dv, hv * dv], dim=2)
    beta, a = layers.split(proj(u, 2 * hv, name + ".in_proj_ba", cfg), 2,
                           dim=2)
    edge = cfg.linear_conv_kernel_dim ** -0.5
    q, k, v = layers.split(layers.short_conv1d(
        qkv, cfg.linear_conv_kernel_dim,
        param_attr=ParamAttr(name=name + ".conv.w_0",
                             initializer=Uniform(-edge, edge))),
        [hk * dk, hk * dk, hv * dv], dim=2)
    o = layers.kda_attention(
        q, k, v, a, beta, num_heads=hv, num_key_heads=hk,
        l2norm_epsilon=cfg.l2norm_epsilon,
        a_log_attr=ParamAttr(name=name + ".A_log"),
        dt_bias_attr=ParamAttr(name=name + ".dt_bias"),
        beta_scale=getattr(cfg, "linear_beta_scale", 1.0))
    o = norm(layers.reshape(o, [b, s, hv, dv]), name + ".norm", cfg, axis=3)
    o = layers.elementwise_mul(layers.reshape(o, [b, s, hv * dv]),
                               layers.swish(z))
    return proj(o, cfg.hidden_size, name + ".out_proj", cfg)


def mamba2_mixer(u, cfg, name, out_std=None):
    """Mamba-2's mixer (arXiv:2405.21060), u [b, s, hidden] to
    [b, s, hidden], with the H = `mamba_num_heads` heads of P =
    `mamba_head_dim` and the G = `mamba_n_groups` groups of B and C that
    are held here (a tensor-parallel rank's share of the published mixer
    is whole groups: its heads, their B and C, their group of the norm):
    `[z ; xBC ; dt] = W_in u` (hidden to H P + (H P + 2 G N) + H, N =
    `ssm_state_size`), one product; `xBC` through one causal depthwise
    convolution of `mamba_conv_kernel` taps with bias and a SiLU; the op
    `ssd_scan` makes the step `softplus(dt + dt_bias)` and the decay from
    `A_log` and runs the recurrence in chunks of `mamba_chunk_size`; the
    output times `SiLU(z)` is RMS-normed group by group over the H P / G
    channels of a group, each channel with its own learned weight
    (`.norm.group{i}.w_0`), before `W_out`. The filter and its bias are
    seeded uniform in +-taps^-1/2."""
    h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                  cfg.mamba_n_groups, cfg.ssm_state_size)
    z, xbc, dt = layers.split(
        proj(u, 2 * h * p + 2 * g * n + h, name + ".in_proj", cfg),
        [h * p, h * p + 2 * g * n, h], dim=2)
    edge = cfg.mamba_conv_kernel ** -0.5
    xs, bm, cm = layers.split(layers.short_conv1d(
        xbc, cfg.mamba_conv_kernel,
        param_attr=ParamAttr(name=name + ".conv.w_0",
                             initializer=Uniform(-edge, edge)),
        bias_attr=ParamAttr(name=name + ".conv.b_0",
                            initializer=Uniform(-edge, edge))),
        [h * p, g * n, g * n], dim=2)
    y = layers.ssd_scan(
        xs, dt, bm, cm, num_heads=h, n_groups=g,
        chunk_size=cfg.mamba_chunk_size,
        a_log_attr=ParamAttr(name=name + ".A_log"),
        dt_bias_attr=ParamAttr(name=name + ".dt_bias"),
        d_attr=ParamAttr(name=name + ".D"))
    gated = layers.elementwise_mul(y, layers.swish(z))
    parts = [gated] if g == 1 else layers.split(gated, g, dim=2)
    normed = [norm(part, f"{name}.norm.group{i}", cfg)
              for i, part in enumerate(parts)]
    o = normed[0] if g == 1 else layers.concat(normed, axis=2)
    return proj(o, cfg.hidden_size, name + ".out_proj", cfg, std=out_std)


def _by_pairs(t, b, s, pairs, d):
    """[b, s, pairs * 2 * d], pair n heads 2n and 2n + 1, to the first and
    the second head of every pair, [b, s, pairs, d] each."""
    first, second = layers.split(
        layers.reshape(t, [b, s, pairs, 2, d]), 2, dim=3)
    return (layers.reshape(first, [b, s, pairs, d]),
            layers.reshape(second, [b, s, pairs, d]))


def differential_attention(u, cfg, name, window=0, kv=None, lam0=0.8):
    """Causal differential attention (arXiv:2410.05258), u [b, s, hidden]
    to [b, s, hidden]: `num_attention_heads` query heads and
    `num_key_value_heads` key/value heads of `head_dim` taken in pairs.
    Each pair has two softmax maps, one a head of the pair, over the
    pair's two value heads side by side (`2 * head_dim` wide), and gives
    `(1 - lam0) RMSNorm(a_1 - lam a_2)` with `lam = exp(lq1 . lk1) -
    exp(lq2 . lk2) + lam0` (four learned vectors of `head_dim` a layer)
    and one norm weight of `2 * head_dim` a layer. Query pair n reads
    key/value pair n // group. Projections have a bias. `window` keys
    wide where it is not 0. `kv`: another layer's keys and values as this
    function returned them, and then only the query is projected here
    (`.q`; otherwise `.qkv`, one product). Returns (out, kv).

    The two maps are two `fused_multihead_attention` calls over the first
    and the second heads of the pairs, values at `2 * head_dim` beside
    keys of `head_dim`; the difference, the norm and `lam` are ordinary
    ops, in float32."""
    b, s, _ = u.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    if kv is None:
        q, k, v = layers.split(
            proj(u, (h + 2 * g) * d, name + ".qkv", cfg, bias=True),
            [h * d, g * d, g * d], dim=2)
        kv = (*_by_pairs(k, b, s, g // 2, d),
              layers.reshape(v, [b, s, g // 2, 2 * d]))
    else:
        q = proj(u, h * d, name + ".q", cfg, bias=True)
    k1, k2, v = kv
    maps = [layers.cast(layers.fused_multihead_attention(
        q_c, k_c, v, causal=True, sm_scale=1.0 / math.sqrt(d), layout="bshd",
        window=window), "float32")
        for q_c, k_c in zip(_by_pairs(q, b, s, h // 2, d), (k1, k2))]

    def dot(c):
        lq, lk = (layers.create_parameter(
            [d], "float32", attr=ParamAttr(
                name=f"{name}.lambda_{x}{c}", initializer=Normal(0.0, 0.1)))
            for x in "qk")
        return layers.exp(layers.reduce_sum(layers.elementwise_mul(lq, lk)))

    lam = layers.scale(layers.elementwise_sub(dot(1), dot(2)), bias=lam0)
    a = layers.elementwise_sub(maps[0], layers.elementwise_mul(maps[1], lam))
    a = layers.rms_norm(a, begin_norm_axis=3, epsilon=cfg.layer_norm_eps,
                        param_attr=ParamAttr(name=name + ".subln.w_0"))
    a = layers.reshape(layers.scale(a, scale=1.0 - lam0), [b, s, h * d])
    return proj(a, cfg.hidden_size, name + ".o", cfg, bias=True), kv


def attention(u, cfg, name, window=0, rope_theta=0.0, rope_scaling=None,
              gated=False, rotary_dim=0, qk_norm=True, out_std=None,
              diffusion_block=0, scale=None):
    """Causal attention of `num_attention_heads` query heads over
    `num_key_value_heads` key/value heads of `head_dim`, u [b, s, hidden]
    to [b, s, hidden]: q and k normed over a head's width (one weight of
    `head_dim` each; not with `qk_norm` False; with `qk_norm`
    "projection" over the whole projection before the heads are cut, one
    weight and one statistic over all the heads' lanes, as OLMo's norm:
    two ordinary `rms_norm` ops), turned by rotary positions
    where `rope_theta` is not 0 (`rope_scaling`: a YaRN group;
    `rotary_dim` not 0: the first `rotary_dim` lanes of a head alone),
    `window` keys wide where it is not 0, and with `gated` the output
    times `sigmoid(W_g u)` before the output projection. The scores are
    multiplied by `scale` (None: `head_dim ** -0.5`; Granite's
    `attention_multiplier` is a number of its own). The heads are the
    ones held here, which may be a share of the model's.

    `diffusion_block` B > 0: u holds each sequence twice, L = s / 2 noisy
    rows and then its L clean rows, and the mask is block diffusion's
    over blocks of B (`layers.fused_multihead_attention`) in place of the
    causal one; both copies count positions 0..L-1. Counters, once a
    layer built: `diffusion_layers`, `attn_pairs_admitted` (the three
    rectangles' pairs: b (L B + (L^2 - L B) / 2 + (L^2 + L B) / 2)) and
    `attn_pairs_causal` (b s (s + 1) / 2, the doubled row's); gauge
    `diffusion_block_length`."""
    b, s, _ = u.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    def heads(which, n):
        t = proj(u, n * d, f"{name}.{which}", cfg)
        if qk_norm == "projection" and which != "v":
            t = norm(t, f"{name}.{which}_norm", cfg)
        return layers.reshape(t, [b, s, n, d])

    q, k, v = heads("q", h), heads("k", g), heads("v", g)
    if gated:
        gate = layers.sigmoid(proj(u, h * d, name + ".gate", cfg))
    # QK-norm and the positions inside the attention op, where they and
    # the kernel's head-major write are one pass over q and k
    prep = dict(rope_theta=rope_theta, rope_scaling=rope_scaling,
                rotary_dim=rotary_dim)
    if qk_norm is True:
        prep.update(q_norm_attr=ParamAttr(name=name + ".q_norm.w_0"),
                    k_norm_attr=ParamAttr(name=name + ".k_norm.w_0"),
                    qk_norm_epsilon=cfg.rms_norm_eps)
    if diffusion_block:
        profiler.bump_counter("diffusion_layers")
        profiler.set_counter("diffusion_block_length", diffusion_block)
        profiler.bump_counter(
            "attn_pairs_admitted",
            b * cost.block_diffusion_pairs(s // 2, diffusion_block))
        profiler.bump_counter("attn_pairs_causal",
                              b * cost.admitted_pairs(s, s, causal=True))
        prep.update(diffusion_block=diffusion_block)
    a = layers.fused_multihead_attention(
        q, k, v, causal=not diffusion_block,
        sm_scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        layout="bshd", window=window, **prep)
    a = layers.reshape(a, [b, s, h * d])
    if gated:
        a = layers.elementwise_mul(a, gate)
    return proj(a, cfg.hidden_size, name + ".o", cfg, std=out_std)


def sparse_attention(u, cfg, name, rope_theta):
    """Causal attention whose keys a learned indexer chooses
    (DeepSeek-V3.2-Exp's sparse attention), u [b, s, hidden] to
    ([b, s, hidden], [b, s] float32, [b, s, s] int8): `attention`'s heads
    with QK-norm and rotary positions, each query reading only the
    `cfg.topk` keys at or before it that the indexer scores highest (every
    one where it has no more); beside the output the loss that trains the
    indexer, a number a query, and the selection itself.

    The indexer reads a detached copy of u: `cfg.indexer_num_heads` query
    heads of `cfg.indexer_head_dim` (`.indexer.q`) against one key head
    (`.indexer.k` through a LayerNorm, `.indexer.k_norm`), both turned by
    rotary positions over all their lanes, and a weight a head
    (`.indexer.w`); `sparse_index` scores every causal pair,
    `sparse_select` keeps the K largest a row, and the flash kernels take
    the selection as an admission for all the heads. `index_kl` holds the
    indexer's softmax over the kept keys against the attention's own
    probabilities averaged over the heads, held constant. So the
    indexer's parameters get `index_kl`'s gradient alone and everything
    else none of it: the selection passes no gradient.

    q and k are normed and turned inside the attention op, which hands
    them back head-major as it took them: `index_kl` reads those. The
    indexer's own heads, 64 lanes wide, are turned by `rotary_embedding`.
    Counters, once a layer built: `sparse_attn_layers`,
    `attn_pairs_admitted` (b * sum_t min(t + 1, K)) and
    `attn_pairs_causal` (b * s (s + 1) / 2)."""
    b, s, _ = u.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    hi, di, topk = cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.topk

    def heads(t, n, width):
        return layers.reshape(t, [b, s, n, width])

    def turned(t, n, width):
        return layers.rotary_embedding(heads(t, n, width), theta=rope_theta)

    q = heads(proj(u, h * d, name + ".q", cfg), h, d)
    k = heads(proj(u, g * d, name + ".k", cfg), g, d)
    v = heads(proj(u, g * d, name + ".v", cfg), g, d)

    detached = layers.assign(u)
    detached.stop_gradient = True
    qi = turned(proj(detached, hi * di, name + ".indexer.q", cfg), hi, di)
    ki = turned(layer_norm(proj(detached, di, name + ".indexer.k", cfg),
                           name + ".indexer.k_norm", cfg), 1, di)
    w = proj(detached, hi, name + ".indexer.w", cfg)
    index = layers.sparse_index(qi, ki, w, scale=(hi * di) ** -0.5)
    admit, _ = layers.sparse_select(index, topk)

    sm_scale = 1.0 / math.sqrt(d)
    a, lse, q_taken, k_taken = layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=sm_scale, layout="bshd", admit=admit,
        admit_keys=topk, return_lse=True, return_prepared=True,
        q_norm_attr=ParamAttr(name=name + ".q_norm.w_0"),
        k_norm_attr=ParamAttr(name=name + ".k_norm.w_0"),
        qk_norm_epsilon=cfg.rms_norm_eps, rope_theta=rope_theta)
    kl = layers.index_kl(q_taken, k_taken, lse, index, admit, sm_scale,
                         admit_keys=topk)
    profiler.bump_counter("sparse_attn_layers")
    profiler.bump_counter("attn_pairs_admitted", b * cost.admitted_pairs(
        s, s, causal=True, window=topk))
    profiler.bump_counter("attn_pairs_causal",
                          b * cost.admitted_pairs(s, s, causal=True))
    return proj(layers.reshape(a, [b, s, h * d]), cfg.hidden_size,
                name + ".o", cfg), kl, admit


def latent_attention(u, cfg, name):
    """Causal latent attention (DeepSeek-V2/V3's MLA, arXiv:2412.19437
    section 2.1.1) as it trains, u [b, s, hidden] to [b, s, hidden]:
    `num_attention_heads` heads whose queries and keys are
    `qk_nope_head_dim + qk_rope_head_dim` wide and whose values are
    `v_head_dim` wide. Keys and values come up from one `kv_lora_rank`
    latent through an RMSNorm (`.kv_a`, `.kv_a_norm`, `.kv_b`); the
    `qk_rope_head_dim` lanes of the key that `.kv_a` writes beside the
    latent are one part for all the heads. Where `cfg.q_lora_rank` is
    set the query is compressed too (`.q_a`, `.q_a_norm`, `.q_b`),
    otherwise it is one projection (`.q`). Where `cfg.rope_theta` is not
    0 the last `qk_rope_head_dim` lanes of each query head and the shared
    key part are turned by rotary positions 0..s-1, by pairs of
    neighbouring lanes if `cfg.rope_interleave`; the other lanes carry no
    position. Scores scale by the whole width's root."""
    b, s, _ = u.shape
    h = cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def turned(t):
        return layers.rotary_embedding(t, theta=cfg.rope_theta,
                                       interleaved=cfg.rope_interleave)

    if cfg.q_lora_rank:
        c_q = norm(proj(u, cfg.q_lora_rank, name + ".q_a", cfg),
                   name + ".q_a_norm", cfg)
        q = proj(c_q, h * (dn + dr), name + ".q_b", cfg)
    else:
        q = proj(u, h * (dn + dr), name + ".q", cfg)
    q = layers.reshape(q, [b, s, h, dn + dr])
    if cfg.rope_theta:
        q_n, q_r = layers.split(q, [dn, dr], dim=3)
        q = layers.concat([q_n, turned(q_r)], axis=3)
    c, k_r = layers.split(proj(u, cfg.kv_lora_rank + dr, name + ".kv_a", cfg),
                          [cfg.kv_lora_rank, dr], dim=2)
    kv = layers.reshape(
        proj(norm(c, name + ".kv_a_norm", cfg), h * (dn + dv),
             name + ".kv_b", cfg), [b, s, h, dn + dv])
    k_n, v = layers.split(kv, [dn, dv], dim=3)
    # the one dr-wide key part, the same for every head: turned once, as
    # one head, and its gradient comes back summed over the heads
    k_r = layers.reshape(k_r, [b, s, 1, dr])
    if cfg.rope_theta:
        k_r = turned(k_r)
    k = layers.concat([k_n, layers.expand(k_r, [1, 1, h, 1])], axis=3)
    o = layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(dn + dr),
        layout="bshd", q_lora_rank=cfg.q_lora_rank or 0)
    return proj(layers.reshape(o, [b, s, h * dv]), cfg.hidden_size,
                name + ".o", cfg)


def expert_ffn(u, cfg, name, norm_eps=0.0, out_std=None):
    """Returns (what the shared expert and the held experts add, load).
    `norm_eps`: added to the sum the selected scores are divided by.
    Where `cfg.shared_expert_gate` is set (absent: not) the shared
    expert's output is multiplied by `sigmoid(w_sg . u)`, one number a
    token from a projection of width 1 (`.shared_gate`; counter
    `moe_shared_expert_gated`, once a layer built).

    Where `cfg.moe_latent_size` is set (absent: not) the experts work in
    a latent of that width: the router and the shared expert read u, the
    experts `W_lat_in u` (`.latent_in`, `moe_experts`' second input), and
    what they give comes back through `W_lat_out` (`.latent_out`). Where
    `cfg.expert_form` is "relu2" (absent: SiLU-gated) the experts and the
    shared expert are `W_down relu(W_up x)^2` with no gate (counter
    `moe_experts_ungated`, once a layer built). `out_std` seeds the last
    product of each path that ends outside the experts."""
    latent = getattr(cfg, "moe_latent_size", 0)
    form = getattr(cfg, "expert_form", "silu_gated")
    more = {}
    if latent:
        more["experts_input"] = proj(u, latent, name + ".latent_in", cfg)
    if form != "silu_gated":
        profiler.bump_counter("moe_experts_ungated")
        more["expert_form"] = form
    routed, load = layers.moe_experts(
        u, experts_total=cfg.num_experts, experts_held=cfg.experts_held,
        d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_token,
        held_from=cfg.held_from, scaling=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, bias_scale=cfg.router_bias_scale,
        param_attr=attr(name + ".moe", cfg),
        score_func=cfg.score_func, norm_eps=norm_eps, **more)
    if latent:
        routed = proj(routed, cfg.hidden_size, name + ".latent_out", cfg,
                      std=out_std)
    if not cfg.num_shared_experts:
        return routed, load
    width = cfg.moe_intermediate_size * cfg.num_shared_experts
    shared = (relu2_ffn(u, width, name + ".shared", cfg, out_std)
              if form == "relu2" else ffn(u, width, name + ".shared", cfg))
    if getattr(cfg, "shared_expert_gate", False):
        profiler.bump_counter("moe_shared_expert_gated")
        shared = layers.elementwise_mul(
            shared, layers.sigmoid(proj(u, 1, name + ".shared_gate", cfg)))
    return layers.elementwise_add(shared, routed), load
